"""Memory-placement subsystem: capability probe + offload policy.

The reference's headline capability is 10B-scale training on small
device footprints via DeepSpeed optimizer/param offload (the "1.3B
finetune in 7 GB" recipe, reference: fengshen/examples/classification/
demo_classification_afqmc_erlangshen_offload.sh). ZeRO-Offload (arxiv
2101.06840) and ZeRO-Infinity (arxiv 2104.07857) show host-memory
placement of optimizer state and master weights buys 10-100x larger
models per chip — but only when the runtime actually HAS the memory
kind the placement asks for. `with_memory_kind("pinned_host")` raises
at sharding construction on backends without that space (this repo's
CPU tier-1 backend exposes only `unpinned_host`), which is exactly how
the offload bench rungs died from seed through PR 8.

Two pieces fix that for good (docs/offload.md):

- **`probe_memory_capabilities()`** — detects, once per process, which
  memory kinds (`pinned_host` / `unpinned_host`) the live backend
  supports by attempting a sharding construction + a tiny transfer,
  plus the device/host byte budgets when the runtime reports them.
  The probe is plain host code between jit boundaries — it never runs
  inside a traced program (gated by the fslint clean-fixture test).
- **`OffloadPolicy`** — given the probe, the model's byte footprint
  (from `jax.eval_shape`, so no buffers are materialised), and the
  `--offload` flag, decides WHERE optimizer moments, master/param
  copies, and streamed parameters live. Levels form a ladder

      none -> opt -> opt_master -> stream

  and every level degrades gracefully DOWN the ladder when the memory
  kind it needs is unsupported, with one loud log line stating the
  chosen placement and why. `--offload_memory_kind` overrides the
  probe's host-kind choice; forcing an unsupported kind raises instead
  of silently degrading (an explicit override is a statement of fact
  about the hardware — being wrong about it must be loud).

The resolved policy feeds the TrainState shardings
(`create_sharded_state` / `offload_opt_state_shardings`), the
offloaded two-program step (`Trainer._build_offloaded_train_step`),
the streamed engine's `moments_dtype` knob (`StreamedAdamW`), and the
observability gauges
(`fstpu_offload_level`, `fstpu_memory_kind_supported{kind}`,
`fstpu_offload_host_bytes`).
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Callable, Dict, Optional

#: the offload ladder, least to most aggressive; index = the numeric
#: value of the `fstpu_offload_level` gauge
OFFLOAD_LEVELS = ("none", "opt", "opt_master", "stream")

#: host memory kinds worth probing, preference order: pinned host
#: memory DMA-streams to the accelerator without a bounce buffer, so
#: it wins whenever the backend has it
HOST_MEMORY_KINDS = ("pinned_host", "unpinned_host")

#: fraction of the reported device budget the placement math may plan
#: against — the rest is headroom for activations/fragmentation
DEVICE_BUDGET_FRACTION = 0.9

#: fp32 adam moments (m + v) cost 8 bytes/param — the term that
#: decides whether a host-resident optimizer fits host RAM
#: (docs/offload.md has the sizing table)
MOMENT_BYTES_PER_PARAM_FP32 = 8


@dataclasses.dataclass(frozen=True)
class MemoryCapabilities:
    """What the live backend can actually place where."""

    backend: str
    device_count: int
    #: kind -> probed support (sharding construction + tiny transfer)
    supported: Dict[str, bool]
    #: the device's DEFAULT memory kind ("device" on TPU/GPU,
    #: "unpinned_host" on the CPU backend) — the safe target for
    #: "bring it back on-device" shardings; `with_memory_kind("device")`
    #: raises on backends whose default space has another name
    device_memory_kind: str
    #: per-device byte budget (memory_stats()["bytes_limit"]); None
    #: when the runtime does not report one (CPU backend)
    device_bytes: Optional[int]
    #: host RAM (sysconf); None when unavailable
    host_bytes: Optional[int]

    def supports(self, kind: str) -> bool:
        return bool(self.supported.get(kind, False))

    @property
    def host_kind(self) -> Optional[str]:
        """Preferred host memory kind, or None when the backend has no
        addressable host space distinct from probing failures."""
        for kind in HOST_MEMORY_KINDS:
            if self.supports(kind):
                return kind
        return None

    def describe(self) -> dict:
        return {
            "backend": self.backend,
            "device_count": self.device_count,
            "supported": dict(sorted(self.supported.items())),
            "device_memory_kind": self.device_memory_kind,
            "device_bytes": self.device_bytes,
            "host_bytes": self.host_bytes,
        }


def _kind_supported(kind: str, device: Any) -> bool:
    """One probe attempt: construct a sharding with `kind` and move 8
    bytes through it. Construction raising (how this jax build reports
    a missing memory space) and transfer failures both read as
    unsupported."""
    import jax
    import numpy as np

    try:
        sharding = jax.sharding.SingleDeviceSharding(device,
                                                     memory_kind=kind)
        x = jax.device_put(np.ones((8,), np.uint8), sharding)
        jax.block_until_ready(x)
        return True
    except Exception:  # noqa: BLE001 — any failure means "do not
        # place data there"; the probe exists to turn the crash into
        # a capability bit
        return False


def _host_ram_bytes() -> Optional[int]:
    import os
    try:
        return int(os.sysconf("SC_PAGE_SIZE") *
                   os.sysconf("SC_PHYS_PAGES"))
    except (ValueError, OSError, AttributeError):
        return None


def _device_budget_bytes(device: Any) -> Optional[int]:
    try:
        stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — absent stats = unknown budget
        return None
    if not stats:
        return None
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    return int(limit) if limit else None


#: (backend, device_count) -> MemoryCapabilities; probing costs a few
#: tiny transfers, and every placement decision consults it
_PROBE_CACHE: Dict[tuple, MemoryCapabilities] = {}


def probe_memory_capabilities(refresh: bool = False) -> MemoryCapabilities:
    """Detect the live backend's memory kinds + byte budgets, cached
    per process (keyed by backend + device count so a test that swaps
    backends re-probes)."""
    import jax

    devices = jax.devices()
    cache_key = (jax.default_backend(), len(devices))
    if not refresh and cache_key in _PROBE_CACHE:
        return _PROBE_CACHE[cache_key]
    device = devices[0]
    caps = MemoryCapabilities(
        backend=jax.default_backend(),
        device_count=len(devices),
        supported={kind: _kind_supported(kind, device)
                   for kind in HOST_MEMORY_KINDS},
        device_memory_kind=device.default_memory().kind,
        device_bytes=_device_budget_bytes(device),
        host_bytes=_host_ram_bytes(),
    )
    _PROBE_CACHE[cache_key] = caps
    return caps


@dataclasses.dataclass(frozen=True)
class OffloadPolicy:
    """A resolved placement decision (see module docstring).

    `level` is what actually runs; `requested` is what the flag asked
    for — they differ exactly when the ladder degraded (unsupported
    memory kind, no host space, a trainer that cannot stream) and
    `reason` says why.
    """

    requested: str
    level: str
    #: host memory kind for the adam moments between steps; None when
    #: they stay on-device (level "none")
    opt_state_kind: Optional[str]
    #: host memory kind for master/param copies between steps; None
    #: below level "opt_master"
    master_kind: Optional[str]
    #: storage dtype for streamed adam moments (StreamedAdamW knob);
    #: None keeps param-dtype bit-parity with monolithic optax
    moments_dtype: Optional[str]
    reason: str
    caps: MemoryCapabilities

    @property
    def offloads_opt_state(self) -> bool:
        return self.opt_state_kind is not None

    @property
    def offloads_params(self) -> bool:
        return self.master_kind is not None

    @property
    def level_index(self) -> int:
        return OFFLOAD_LEVELS.index(self.level)

    def describe(self) -> dict:
        return {
            "requested": self.requested,
            "level": self.level,
            "opt_state_kind": self.opt_state_kind,
            "master_kind": self.master_kind,
            "moments_dtype": self.moments_dtype,
            "reason": self.reason,
            "memory_kinds": dict(sorted(self.caps.supported.items())),
        }


def _tree_bytes(tree: Any) -> int:
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * \
            np.dtype(dtype).itemsize
    return total


def state_byte_footprint(abstract_state: Any) -> tuple[int, int]:
    """(params_bytes, opt_state_bytes) of a TrainState eval_shape —
    the placement math's inputs, computed without materialising a
    single buffer."""
    return (_tree_bytes(getattr(abstract_state, "params", None)),
            _tree_bytes(getattr(abstract_state, "opt_state", None)))


def offload_request_from_args(args: Any) -> str:
    """The `--offload` / legacy `--offload_optimizer` flag surface,
    reduced to one request string. An explicit `--offload` wins; the
    deprecated boolean maps to "opt" only when `--offload` kept its
    "auto" default."""
    request = str(getattr(args, "offload", "auto") or "auto")
    if request == "auto" and getattr(args, "offload_optimizer", False):
        return "opt"
    return request


def resolve_offload_policy(request: str = "auto", *,
                           params_bytes: Optional[int] = None,
                           opt_bytes: Optional[int] = None,
                           abstract_state: Any = None,
                           memory_kind: str = "auto",
                           moments_dtype: Optional[str] = None,
                           can_stream: bool = True,
                           state_shard_ways: Optional[int] = None,
                           caps: Optional[MemoryCapabilities] = None,
                           log: Optional[Callable[[dict], None]] = None
                           ) -> OffloadPolicy:
    """Turn a request (`auto|none|opt|opt_master|stream`) into a
    concrete placement against the probed capabilities.

    The auto heuristic plans against ``DEVICE_BUDGET_FRACTION`` of the
    reported per-device budget times ``state_shard_ways`` — the number
    of ways ONE replica of the training state is actually sharded
    (fsdp x tensor x pipe for the Trainer's mesh; data/sequence axes
    REPLICATE the state, so counting them would overestimate capacity
    by the DP factor and under-offload). Defaults to the device count
    (fully sharded) when the caller has no mesh. Grads are costed at
    one param-sized tree:

    - params + grads + moments fit -> none
    - params + grads fit           -> opt (given a host kind)
    - otherwise                    -> stream (the only level that
      bounds the PER-STEP peak; opt_master only lowers between-step
      residency, so auto picks it solely as the best effort when the
      entry point cannot stream)

    With no reported budget (the CPU backend) auto picks "none":
    nothing indicates pressure, and the non-offloaded step is the fast
    path. Explicit levels keep their placement when the kinds exist and
    fall DOWN the ladder loudly when they don't; `can_stream=False`
    (the standard Trainer, which has no per-layer stream spec) demotes
    "stream" to "opt_master".

    `moments_dtype`: None lets the policy auto-suggest bfloat16 moment
    storage for "stream" when fp32 moments would dwarf host RAM;
    "param" explicitly demands param-dtype storage (bit-parity with
    monolithic optax — never auto-upgraded); any other dtype string is
    passed through.
    """
    if caps is None:
        caps = probe_memory_capabilities()
    if request not in ("auto",) + OFFLOAD_LEVELS:
        raise ValueError(
            f"unknown offload request {request!r}; expected one of "
            f"{('auto',) + OFFLOAD_LEVELS}")
    if abstract_state is not None:
        sized = state_byte_footprint(abstract_state)
        params_bytes = sized[0] if params_bytes is None else params_bytes
        opt_bytes = sized[1] if opt_bytes is None else opt_bytes

    # the host kind every offloading level places into
    if memory_kind not in ("auto",) + HOST_MEMORY_KINDS:
        raise ValueError(
            f"unknown --offload_memory_kind {memory_kind!r}; expected "
            f"one of {('auto',) + HOST_MEMORY_KINDS}")
    if memory_kind != "auto":
        if not caps.supports(memory_kind):
            raise ValueError(
                f"--offload_memory_kind={memory_kind} forced, but the "
                f"{caps.backend} backend does not support it (probed "
                f"kinds: {caps.describe()['supported']}); drop the "
                "override to let the probe pick")
        host_kind = memory_kind
        kind_why = f"forced by --offload_memory_kind={memory_kind}"
    else:
        host_kind = caps.host_kind
        kind_why = f"probe picked {host_kind}" if host_kind else \
            "no host memory kind supported"

    level, reason = _resolve_level(request, caps, host_kind,
                                   params_bytes, opt_bytes, can_stream,
                                   state_shard_ways)
    if level not in ("none", "stream") and host_kind is None:
        # nothing to place jax shardings INTO: opt/opt_master collapse.
        # "stream" is exempt — the streamed engine parks state as host
        # numpy (trainer/param_streaming.py) and needs no jax memory
        # kind, so it keeps its level (and its moments_dtype knob)
        reason = (f"requested {request!r} but the {caps.backend} "
                  "backend supports no host memory kind — running "
                  "without offload")
        level = "none"

    if moments_dtype == "param":
        # EXPLICIT bit-parity demand: param-dtype storage, never
        # auto-upgraded (the streamed drivers' flag contract)
        resolved_moments = None
    else:
        resolved_moments = moments_dtype
        if level == "stream" and resolved_moments is None and \
                opt_bytes and caps.host_bytes and \
                opt_bytes > caps.host_bytes // 2:
            # fp32 m+v would eat more than half of host RAM: halve the
            # moment storage (update math stays fp32 in StreamedAdamW)
            resolved_moments = "bfloat16"
            reason += ("; moments_dtype=bfloat16 (fp32 moments "
                       f"{opt_bytes >> 30} GiB > half of host RAM)")

    policy = OffloadPolicy(
        requested=request, level=level,
        opt_state_kind=host_kind if level != "none" else None,
        master_kind=host_kind
        if level in ("opt_master", "stream") else None,
        moments_dtype=resolved_moments if level == "stream" else None,
        reason=f"{reason} ({kind_why})",
        caps=caps)
    _announce(policy, log)
    return policy


def _resolve_level(request: str, caps: MemoryCapabilities,
                   host_kind: Optional[str],
                   params_bytes: Optional[int],
                   opt_bytes: Optional[int],
                   can_stream: bool,
                   state_shard_ways: Optional[int] = None
                   ) -> tuple[str, str]:
    if request == "none":
        return "none", "offload disabled by flag"
    if request == "auto":
        if not params_bytes or caps.device_bytes is None:
            return "none", ("auto: no device byte budget reported — "
                            "assuming everything fits")
        ways = max(1, min(int(state_shard_ways or caps.device_count),
                          caps.device_count))
        budget = caps.device_bytes * ways * DEVICE_BUDGET_FRACTION
        opt = opt_bytes or 0
        grads = params_bytes  # one param-sized tree during the step
        if params_bytes + grads + opt <= budget:
            return "none", (
                f"auto: params+grads+moments "
                f"{(params_bytes + grads + opt) >> 20} MiB fit the "
                f"{int(budget) >> 20} MiB device budget "
                f"({ways}-way sharded state)")
        if params_bytes + grads <= budget:
            # only the moments overflow the budget
            if host_kind is not None:
                return "opt", (
                    f"auto: moments ({opt >> 20} MiB) overflow the "
                    "device budget — parking them in host memory")
            # no jax host kind to park them in: "opt" cannot help;
            # streaming (host numpy) still can
            if can_stream:
                return "stream", (
                    f"auto: moments ({opt >> 20} MiB) overflow the "
                    "device budget and the backend has no host memory "
                    "kind for level 'opt' — per-layer streaming "
                    "instead")
            return "none", (
                f"auto: moments ({opt >> 20} MiB) overflow the device "
                "budget, but the backend has no host memory kind and "
                "this path cannot stream — running without offload "
                "(may OOM)")
        # past this point the PER-STEP peak (params+grads during the
        # gradient pass) overflows: opt_master only lowers BETWEEN-step
        # residency, not the peak, so auto never picks it as a fit —
        # per-layer streaming is the only level that bounds the peak
        if can_stream:
            return "stream", (
                f"auto: params+grads ({(params_bytes + grads) >> 20} "
                "MiB) overflow the device budget — per-layer streaming "
                "is the only level that bounds the per-step peak")
        return "opt_master", (
            f"auto: params+grads ({(params_bytes + grads) >> 20} MiB) "
            "overflow the device budget and this path cannot stream — "
            "opt_master is the deepest available level (best effort: "
            "between-step residency drops, but the per-step peak may "
            "still not fit)")
    if request == "stream" and not can_stream:
        return "opt_master", (
            "requested 'stream' but this entry point has no per-layer "
            "stream spec (use the --offload_params drivers, "
            "docs/offload.md) — degrading to opt_master")
    return request, f"explicit --offload={request}"


def _announce(policy: OffloadPolicy,
              log: Optional[Callable[[dict], None]]) -> None:
    """THE loud line: every resolved placement states itself and why,
    through the structured sink when one exists, stderr otherwise."""
    if log is not None:
        log({"event": "offload_policy", **policy.describe()})
        return
    print(f"[fengshen-tpu] offload policy: level={policy.level} "
          f"(requested={policy.requested}) "
          f"opt_state->{policy.opt_state_kind or 'device'} "
          f"master->{policy.master_kind or 'device'} — {policy.reason}",
          file=sys.stderr, flush=True)


def record_offload_metrics(policy: OffloadPolicy,
                           host_resident_bytes: Optional[int] = None,
                           registry: Any = None) -> None:
    """Export the placement to /metrics (docs/observability.md):
    `fstpu_offload_level` (ladder index), per-kind support bits, and
    the host-resident byte gauge. Host-side only — called once per fit,
    never from traced code."""
    from fengshen_tpu.observability import get_registry

    reg = registry if registry is not None else get_registry()
    reg.gauge("fstpu_offload_level",
              "resolved offload ladder level "
              "(0=none 1=opt 2=opt_master 3=stream)"
              ).set(float(policy.level_index))
    supported = reg.gauge("fstpu_memory_kind_supported",
                          "1 when the probed backend supports placing "
                          "data in this memory kind",
                          labelnames=("kind",))
    for kind in HOST_MEMORY_KINDS:
        supported.labels(kind).set(1.0 if policy.caps.supports(kind)
                                   else 0.0)
    if host_resident_bytes is not None:
        reg.gauge("fstpu_offload_host_bytes",
                  "bytes of training state parked in host memory "
                  "between steps").set(float(host_resident_bytes))
