"""Kernel layer: registry + capability probe + dispatch seam.

Before this, the two Pallas kernels in this package (flash_attention,
block_sparse_attention) were orphans — each caller re-derived "can the
backend run Mosaic?" from ``jax.default_backend()`` inline, and the
decision never reached logs or metrics. Now every
fused kernel registers BOTH implementations here:

- ``pallas`` — the Mosaic TPU kernel (fengshen_tpu.ops.pallas.*);
- ``xla``    — the stock lowering the kernel replaces, numerically
  identical by construction so CPU tier-1 can pin parity.

and callers route through one seam:

- :func:`probe` — cached capability probe (same shape as the offload
  ladder's ``probe_memory_capabilities``): is this backend able to run
  Mosaic kernels at all?  ``FSTPU_KERNEL_FORCE=xla|pallas`` overrides
  for benchmarking / debugging.  Cached per (backend, force) so the
  decision is made ONCE per process — dispatch inside a traced function
  reads a python bool, never a runtime branch, so it is not a
  retrace hazard.
- :func:`kernel_choice` — the per-op decision (``"pallas"`` or
  ``"xla"``), and :func:`get_kernel` to fetch the callable.
- :func:`resolve_dispatch` — what each TRACED call site actually
  takes: the table says which implementation an op prefers on this
  backend, but a seam still routes a shape its kernel cannot tile to
  the xla lowering. Every seam makes that choice (and records why)
  here at trace time, so it is never made without a word.
- :func:`log_dispatch` — THE loud line: the table, the probe, and the
  call sites traced so far, plus the ``fstpu_kernel_dispatch{op,impl}``
  gauge.

See docs/kernels.md for the dispatch ladder and the
writing-a-kernel checklist.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Callable, Dict, Optional

KERNEL_DISPATCH_METRIC = "fstpu_kernel_dispatch"

#: env override: "xla" benches the fallback on TPU, "pallas" forces the
#: kernels on (interpret-mode debugging); unset = probe the backend
FORCE_ENV = "FSTPU_KERNEL_FORCE"


@dataclasses.dataclass(frozen=True)
class KernelProbe:
    """One process-wide answer to "can this backend run Mosaic?"."""

    backend: str
    #: True when pl.pallas_call compiles to Mosaic on this backend —
    #: the per-op shape checks still apply on top of this
    pallas_tpu: bool
    #: the FSTPU_KERNEL_FORCE value when it decided, else None
    forced: Optional[str]
    reason: str

    def describe(self) -> dict:
        return {
            "backend": self.backend,
            "pallas_tpu": self.pallas_tpu,
            "forced": self.forced,
            "reason": self.reason,
        }


#: (backend, force-env) -> KernelProbe; keyed on the env var so a bench
#: that flips FSTPU_KERNEL_FORCE mid-process re-probes
_PROBE_CACHE: Dict[tuple, KernelProbe] = {}


def probe(refresh: bool = False) -> KernelProbe:
    """Cached capability probe. A backend that is not a TPU answers
    ``pallas_tpu=False`` with the reason and every op takes its xla
    lowering. On the ``tpu`` backend the kernels are the
    implementation, so a jax build whose pallas does not import is an
    error there, not a quiet change of lowering."""
    import jax

    forced = os.environ.get(FORCE_ENV, "").strip().lower() or None
    backend = jax.default_backend()
    cache_key = (backend, forced)
    if not refresh and cache_key in _PROBE_CACHE:
        return _PROBE_CACHE[cache_key]
    if forced == "xla":
        result = KernelProbe(backend, False, forced,
                             f"{FORCE_ENV}=xla pins the stock lowering")
    elif forced == "pallas":
        result = KernelProbe(backend, True, forced,
                             f"{FORCE_ENV}=pallas pins the Mosaic "
                             "kernels (off-TPU they must be run in "
                             "interpret mode or will fail at call time)")
    elif backend != "tpu":
        result = KernelProbe(backend, False, None,
                             f"backend={backend} cannot compile Mosaic "
                             "kernels; xla lowering (CPU tier-1 pins "
                             "parity against it)")
    else:
        from jax.experimental import pallas as _pl  # noqa: F401
        from jax.experimental.pallas import tpu as _pltpu  # noqa: F401
        result = KernelProbe(backend, True, None,
                             "tpu backend + pallas importable")
    _PROBE_CACHE[cache_key] = result
    return result


#: op -> {"pallas": fn, "xla": fn}; both impls of one op take the same
#: signature and agree numerically (the parity tests pin it)
_REGISTRY: Dict[str, Dict[str, Callable]] = {}


def register_kernel(op: str, impl: str, fn: Callable) -> Callable:
    """Register one implementation of ``op``; returns ``fn`` so it can
    be used as a decorator tail."""
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be 'pallas' or 'xla', got {impl!r}")
    _REGISTRY.setdefault(op, {})[impl] = fn
    return fn


def kernel_choice(op: str) -> str:
    """The dispatch decision for ``op``: ``"pallas"`` when the probe
    says the backend can run Mosaic AND the op registered a pallas
    impl, else ``"xla"``."""
    impls = _REGISTRY.get(op, {})
    if probe().pallas_tpu and "pallas" in impls:
        return "pallas"
    return "xla"


def get_kernel(op: str, impl: Optional[str] = None) -> Callable:
    """Fetch the callable for ``op`` (``impl=None`` = probed choice)."""
    impls = _REGISTRY.get(op)
    if not impls:
        raise KeyError(f"no kernel registered under {op!r}; "
                       f"known: {sorted(_REGISTRY)}")
    resolved = impl if impl is not None else kernel_choice(op)
    if resolved not in impls:
        raise KeyError(f"kernel {op!r} has no {resolved!r} impl; "
                       f"registered: {sorted(impls)}")
    return impls[resolved]


def dispatch_table() -> Dict[str, str]:
    """op -> chosen impl for every registered kernel."""
    return {op: kernel_choice(op) for op in sorted(_REGISTRY)}


#: (op, impl, detail) of every dispatch decision a seam took while
#: tracing, in first-seen order. Process-wide like the probe cache: the
#: seams are called from inside flax modules with no object to carry it.
_TRACED: Dict[tuple, None] = {}


def resolve_dispatch(op: str, detail: str,
                     ineligible: Optional[str]) -> str:
    """The implementation one traced call site of ``op`` takes:
    ``"pallas"`` when the backend can run Mosaic and the seam found
    nothing against the shape (``ineligible`` is None), else ``"xla"``.
    ``detail`` names the shapes. The decision is recorded with its
    reason, and on a Mosaic-capable backend each distinct one is also
    printed once, so an ineligible shape is never routed to the xla
    lowering in silence."""
    why = ineligible if probe().pallas_tpu else \
        "backend cannot run Mosaic"
    impl = "xla" if why else "pallas"
    key = (op, impl, f"{detail} ({why})" if why else detail)
    if key not in _TRACED:
        _TRACED[key] = None
        if probe().pallas_tpu:
            print(f"[fengshen-tpu] kernel dispatch: {op} -> {impl} "
                  f"[{key[2]}]", file=sys.stderr, flush=True)
    return impl


def traced_dispatch() -> list:
    """Every call-site decision recorded so far, oldest first."""
    return [{"op": op, "impl": impl, "detail": detail}
            for op, impl, detail in _TRACED]


def log_dispatch(log: Optional[Callable[[dict], None]] = None,
                 registry=None) -> Dict[str, str]:
    """THE loud line: state every kernel's dispatch decision (structured
    sink when one exists, stderr otherwise) with the call sites traced
    so far, and set the ``fstpu_kernel_dispatch{op,impl}`` gauge — 1
    for the chosen impl, 0 for the alternative, so a scraper can alert
    on a fleet that is not running its kernels. Returns the dispatch
    table."""
    from fengshen_tpu.observability.registry import get_registry

    info = probe()
    table = dispatch_table()
    gauge = (registry if registry is not None else get_registry()).gauge(
        KERNEL_DISPATCH_METRIC,
        "1 for each op's chosen kernel impl, 0 for the alternative",
        labelnames=("op", "impl"),
    )
    for op, chosen in table.items():
        for impl in ("pallas", "xla"):
            gauge.labels(op, impl).set(1 if impl == chosen else 0)
    if log is not None:
        log({"event": "kernel_dispatch", "table": table,
             "call_sites": traced_dispatch(), **info.describe()})
    else:
        summary = " ".join(f"{op}={impl}" for op, impl in table.items())
        print(f"[fengshen-tpu] kernel dispatch: {summary} "
              f"(backend={info.backend}) — {info.reason}",
              file=sys.stderr, flush=True)
    return table


def run_per_shard(kernel: Callable, q, k, v, *per_token):
    """Run an attention-shaped Mosaic kernel under a device mesh.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a
    shard_map"), so under a multi-device mesh the kernel runs inside a
    shard_map: batch over the batch axes and heads over ``tensor`` —
    attention is independent across both, so no collective is needed —
    with the sequence dimension whole. ``q/k/v`` are ``[B, S, H, D]``;
    ``per_token`` are ``[B, S]`` operands (segment ids) that shard like
    the batch. A dimension the axes do not divide stays replicated (the
    init pass runs batch 1). A call that is already inside a shard_map
    body (ring / Ulysses blocks, pipeline stages) sees local shards and
    runs the kernel as it is."""
    import jax
    from jax.sharding import PartitionSpec as P

    from fengshen_tpu.parallel.mesh import (BATCH_AXES, TENSOR_AXIS,
                                            get_mesh)
    mesh = get_mesh()
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return kernel(q, k, v, *per_token)
    n_batch = 1
    for axis in BATCH_AXES:
        n_batch *= mesh.shape[axis]
    tensor = mesh.shape[TENSOR_AXIS]
    batch_axes = BATCH_AXES if q.shape[0] % n_batch == 0 else None
    head_axis = TENSOR_AXIS if q.shape[2] % tensor == 0 and \
        k.shape[2] % tensor == 0 else None
    qkv = P(batch_axes, None, head_axis, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(qkv,) * 3 + (P(batch_axes, None),) * len(per_token),
        out_specs=qkv, check_vma=False)(q, k, v, *per_token)


# -- registrations ------------------------------------------------------
# Imported after the seam exists; the explicit register_kernel calls
# are kept here so the whole table is visible in one place.

from fengshen_tpu.ops.flash_attention import blockwise_attention  # noqa: E402
from fengshen_tpu.ops.gated_attention import folded_decode_walk  # noqa: E402
from fengshen_tpu.ops.gated_delta import xla_gated_delta_prefill  # noqa: E402
from fengshen_tpu.ops.latent_attention import latent_prefill_walk  # noqa: E402
from fengshen_tpu.ops.moe import xla_grouped_swiglu  # noqa: E402
# aliased: binding the bare function name here would shadow the
# `ops.pallas.block_sparse_attention` SUBMODULE attribute that
# `import fengshen_tpu.ops.pallas.block_sparse_attention as bsa` resolves
from fengshen_tpu.ops.pallas.block_sparse_attention import (  # noqa: E402
    block_sparse_attention as _block_sparse_attention)
from fengshen_tpu.ops.pallas.decode_attention import (  # noqa: E402
    decode_attention, pallas_decode_attention, pallas_decode_eligible,
    pallas_folded_decode_attention, pallas_mla_decode_attention,
    xla_decode_attention, xla_mla_decode_attention)
from fengshen_tpu.ops.pallas.flash_attention import (  # noqa: E402
    pallas_flash_attention)
from fengshen_tpu.ops.pallas.fused_ce import (  # noqa: E402
    fused_ce_loss, pallas_fused_ce, xla_fused_ce)
from fengshen_tpu.ops.pallas.gated_delta import (  # noqa: E402
    pallas_gated_delta_prefill)
from fengshen_tpu.ops.pallas.grouped_matmul import (  # noqa: E402
    pallas_grouped_swiglu)
from fengshen_tpu.ops.pallas.latent_attention import (  # noqa: E402
    pallas_latent_prefill_attention)

register_kernel("flash_attention", "pallas", pallas_flash_attention)
register_kernel("flash_attention", "xla", blockwise_attention)
register_kernel("block_sparse_attention", "pallas", _block_sparse_attention)
# block-sparse has no standalone xla twin here: the fallback (expand the
# layout to a dense mask) lives in ops.attention.dot_product_attention
register_kernel("decode_attention", "pallas", pallas_decode_attention)
register_kernel("decode_attention", "xla", xla_decode_attention)
# the same seam's folded entry (rows that hold a token's few, wide KV
# heads): its own kernel, its xla lowering the plain walk
register_kernel("folded_decode_attention", "pallas",
                pallas_folded_decode_attention)
register_kernel("folded_decode_attention", "xla", folded_decode_walk)
# the same seam's latent entry (one shared row a token, key and value
# at once): its own kernel over a paged pool, its xla lowering the
# gather of the lane
register_kernel("mla_decode_attention", "pallas", pallas_mla_decode_attention)
register_kernel("mla_decode_attention", "xla", xla_mla_decode_attention)
# latent attention's FULL form, a window of queries onto a lane of
# latent rows (the seam is `ops.latent_attention.latent_prefill_attention`);
# its xla lowering the `jax.numpy` walk
register_kernel("mla_prefill_attention", "pallas",
                pallas_latent_prefill_attention)
register_kernel("mla_prefill_attention", "xla", latent_prefill_walk)
# the chunked gated delta rule of a prefill window (the seam is
# `ops.gated_delta.gated_delta_prefill`); its xla lowering the
# `jax.numpy` chunked form
register_kernel("gated_delta_prefill", "pallas", pallas_gated_delta_prefill)
register_kernel("gated_delta_prefill", "xla", xla_gated_delta_prefill)
# the routed experts' three products over sorted rows (the seam is
# `ops.moe.grouped_swiglu`, by the call's shape); its xla lowering three
# `jax.lax.ragged_dot`
register_kernel("grouped_matmul", "pallas", pallas_grouped_swiglu)
register_kernel("grouped_matmul", "xla", xla_grouped_swiglu)
register_kernel("fused_ce", "pallas", pallas_fused_ce)
register_kernel("fused_ce", "xla", xla_fused_ce)

__all__ = [
    "KernelProbe", "probe", "register_kernel", "kernel_choice",
    "get_kernel", "dispatch_table", "log_dispatch",
    "resolve_dispatch", "traced_dispatch", "run_per_shard",
    "decode_attention", "xla_decode_attention", "pallas_decode_attention",
    "pallas_decode_eligible", "fused_ce_loss", "pallas_fused_ce",
    "xla_fused_ce", "pallas_flash_attention",
    "KERNEL_DISPATCH_METRIC", "FORCE_ENV",
]
