"""Memory-placement subsystem (docs/offload.md): capability probe +
offload policy + /metrics gauges.

Fast lane, model-free by design (ISSUE 9 satellite): everything here is
probe plumbing and placement math — the multi-layer parity fits live in
tests/test_trainer.py (slow lane).
"""

import dataclasses

import jax
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fengshen_tpu.trainer import memory as mem
from fengshen_tpu.trainer.memory import (HOST_MEMORY_KINDS,
                                         OFFLOAD_LEVELS,
                                         MemoryCapabilities,
                                         probe_memory_capabilities,
                                         record_offload_metrics,
                                         resolve_offload_policy)


def _fake_caps(pinned=True, unpinned=True, device_bytes=None,
               host_bytes=None, device_count=4,
               device_memory_kind="device"):
    return MemoryCapabilities(
        backend="fake", device_count=device_count,
        supported={"pinned_host": pinned, "unpinned_host": unpinned},
        device_memory_kind=device_memory_kind,
        device_bytes=device_bytes, host_bytes=host_bytes)


# ---- the probe ------------------------------------------------------


def test_probe_reports_this_backends_kinds():
    """The probe agrees with what the live device itself lists — not
    with what one jax build's CPU backend happened to offer (0.4.37 had
    no pinned_host; 0.9.0 has both host kinds)."""
    caps = probe_memory_capabilities(refresh=True)
    assert caps.backend == "cpu"  # conftest pins the CPU mesh
    device = jax.devices()[0]
    listed = {m.kind for m in device.addressable_memories()}
    for kind in HOST_MEMORY_KINDS:
        assert caps.supported[kind] is (kind in listed), (kind, listed)
    assert caps.host_kind == next(
        (k for k in HOST_MEMORY_KINDS if k in listed), None)
    assert caps.device_memory_kind == device.default_memory().kind
    assert caps.device_bytes is None  # CPU reports no budget
    assert caps.host_bytes and caps.host_bytes > 0


def test_probe_is_cached_per_process(monkeypatch):
    calls = []
    real = mem._kind_supported

    def counting(kind, device):
        calls.append(kind)
        return real(kind, device)

    monkeypatch.setattr(mem, "_kind_supported", counting)
    first = probe_memory_capabilities(refresh=True)
    assert sorted(calls) == sorted(HOST_MEMORY_KINDS)
    again = probe_memory_capabilities()
    assert again is first
    assert len(calls) == len(HOST_MEMORY_KINDS)  # no re-probe


# ---- placement math (pure, fake capabilities) -----------------------


def test_auto_level_ladder_against_device_budget():
    gib = 1 << 30
    caps = _fake_caps(device_bytes=16 * gib, host_bytes=256 * gib)
    # params+grads+moments fit -> none
    p = resolve_offload_policy("auto", params_bytes=4 * gib,
                               opt_bytes=8 * gib, caps=caps)
    assert p.level == "none" and not p.offloads_opt_state
    # moments overflow -> opt
    p = resolve_offload_policy("auto", params_bytes=20 * gib,
                               opt_bytes=40 * gib, caps=caps)
    assert p.level == "opt" and p.opt_state_kind == "pinned_host"
    assert p.master_kind is None
    # params+grads overflow: the PER-STEP peak no longer fits, and
    # opt_master only lowers between-step residency — streaming is the
    # only level that bounds the peak, so auto goes straight there
    p = resolve_offload_policy("auto", params_bytes=40 * gib,
                               opt_bytes=80 * gib, caps=caps)
    assert p.level == "stream"
    # ...unless the entry point cannot stream (the standard Trainer):
    # opt_master is the best-effort deepest level, said so loudly
    p = resolve_offload_policy("auto", params_bytes=40 * gib,
                               opt_bytes=80 * gib, caps=caps,
                               can_stream=False)
    assert p.level == "opt_master"
    assert p.master_kind == "pinned_host"
    assert "best effort" in p.reason


def test_auto_budget_counts_only_state_sharding_ways():
    """Replication awareness: a pure-DP mesh replicates the state per
    replica, so capacity is device_bytes x (fsdp*tensor*pipe), NOT
    x device_count — counting every device would resolve 'none' on
    shapes that OOM."""
    gib = 1 << 30
    caps = _fake_caps(device_bytes=1 * gib, device_count=8)
    # 8-way sharded state (the default when no mesh info): 3 GiB of
    # params+grads+moments fit the 7.2 GiB budget
    p = resolve_offload_policy("auto", params_bytes=1 * gib,
                               opt_bytes=1 * gib, caps=caps)
    assert p.level == "none"
    # the SAME bytes on a pure-DP mesh (1-way sharded replica): only
    # 0.9 GiB of budget per replica — moments must offload
    p = resolve_offload_policy("auto", params_bytes=256 << 20,
                               opt_bytes=512 << 20, caps=caps,
                               state_shard_ways=1)
    assert p.level == "opt"
    # shard ways are clamped to the device count (a misreported mesh
    # must not inflate the budget past the hardware)
    p = resolve_offload_policy("auto", params_bytes=16 * gib,
                               opt_bytes=32 * gib, caps=caps,
                               state_shard_ways=1000)
    assert p.level != "none"


def test_auto_moments_only_overflow_without_host_kind():
    """When only the moments overflow and the backend has no host
    memory kind, 'opt' cannot help: a streaming-capable caller
    streams, a non-streaming one runs without offload (said loudly) —
    never a reason line claiming params+grads overflowed."""
    gib = 1 << 30
    caps = _fake_caps(pinned=False, unpinned=False,
                      device_bytes=1 * gib, device_count=4)
    # params+grads (2 GiB) fit the 3.6 GiB budget; moments (4 GiB) don't
    p = resolve_offload_policy("auto", params_bytes=1 * gib,
                               opt_bytes=4 * gib, caps=caps)
    assert p.level == "stream"
    assert "moments" in p.reason and "params+grads" not in p.reason
    p = resolve_offload_policy("auto", params_bytes=1 * gib,
                               opt_bytes=4 * gib, caps=caps,
                               can_stream=False)
    assert p.level == "none"
    assert "may OOM" in p.reason


def test_auto_without_budget_info_picks_none():
    p = resolve_offload_policy("auto", params_bytes=1 << 40,
                               opt_bytes=1 << 41,
                               caps=_fake_caps(device_bytes=None))
    assert p.level == "none"
    assert "budget" in p.reason


def test_fallback_ladder_without_pinned_host():
    caps = _fake_caps(pinned=False)
    p = resolve_offload_policy("opt", caps=caps)
    assert p.level == "opt"
    assert p.opt_state_kind == "unpinned_host"  # one rung down, loudly
    p = resolve_offload_policy("opt_master", caps=caps)
    assert (p.opt_state_kind, p.master_kind) == \
        ("unpinned_host", "unpinned_host")


def test_fallback_to_none_without_any_host_kind():
    caps = _fake_caps(pinned=False, unpinned=False)
    for request in ("opt", "opt_master"):
        p = resolve_offload_policy(request, caps=caps)
        assert p.level == "none", request
        assert p.opt_state_kind is None
        assert "no host memory kind" in p.reason
    # "stream" is exempt: the streamed engine parks state as host
    # NUMPY (trainer/param_streaming.py) and needs no jax memory kind,
    # so its level — and its moments_dtype knob — survive
    p = resolve_offload_policy("stream", caps=caps,
                               moments_dtype="bfloat16")
    assert p.level == "stream"
    assert p.moments_dtype == "bfloat16"
    # auto with a blown budget: opt can't help (no kind to park into),
    # so a streaming-capable entry point streams...
    tight = dataclasses.replace(caps, device_bytes=1 << 30)
    p = resolve_offload_policy("auto", params_bytes=1 << 40,
                               opt_bytes=1 << 40, caps=tight)
    assert p.level == "stream"
    # ...and a non-streaming one degrades to none rather than planning
    # jax-sharding placements against nothing
    p = resolve_offload_policy("auto", params_bytes=1 << 40,
                               opt_bytes=1 << 40, caps=tight,
                               can_stream=False)
    assert p.level == "none"


def test_stream_demotes_when_entry_point_cannot_stream():
    p = resolve_offload_policy("stream", caps=_fake_caps(),
                               can_stream=False)
    assert p.level == "opt_master"
    assert "stream" in p.reason


def test_explicit_memory_kind_override():
    # forcing a supported kind wins over the probe's preference
    p = resolve_offload_policy("opt", caps=_fake_caps(),
                               memory_kind="unpinned_host")
    assert p.opt_state_kind == "unpinned_host"
    # forcing an unsupported kind raises — never a silent degrade
    with pytest.raises(ValueError, match="offload_memory_kind"):
        resolve_offload_policy("opt", caps=_fake_caps(pinned=False),
                               memory_kind="pinned_host")
    with pytest.raises(ValueError, match="unknown"):
        resolve_offload_policy("opt", caps=_fake_caps(),
                               memory_kind="nvme")
    with pytest.raises(ValueError, match="unknown offload request"):
        resolve_offload_policy("zero3", caps=_fake_caps())


def test_stream_moments_dtype_is_a_policy_knob():
    gib = 1 << 30
    caps = _fake_caps(host_bytes=64 * gib)
    # fp32 moments dwarf host RAM -> bf16 storage suggested
    p = resolve_offload_policy("stream", params_bytes=26 * gib,
                               opt_bytes=104 * gib, caps=caps)
    assert p.moments_dtype == "bfloat16"
    # plenty of host RAM -> param-dtype bit-parity default
    p = resolve_offload_policy("stream", params_bytes=1 * gib,
                               opt_bytes=2 * gib, caps=caps)
    assert p.moments_dtype is None
    # an explicit dtype always wins
    p = resolve_offload_policy("stream", params_bytes=26 * gib,
                               opt_bytes=104 * gib, caps=caps,
                               moments_dtype="float32")
    assert p.moments_dtype == "float32"
    # "param" is the explicit bit-parity demand: NEVER auto-upgraded,
    # even when fp32 moments dwarf host RAM (the streamed drivers'
    # --offload_moments_dtype=param contract)
    p = resolve_offload_policy("stream", params_bytes=26 * gib,
                               opt_bytes=104 * gib, caps=caps,
                               moments_dtype="param")
    assert p.moments_dtype is None
    assert "bfloat16" not in p.reason


def test_announce_logs_the_placement_and_why():
    entries = []
    p = resolve_offload_policy("opt", caps=_fake_caps(pinned=False),
                               log=entries.append)
    assert entries and entries[0]["event"] == "offload_policy"
    assert entries[0]["level"] == p.level
    assert entries[0]["opt_state_kind"] == "unpinned_host"
    assert entries[0]["reason"]


# ---- TrainState wiring ----------------------------------------------


def _tiny_sharding_state(mesh):
    from fengshen_tpu.trainer.train_state import TrainState
    sh = NamedSharding(mesh, P())
    return TrainState(step=sh, params={"w": sh}, opt_state={"mu": sh},
                      apply_fn=lambda *a, **k: None, tx=optax.sgd(1e-3),
                      bad_step_count=sh)


def test_offload_opt_state_shardings_no_longer_raises():
    """THE seed failure (ROADMAP item 3): the default call resolved
    pinned_host unconditionally and raised at sharding construction on
    this backend. It now probes."""
    from fengshen_tpu.trainer.train_state import \
        offload_opt_state_shardings
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
    out = offload_opt_state_shardings(_tiny_sharding_state(mesh))
    kind = probe_memory_capabilities().host_kind
    assert out.opt_state["mu"].memory_kind == kind
    assert out.params["w"].memory_kind != "pinned_host"


def test_offload_opt_state_shardings_rejects_unsupported_kind(
        monkeypatch):
    """Forcing a kind the probe reports missing raises with the probe's
    findings; a kind it reports present is taken."""
    from fengshen_tpu.trainer.train_state import \
        offload_opt_state_shardings
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
    real = probe_memory_capabilities()
    present = real.host_kind
    placed = offload_opt_state_shardings(_tiny_sharding_state(mesh),
                                         memory_kind=present)
    assert {s.memory_kind for s in jax.tree_util.tree_leaves(
        placed.opt_state)} == {present}
    monkeypatch.setattr(
        mem, "probe_memory_capabilities", lambda refresh=False:
        _fake_caps(pinned=False, unpinned=True))
    with pytest.raises(ValueError, match="pinned_host"):
        offload_opt_state_shardings(_tiny_sharding_state(mesh),
                                    memory_kind="pinned_host")


def test_offload_request_from_args_flag_precedence():
    import argparse
    from fengshen_tpu.trainer.memory import offload_request_from_args
    ns = argparse.Namespace(offload="auto", offload_optimizer=False)
    assert offload_request_from_args(ns) == "auto"
    ns.offload_optimizer = True  # legacy bool maps to opt...
    assert offload_request_from_args(ns) == "opt"
    ns.offload = "none"          # ...but an explicit --offload wins
    assert offload_request_from_args(ns) == "none"


# ---- /metrics gauges ------------------------------------------------


def test_offload_gauges_pinned_exposition():
    """Pinned /metrics check (ISSUE 9 satellite): the exact exposition
    lines the new gauges render."""
    from fengshen_tpu.observability import (MetricsRegistry,
                                            render_prometheus)
    policy = resolve_offload_policy("opt", caps=_fake_caps(pinned=False))
    reg = MetricsRegistry()
    record_offload_metrics(policy, host_resident_bytes=4096,
                           registry=reg)
    text = render_prometheus(reg)
    assert 'fstpu_memory_kind_supported{kind="pinned_host"} 0' in text
    assert 'fstpu_memory_kind_supported{kind="unpinned_host"} 1' in text
    assert "fstpu_offload_host_bytes 4096" in text
    assert "fstpu_offload_level 1" in text  # opt = ladder index 1


def test_offload_gauge_level_indices_cover_the_ladder():
    from fengshen_tpu.observability import MetricsRegistry
    for i, lvl in enumerate(OFFLOAD_LEVELS):
        reg = MetricsRegistry()
        record_offload_metrics(
            resolve_offload_policy(lvl, caps=_fake_caps()), registry=reg)
        assert reg.get("fstpu_offload_level").value() == float(i)
