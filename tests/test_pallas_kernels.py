"""Kernel layer (fengshen_tpu.ops.pallas): the registry and the probe
behind every dispatch seam. The kernels' own parity cases live a seam a
file, `tests/test_pallas_*.py`, so that `--dist loadfile` spreads them.

Parity doctrine (docs/kernels.md): every Pallas kernel registers next
to the stock XLA lowering it replaces, the xla lowering is op-for-op
the pre-seam model code (so CPU tier-1 pins bit-identical decode), and
the Mosaic path is checked against it in interpret mode — the same
numerics the TPU kernel runs, executed on the CPU backend.
"""

import pytest

from fengshen_tpu.ops.pallas import (
    FORCE_ENV, dispatch_table, get_kernel, log_dispatch, probe)
from fengshen_tpu.ops.pallas.decode_attention import (
    pallas_decode_attention, pallas_folded_decode_attention,
    xla_decode_attention)


# -- registry + probe ---------------------------------------------------


def test_probe_cached_and_forceable(fresh_probe):
    info = probe(refresh=True)
    assert info.backend == "cpu"
    assert not info.pallas_tpu
    assert "cpu" in info.reason
    # cached: the second call answers from the dict, same object
    assert probe() is info

    fresh_probe.setenv(FORCE_ENV, "pallas")
    forced = probe()
    assert forced.pallas_tpu and forced.forced == "pallas"
    fresh_probe.setenv(FORCE_ENV, "xla")
    assert not probe().pallas_tpu


def test_dispatch_table_follows_the_probe(fresh_probe):
    table = dispatch_table()
    for op in ("decode_attention", "folded_decode_attention",
               "mla_decode_attention", "mla_prefill_attention", "fused_ce",
               "flash_attention", "block_sparse_attention",
               "gated_delta_prefill", "grouped_matmul"):
        assert table[op] == "xla"  # CPU backend: stock lowerings

    fresh_probe.setenv(FORCE_ENV, "pallas")
    assert dispatch_table()["decode_attention"] == "pallas"


def test_get_kernel_resolution(fresh_probe):
    assert get_kernel("decode_attention") is xla_decode_attention
    assert get_kernel("decode_attention",
                      "pallas") is pallas_decode_attention
    from fengshen_tpu.ops.gated_attention import folded_decode_walk
    assert get_kernel("folded_decode_attention") is folded_decode_walk
    assert get_kernel("folded_decode_attention",
                      "pallas") is pallas_folded_decode_attention
    with pytest.raises(KeyError):
        get_kernel("nonexistent_op")
    with pytest.raises(KeyError):
        # block-sparse's fallback lives in ops.attention, not here
        get_kernel("block_sparse_attention", "xla")


def test_log_dispatch_event_and_gauge(fresh_probe):
    from fengshen_tpu.observability.registry import MetricsRegistry

    events = []
    reg = MetricsRegistry()
    table = log_dispatch(events.append, registry=reg)
    assert table == dispatch_table()
    (event,) = events
    assert event["event"] == "kernel_dispatch"
    assert event["table"]["decode_attention"] == "xla"
    assert event["backend"] == "cpu" and event["reason"]
    gauge = reg.gauge("fstpu_kernel_dispatch", "",
                      labelnames=("op", "impl"))
    assert gauge.labels("decode_attention", "xla").value == 1.0
    assert gauge.labels("decode_attention", "pallas").value == 0.0
