"""The readers of the scheduler's and the Trainer's new spans and
counters, against intervals made by hand (`fixtures/ticks_hand_made.json`:
every answer below can be checked on paper), against a trace with no
device plane, and the attribute loader against a real trace of this
CPU."""

import json
import os

import pytest

from benchmarks.lib import costs, manifest, xplane_attrs

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ["decode_dispatch_ms.chat", "decode_dispatch_ms.serve",
       "commit_ms.chat", "commit_ms.serve",
       "submit_lock_wait_p50_ms.chat", "log_stall_ms.train",
       "prefill_padding_share.serve", "prefill_device_us_per_token.serve",
       "assign_device_ms.serve", "decode_attn_roofline_share.doc",
       "decode_step_device_ms.serve", "decode_step_device_ms.chat"]


@pytest.fixture(scope="module")
def made():
    with open(os.path.join(HERE, "fixtures", "ticks_hand_made.json")) as f:
        return json.load(f)


def _obs(part: dict, window, **more) -> dict:
    return dict({"trace": {"devices": part["devices"], "host": part["host"]},
                 "trace_window": window,
                 "trace_attrs": {"spans": part["spans"],
                                 "modules": part["modules"]}}, **more)


def read(name, obs):
    return manifest.reader(name)(obs)


@pytest.mark.parametrize("cell", ["chat", "serve"])
def test_the_tick_on_the_module_line_its_dispatch_and_its_commit(made,
                                                                cell):
    obs = _obs(made["serve"], (0.0, 1.0))
    # four runs of `jit_decode_fn`: 50, 52, 50 and 50 ms
    assert read(f"decode_step_device_ms.{cell}", obs) == pytest.approx(50.0)
    assert read(f"decode_dispatch_ms.{cell}", obs) == pytest.approx(3.0)
    assert read(f"commit_ms.{cell}", obs) == pytest.approx(2.5)
    # a window that cuts the second run leaves it out
    assert read(f"decode_step_device_ms.{cell}",
                _obs(made["serve"], (0.0, 0.2))) == pytest.approx(50.0)


def test_prefill_cost_a_padded_token_and_the_assign_program(made):
    obs = _obs(made["serve"], (0.0, 1.0))
    # `jit_prefill_fn` ran 80 ms for the span of the 512 bucket and
    # 70 ms for the span of the 1024 one
    assert read("prefill_device_us_per_token.serve", obs) == \
        pytest.approx(1e6 * 0.150 / 1536)
    assert read("assign_device_ms.serve", obs) == pytest.approx(5.0)
    # a window that holds only the first prefill
    assert read("prefill_device_us_per_token.serve",
                _obs(made["serve"], (0.0, 0.3))) == \
        pytest.approx(1e6 * 0.080 / 512)


def test_decode_attention_roofline_share_from_counters_and_kernel_time(
        made):
    config = {"num_key_value_heads": 8, "head_dim": 128,
              "num_hidden_layers": 16, "program": {"dtype": "bfloat16"},
              "engine_args": {"kv_dtype": "fp32"}}
    ticks, attended = "fstpu_serving_decode_ticks_total", \
        "fstpu_serving_kv_tokens_attended_total"
    obs = _obs(made["serve"], (0.0, 1.0), config=config,
               peaks={"hbm_bytes_per_s": 819e9},
               stats_open={ticks: 1000.0, attended: 5e6},
               stats_close={ticks: 1100.0, attended: 9e6})
    # 40,000 real cached tokens a tick, 65,536 B each over 16 layers:
    # 2.62 GB, 3.2 ms at 819 GB/s; the kernel took 10 and 12 ms inside
    # two of the four runs of `jit_decode_fn`: 5.5 ms a tick
    least = costs.decode_attention_bytes(40000, 8, 128, 2, 16) / 819e9
    assert least == pytest.approx(3.2008e-3, rel=1e-4)
    share = read("decode_attn_roofline_share.doc", obs)
    assert share == pytest.approx(100 * least / 0.0055)
    assert 0 < share < 100
    # an int8 pool halves the bytes; the parent's engine has no counter
    obs["config"] = dict(config, engine_args={"kv_dtype": "int8"})
    assert read("decode_attn_roofline_share.doc", obs) == \
        pytest.approx(50 * least / 0.0055)
    del obs["stats_open"][attended], obs["stats_close"][attended]
    assert read("decode_attn_roofline_share.doc", obs) is None


def test_padding_share_and_lock_wait_read_counters_and_timelines():
    real, padded = "fstpu_serving_prefill_tokens_total", \
        "fstpu_serving_prefill_padded_tokens_total"
    obs = {"stats_open": {real: 1000.0, padded: 2000.0},
           "stats_close": {real: 31000.0, padded: 42000.0}}
    assert read("prefill_padding_share.serve", obs) == pytest.approx(25.0)
    obs = {"timelines": {
        "a": {"phases": {"queue_wait_s": 1.0, "lock_wait_s": 0.9}},
        "b": {"phases": {"queue_wait_s": 0.3, "lock_wait_s": 0.1}},
        "c": {"phases": {"queue_wait_s": 0.5, "lock_wait_s": 0.4}},
        "live": {"phases": None}}}
    assert read("submit_lock_wait_p50_ms.chat", obs) == pytest.approx(400.0)
    assert read("queue_wait_p50_ms.chat", obs) == pytest.approx(500.0)
    # the parent's timelines have no such phase
    assert read("submit_lock_wait_p50_ms.chat", {"timelines": {
        "a": {"phases": {"queue_wait_s": 1.0}}}}) is None


def test_log_stall_is_the_device_idle_time_inside_the_log_span(made):
    obs = _obs(made["train"], (0.0, 1.7))
    # the device runs dry 10, 12, 40 and 10 ms inside the four spans
    assert read("log_stall_ms.train", obs) == pytest.approx(11.0)


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_returns_nothing_without_a_device_plane(
        name, made):
    """The CPU rehearsal and the parent commit: no device plane, no new
    span, no new counter. A reader then returns None and does not
    raise."""
    part = made["serve"]
    bare = {"cell": {"name": "no_such_cell"}, "timelines": {},
            "trace": {"devices": {}, "host": part["host"]},
            "trace_window": (0.0, 1.0), "stats_open": {}, "stats_close": {}}
    assert read(name, bare) is None
    assert read(name, {"trace": None}) is None
    # the parent's program under these readers: a device plane, the old
    # spans only, none of the new counters, no attribute on any span
    old = [e for e in part["host"] if e[0] in (
        "bench/traced", "serving/decode", "serving/prefill",
        "serving/admit")]
    parent = {"cell": {"name": "no_such_cell"}, "timelines": {
        "a": {"phases": {"queue_wait_s": 1.0}}},
        "trace": {"devices": part["devices"], "host": old},
        "trace_window": (0.0, 1.0),
        "trace_attrs": {"spans": [], "modules": part["modules"]},
        "stats_open": {"fstpu_serving_decode_ticks_total": 1.0},
        "stats_close": {"fstpu_serving_decode_ticks_total": 9.0},
        "config": {}, "peaks": {"hbm_bytes_per_s": 819e9}}
    # what the module line alone gives is read all the same
    expected = {"assign_device_ms.serve": 5.0,
                "decode_step_device_ms.serve": 50.0,
                "decode_step_device_ms.chat": 50.0}
    assert read(name, parent) == (pytest.approx(expected[name])
                                  if name in expected else None)


def test_busy_index_and_children_on_hand_made_intervals():
    trace = {"devices": {"/device:TPU:0": [
        ["a", 0.0, 1.0], ["b", 0.5, 1.0], ["c", 3.0, 1.0]]},
        "host": [["p/q", 0.0, 2.0], ["p/q/r", 0.5, 1.0], ["p/q", 2.5, 1.0],
                 ["p/q", 4.0, 0.5], ["p/q/r", 4.1, 0.2]]}
    busy = xplane_attrs.Busy(trace, 0.0, 5.0)
    assert busy.seconds(0.0, 5.0) == pytest.approx(2.5)
    assert busy.seconds(1.0, 3.5) == pytest.approx(1.0)
    assert busy.seconds(1.6, 2.9) == 0.0
    assert busy.last_end(0.0, 2.0) == pytest.approx(1.5)
    assert busy.last_end(0.0, 1.2) == pytest.approx(1.2)   # clipped
    assert busy.last_end(1.6, 2.9) is None
    assert busy.last_end(2.0, 3.5) == pytest.approx(3.5)
    # the second p/q has no child, the others one each
    assert xplane_attrs.children(trace, "p/q", "p/q/r", 0.0, 5.0) == [
        ((0.0, 2.0), (0.5, 1.5)), ((4.0, 4.5), (4.1, pytest.approx(4.3)))]


def test_the_loader_reads_span_attributes_from_a_real_trace(tmp_path):
    """`span(..., **attrs)` through the profiler and back on this CPU:
    the event keeps its plain name (the existing readers match it) and
    the attributes arrive as its stats. A CPU trace has no device
    plane, so no module line."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import xplane
    from benchmarks.lib.tracing import Traced
    from fengshen_tpu.observability import span

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    traced = Traced(str(tmp_path / "trace"))
    with traced:
        with span("serving/prefill", request_id="req-9", bucket=512,
                  prompt_tokens=300):
            f(x).block_until_ready()
        with span("serving/decode"):
            with span("dispatch", lanes=3):
                y = f(x)
            with span("fetch"):
                y.block_until_ready()
    trace, (lo, hi) = traced.load()
    names = {n for n, _, _ in trace["host"]}
    assert {"serving/prefill", "serving/decode", "serving/decode/dispatch",
            "serving/decode/fetch"} <= names
    attrs = xplane_attrs.load(xplane.find_xplane(str(tmp_path / "trace")))
    assert attrs["modules"] == []
    (a, b, got), = xplane_attrs.spans_with(attrs, "serving/prefill", lo, hi)
    assert got == {"request_id": "req-9", "bucket": 512,
                   "prompt_tokens": 300} and lo <= a < b <= hi
    (_, _, got), = xplane_attrs.spans_with(
        attrs, "serving/decode/dispatch", lo, hi)
    assert got == {"lanes": 3}
    # found through `obs` where the harness wrote it, nothing without
    obs = {"cell": {"name": "no_such_cell"}, "trace": trace}
    assert xplane_attrs.of(obs) is None
