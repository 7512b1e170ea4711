"""Observability subsystem tests (fast CPU lane — NOT marked slow):
registry determinism, histogram percentiles vs the reference
implementation, span nesting + the no-profiler fallback, the MFU
estimator against a hand-computed llama-shape FLOPs count, Prometheus
exposition through both server paths, process_index gating, and the
acceptance-bar Trainer fit logging a finite `mfu`.
"""

import argparse
import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

from fengshen_tpu.observability import (JsonlSink, MetricsRegistry,
                                        CPU_NOMINAL_FLOPS, PEAK_FLOPS,
                                        StepStats, current_span_stack,
                                        estimate_flops_per_token,
                                        get_registry, peak_flops_per_chip,
                                        percentile, render_prometheus,
                                        span, start_metrics_server)


@pytest.fixture(autouse=True)
def _leave_no_mesh_behind():
    """`Trainer.fit` sets the process-global mesh and leaves it set; a
    serving engine built by a later test of this worker would shard
    over it and compile its decode program a second time."""
    yield
    from fengshen_tpu.parallel import set_mesh
    set_mesh(None)


# -- registry -------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("t_total", "c")
    c.inc()
    c.inc(2)
    assert c.value() == 3
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = r.gauge("t_gauge", "g")
    g.set(5.0)
    g.inc()
    g.dec(0.5)
    assert g.value() == 5.5
    h = r.histogram("t_hist", "h", buckets=(1.0, 10.0))
    for v in (0.5, 2.0, 50.0):
        h.observe(v)
    child = h.labels() if h.labelnames else h._only_child()
    assert child.count == 3 and child.sum == 52.5
    assert child.counts == [1, 1, 1]  # <=1, <=10, +Inf


def test_registry_get_or_create_and_conflicts():
    r = MetricsRegistry()
    a = r.counter("same_total", "x")
    assert r.counter("same_total", "x") is a
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("same_total", "x")
    with pytest.raises(ValueError, match="already registered"):
        r.counter("same_total", "x", labelnames=("k",))
    with pytest.raises(ValueError, match="invalid metric name"):
        r.counter("bad name", "x")
    lab = r.counter("lab_total", "x", labelnames=("k",))
    with pytest.raises(ValueError, match="label"):
        lab.labels("a", "b")
    with pytest.raises(ValueError, match="labelled"):
        lab.inc()


def test_render_prometheus_is_sorted_and_typed():
    r = MetricsRegistry()
    # insert in an order that differs from sorted order
    r.gauge("zz_gauge", "z").set(1)
    c = r.counter("aa_total", "a", labelnames=("k",))
    for key in {"zebra", "alpha", "mid"}:  # set: hash-ordered source
        c.labels(key).inc()
    text = render_prometheus(r)
    lines = text.splitlines()
    assert lines[0] == "# HELP aa_total a"
    assert lines[1] == "# TYPE aa_total counter"
    assert lines[2:5] == ['aa_total{k="alpha"} 1',
                         'aa_total{k="mid"} 1',
                         'aa_total{k="zebra"} 1']
    assert lines[-1] == "zz_gauge 1"


def test_render_deterministic_across_hashseed():
    """Byte-identical exposition no matter PYTHONHASHSEED: label values
    arrive from a set (hash-ordered), rendering must sort them."""
    snippet = textwrap.dedent("""
        from fengshen_tpu.observability import (MetricsRegistry,
                                                render_prometheus)
        r = MetricsRegistry()
        c = r.counter("t_total", "t", labelnames=("k",))
        for key in {"a", "b", "c", "dd", "ee", "zz", "m1", "m2"}:
            c.labels(key).inc()
        h = r.histogram("t_h", "h", labelnames=("k",))
        for key in {"x", "y", "z"}:
            h.labels(key).observe(1.0)
        print(render_prometheus(r))
    """)
    outs = set()
    for seed in ("0", "1", "31337"):
        env = dict(os.environ, PYTHONHASHSEED=seed)
        outs.add(subprocess.run(
            [sys.executable, "-c", snippet], env=env, check=True,
            capture_output=True, text=True).stdout)
    assert len(outs) == 1


def test_histogram_percentile_matches_reference():
    """`registry.percentile` (the single implementation) agrees with
    the PR-3 serving implementation it replaced, across sizes/qs."""
    def reference(values, q):  # verbatim old serving/metrics.py
        vals = sorted(values)
        if not vals:
            return 0.0
        idx = min(int(q * len(vals)), len(vals) - 1)
        return float(vals[idx])

    rng = np.random.RandomState(7)
    r = MetricsRegistry()
    for n in (0, 1, 2, 7, 100, 513):
        h = r.histogram(f"h_{n}", "h", window=512)
        vals = rng.rand(n).tolist()
        for v in vals:
            h.observe(v)
        window = vals[-512:]  # histogram window is bounded
        for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
            assert h.percentile(q) == reference(window, q)
            assert percentile(window, q) == reference(window, q)


# -- spans ----------------------------------------------------------------

def test_span_nesting_and_labels():
    r = MetricsRegistry()
    with span("outer", registry=r):
        assert current_span_stack() == ("outer",)
        with span("inner", registry=r):
            assert current_span_stack() == ("outer", "inner")
    assert current_span_stack() == ()
    metric = r.get("fstpu_span_seconds")
    labels = [v for v, _ in metric.children()]
    assert (("outer",) in labels and ("outer/inner",) in labels)


def test_span_fallback_without_jax_profiler(monkeypatch):
    import fengshen_tpu.observability.tracing as tracing
    monkeypatch.setattr(tracing, "_TRACE_ANNOTATION", None)
    r = MetricsRegistry()
    with span("noprof", registry=r):
        pass
    child = r.get("fstpu_span_seconds").labels("noprof")
    assert child.count == 1 and child.sum >= 0


def test_span_records_on_exception():
    r = MetricsRegistry()
    with pytest.raises(RuntimeError):
        with span("boom", registry=r):
            raise RuntimeError("x")
    assert r.get("fstpu_span_seconds").labels("boom").count == 1
    assert current_span_stack() == ()


def _sleep_50ms():
    time.sleep(0.05)


def _burn_20ms_of_cpu():
    """Returns the largest step the thread's clock was seen to make."""
    last = time.thread_time()
    end, step = last + 0.02, 0.0
    while last < end:
        now = time.thread_time()
        step, last = max(step, now - last), now
    return step


#: what a span around `_burn_20ms_of_cpu` has to show. Not 0.02 itself:
#: where the thread's clock advances in steps of 10 ms the loop ends on
#: a reading of exactly `T0 + 0.02`, whose difference from `T0` falls
#: under 0.02 by an ulp about half the time, and `thread_times()`
#: extrapolates a reading taken within `_CPU_REUSE_S` (100 us) of the
#: thread's last, which puts a nested span's start ahead of the truth
#: by as much. The upper bound below carries the same 1e-4.
_BURNT = 0.02 - 1e-4


@pytest.mark.parametrize("body, low, high", [
    # a wait: the thread is off the CPU for nearly all of the wall
    (_sleep_50ms, 0.0, 0.2),
    # work: what the wall holds beyond the CPU is what a loaded test
    # host took from the thread, never the other way round
    (_burn_20ms_of_cpu, _BURNT, None)],
    ids=["a_sleep_is_a_wait", "a_busy_loop_is_work"])
def test_span_measures_the_threads_cpu_beside_the_wall(body, low, high):
    r = MetricsRegistry()
    with span("cpu/probe", registry=r) as s:
        step = body() or 0.0
    # the two clocks are not read at one instant, and a CPU clock that
    # advances in steps runs ahead of the wall by up to one of them
    assert low <= s.cpu_seconds <= s.seconds + step + 1e-4
    if high is not None:
        assert s.cpu_seconds < high * s.seconds
    assert r.get("fstpu_span_seconds").labels("cpu/probe").sum == \
        pytest.approx(s.seconds)
    assert r.get("fstpu_span_cpu_seconds_total").labels(
        "cpu/probe").value == pytest.approx(s.cpu_seconds)


@pytest.mark.parametrize("raises", [False, True],
                         ids=["at_exit", "on_an_exception"])
def test_span_yields_a_record_filled_when_the_section_ends(raises):
    r = MetricsRegistry()
    try:
        with span("record/probe", registry=r, lanes=2) as s:
            assert s.seconds == 0.0 and s.cpu_seconds == 0.0
            time.sleep(0.002)
            if raises:
                raise RuntimeError("x")
    except RuntimeError:
        assert raises
    assert s.seconds >= 0.002 and 0.0 <= s.cpu_seconds <= s.seconds + 1e-4
    assert current_span_stack() == ()
    # a caller that ignores the record is unchanged
    with span("record/ignored", registry=r):
        pass
    assert r.get("fstpu_span_seconds").labels("record/ignored").count == 1


def test_span_cpu_counter_renders_labelled_beside_the_histogram():
    r = MetricsRegistry()
    with span("outer", registry=r):
        with span("inner", registry=r):
            _burn_20ms_of_cpu()
    text = render_prometheus(r)
    assert "# TYPE fstpu_span_cpu_seconds_total counter" in text
    cpu = {line.split(" ")[0]: float(line.split(" ")[1])
           for line in text.splitlines()
           if line.startswith("fstpu_span_cpu_seconds_total{")}
    assert set(cpu) == {
        'fstpu_span_cpu_seconds_total{span="outer"}',
        'fstpu_span_cpu_seconds_total{span="outer/inner"}'}
    # the parent's CPU holds its child's
    assert cpu['fstpu_span_cpu_seconds_total{span="outer"}'] >= \
        cpu['fstpu_span_cpu_seconds_total{span="outer/inner"}'] >= _BURNT
    assert 'fstpu_span_seconds_count{span="outer/inner"} 1' in text


def test_span_resolves_its_children_once_per_registry_and_label(
        monkeypatch):
    """An exit looks nothing up by name after the first, and two
    registries never share a child."""
    a, b = MetricsRegistry(), MetricsRegistry()
    lookups = []
    for reg in (a, b):
        real = reg.histogram
        monkeypatch.setattr(
            reg, "histogram",
            lambda *args, _real=real, _reg=reg, **kw:
            (lookups.append(_reg), _real(*args, **kw))[1])
    for _ in range(5):
        with span("cached", registry=a):
            pass
    with span("cached", registry=b):
        pass
    with span("other", registry=a):
        pass
    assert lookups == [a, b, a]
    assert a.get("fstpu_span_seconds").labels("cached").count == 5
    assert b.get("fstpu_span_seconds").labels("cached").count == 1
    assert a.span_children["cached"][0] is not b.span_children["cached"][0]
    assert a.span_children["cached"][1] is \
        a.get("fstpu_span_cpu_seconds_total").labels("cached")
    assert set(a.span_children) == {"cached", "other"}
    assert set(b.span_children) == {"cached"}


def test_nested_and_adjacent_spans_share_one_read_of_the_cpu_clock(
        monkeypatch):
    """The CPU clock is a system call: a reading within 100 us of the
    thread's last is extrapolated from it, a later one is read anew and
    never lies behind an earlier reading."""
    import fengshen_tpu.observability.tracing as tracing
    wall, cpu, reads = [100.0], [7.0], []
    fake = type("T", (), {
        "perf_counter": staticmethod(lambda: wall[0]),
        "thread_time": staticmethod(
            lambda: (reads.append(wall[0]), cpu[0])[1])})
    monkeypatch.setattr(tracing, "time", fake)
    monkeypatch.setattr(tracing._local, "cpu_anchor", None, raising=False)
    assert tracing.thread_times() == (100.0, 7.0)
    wall[0] += 5e-6
    cpu[0] += 1.0                   # not looked at: 5 us after the last
    t, c = tracing.thread_times()
    assert t == wall[0] and c == pytest.approx(7.0 + 5e-6)
    wall[0] += 96e-6                # 101 us after the anchor: read anew
    assert tracing.thread_times() == (wall[0], 8.0)
    assert reads == [100.0, wall[0]]
    # a coarse clock that has not moved since: the extrapolated reading
    # stands, the next one is not behind it
    wall[0] += 60e-6
    assert tracing.thread_times()[1] == pytest.approx(8.0 + 60e-6)
    wall[0] += 60e-6
    assert tracing.thread_times()[1] == pytest.approx(8.0 + 60e-6)
    assert len(reads) == 3
    # a sleeping section is measured, not extrapolated
    r = MetricsRegistry()
    with span("reuse/probe", registry=r) as s:
        wall[0] += 0.5
        cpu[0] += 0.001
    assert s.seconds == pytest.approx(0.5)
    assert s.cpu_seconds == pytest.approx(0.001, abs=125e-6)


def _record_annotations(monkeypatch) -> list:
    """Stand a recorder in for `TraceAnnotation`; returns the list it
    fills with (label, attributes) per span entered."""
    import fengshen_tpu.observability.tracing as tracing
    seen = []

    class Annotation:
        def __init__(self, label, **attrs):
            seen.append((label, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_TRACE_ANNOTATION", Annotation)
    return seen


def test_span_attributes_reach_the_annotation_never_the_label(
        monkeypatch):
    """`span(name, **attrs)`: the attributes ride on the profiler's
    event (a request id, a bucket), the histogram's label is the span
    path alone, or /metrics would grow a series per request."""
    import fengshen_tpu.observability.tracing as tracing
    seen = _record_annotations(monkeypatch)
    r = MetricsRegistry()
    with span("serving/prefill", registry=r, request_id="req-7",
              bucket=512):
        with span("inner", registry=r, lanes=3):
            pass
    with span("serving/prefill", registry=r, request_id="req-8",
              bucket=128):
        pass
    assert seen == [
        ("serving/prefill", {"request_id": "req-7", "bucket": 512}),
        ("serving/prefill/inner", {"lanes": 3}),
        ("serving/prefill", {"request_id": "req-8", "bucket": 128})]
    metric = r.get("fstpu_span_seconds")
    assert sorted(v for v, _ in metric.children()) == [
        ("serving/prefill",), ("serving/prefill/inner",)]
    assert metric.labels("serving/prefill").count == 2
    # the real TraceAnnotation takes the same call
    monkeypatch.setattr(tracing, "_TRACE_ANNOTATION", tracing._UNRESOLVED)
    with span("attrs/real", registry=r, step=3, callback="Probe"):
        pass
    assert r.get("fstpu_span_seconds").labels("attrs/real").count == 1


# -- flops / mfu ----------------------------------------------------------

class _Cfg:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_flops_estimator_hand_computed_llama_shape():
    # h=32, l=3, inter=64, v=97, 4 heads (no GQA):
    #   per_layer = 2*32*32 (q+o) + 2*32*32 (k+v) + 3*32*64 (mlp)
    #             = 2048 + 2048 + 6144 = 10240
    #   total = 3*10240 + 32*97 = 30720 + 3104 = 33824 -> x6 = 202944
    cfg = _Cfg(hidden_size=32, num_hidden_layers=3,
               intermediate_size=64, vocab_size=97,
               num_attention_heads=4)
    assert estimate_flops_per_token(cfg) == 202944.0
    assert estimate_flops_per_token(cfg, include_backward=False) == \
        202944.0 / 3
    # GQA: 8 kv heads of head_dim 128 under 40 query heads (13B shape)
    gqa = _Cfg(hidden_size=5120, num_hidden_layers=1,
               intermediate_size=13824, vocab_size=0,
               num_attention_heads=40, num_key_value_heads=8)
    per_layer = (2 * 5120 * 5120 + 2 * 5120 * (8 * 128)
                 + 3 * 5120 * 13824)
    assert estimate_flops_per_token(gqa) == 6.0 * per_layer
    # unsupported config (no hidden_size/num_hidden_layers) -> None
    assert estimate_flops_per_token(_Cfg(d_model=768)) is None


def test_peak_flops_resolution():
    assert peak_flops_per_chip("TPU v5e") == PEAK_FLOPS["TPU v5e"]
    assert peak_flops_per_chip("TPU v5 lite") == 197e12
    # the CPU backend (CI) alone gets the nominal figure ...
    assert peak_flops_per_chip("cpu") == CPU_NOMINAL_FLOPS
    assert peak_flops_per_chip() == CPU_NOMINAL_FLOPS
    # ... an accelerator the table does not know is an error, never a
    # made-up peak under a made-up MFU
    with pytest.raises(ValueError, match="weird chip"):
        peak_flops_per_chip("weird chip")


def test_stepstats_mfu_and_goodput():
    r = MetricsRegistry()
    clock = [0.0]
    stats = StepStats(flops_per_token=100.0, n_devices=2,
                      device_kind="cpu", registry=r,
                      clock=lambda: clock[0])
    stats.record_execution(n_steps=2, n_tokens=1000)
    clock[0] = 2.0
    entry = stats.window_entry(global_step=2, bad_step_count=0)
    assert entry["tokens_per_sec"] == 500.0
    assert entry["mfu"] == pytest.approx(
        500.0 * 100.0 / (2 * CPU_NOMINAL_FLOPS))
    assert entry["goodput"] == 1.0
    # window resets: no tokens since -> 0 tps
    clock[0] = 3.0
    assert stats.window_entry(4, 0)["tokens_per_sec"] == 0.0
    # guards skipped 3 of 10 steps, one rewind replayed 5
    stats.record_rewind(from_step=10, to_step=5)
    assert stats.goodput(global_step=10, bad_step_count=3) == \
        pytest.approx(7 / 15)
    assert int(r.get("fstpu_train_rewinds_total").value()) == 1


# -- sink -----------------------------------------------------------------

def test_jsonl_sink_writes_and_echoes(tmp_path, capsys):
    path = tmp_path / "sub" / "metrics.jsonl"
    sink = JsonlSink(path=str(path), echo=True)
    sink({"event": "x", "v": 1.23456, "n": 7})
    sink({"event": "y"})
    lines = [json.loads(l) for l in open(path)]
    assert lines == [{"event": "x", "v": 1.23456, "n": 7},
                     {"event": "y"}]
    out = capsys.readouterr().out
    assert "[fengshen-tpu] event=x v=1.235 n=7" in out


def test_jsonl_sink_stream_and_logger(tmp_path):
    import io
    buf = io.StringIO()
    seen = []

    class Logger:
        def log_metrics(self, metrics, step=None):
            seen.append((metrics, step))

    sink = JsonlSink(stream=buf, logger=Logger())
    sink({"step": 3, "loss": 1.5, "note": "text"})
    assert json.loads(buf.getvalue()) == {"step": 3, "loss": 1.5,
                                          "note": "text"}
    assert seen == [({"step": 3, "loss": 1.5}, 3)]


def test_jsonl_sink_process_index_gating(tmp_path, monkeypatch):
    import fengshen_tpu.observability.sink as sink_mod
    monkeypatch.setattr(sink_mod, "_process_index", lambda: 1)
    path = tmp_path / "m.jsonl"
    JsonlSink(path=str(path))({"event": "x"})
    assert not path.exists()
    JsonlSink(path=str(path), only_process_zero=False)({"event": "x"})
    assert path.exists()


# -- exposition endpoints -------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.headers.get("Content-Type"), \
            r.read().decode()


def test_metrics_exporter_thread_and_gating(monkeypatch):
    reg = MetricsRegistry()
    reg.counter("exp_total", "x").inc(4)
    server = start_metrics_server(0, host="127.0.0.1",
                                  registries=(reg,))
    try:
        code, ctype, body = _get(
            f"http://127.0.0.1:{server.port}/metrics")
        assert code == 200
        assert ctype.startswith("text/plain; version=0.0.4")
        assert "exp_total 4" in body
        code, _, _ = _get(f"http://127.0.0.1:{server.port}/healthz")
        assert code == 200
    finally:
        server.close()
    # multihost gating: non-zero process index binds no socket
    import fengshen_tpu.observability.exposition as expo
    monkeypatch.setattr(expo, "_process_index", lambda: 1)
    assert start_metrics_server(0, registries=(reg,)) is None


def test_metrics_endpoint_stdlib_server_simple_pipeline():
    """GET /metrics on the stdlib server path: valid Prometheus text,
    and the HTTP request counter shows up after a POST."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)

    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0),
        PipelineConfig(task="text_classification"),
        pipeline=lambda text: {"label": 0})
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/text_classification",
            data=json.dumps({"input_text": "hi"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 200
        with span("probe/metrics_endpoint"):
            pass
        code, ctype, body = _get(f"http://127.0.0.1:{port}/metrics")
        assert code == 200
        assert ctype.startswith("text/plain; version=0.0.4")
        # a span's wall histogram and its CPU counter, both labelled
        assert ('fstpu_span_seconds_count{span="probe/metrics_endpoint"}'
                in body)
        assert ('fstpu_span_cpu_seconds_total{span='
                '"probe/metrics_endpoint"}') in body
        assert ('fstpu_http_requests_total{route='
                '"/api/text_classification",code="200"} 1') in body
        # every sample line parses as `name{labels} value`
        for line in body.splitlines():
            if line.startswith("#") or not line:
                continue
            name_part, _, value = line.rpartition(" ")
            float(value)
            assert name_part
    finally:
        server.shutdown()


# -- engine metrics adapter ----------------------------------------------

def test_engine_metrics_snapshot_shape_pinned():
    """EngineMetrics over the registry keeps the exact PR-3 /stats JSON
    shape, and its registry renders the same numbers as Prometheus."""
    from fengshen_tpu.serving.metrics import EngineMetrics

    m = EngineMetrics()
    m.count("admitted", 2)
    m.count("completed")
    m.record_prefill(64, 50)
    m.record_prefill(64, 50)
    m.record_tick(3, 8)
    m.record_ttft(0.2)
    m.record_ttft(0.4)
    m.warmup_compile_s = 1.5
    snap = m.snapshot(queue_depth=1, slots_active=3, num_slots=8,
                      kv={"layout": "paged", "dtype": "int8",
                          "blocks_total": 16, "blocks_used": 5,
                          "blocks_free": 11, "block_tokens": 64,
                          "bytes": 4096, "fragmentation": 0.25})
    assert snap == {
        "queue_depth": 1, "slots_active": 3, "num_slots": 8,
        "admitted": 2, "rejected_queue_full": 0,
        "rejected_prompt_too_long": 0, "rejected_draining": 0,
        "rejected_duplicate": 0,
        "completed": 1,
        "cancelled": 0, "expired": 0,
        "deferred_admissions": 0, "slots_active_peak": 3,
        "kv_layout": "paged", "kv_dtype": "int8",
        "kv_blocks_total": 16, "kv_blocks_used": 5,
        "kv_blocks_free": 11, "kv_block_tokens": 64,
        "kv_cache_bytes": 4096, "kv_fragmentation": 0.25,
        "prefills_per_bucket": {64: 2},
        "decode_ticks": 1, "decode_tokens": 3, "slot_occupancy": 0.375,
        "ttft_avg_s": 0.3, "ttft_p50_s": 0.4, "ttft_p95_s": 0.4,
        "warmup_compile_s": 1.5,
        # ISSUE 8: the payload only EXTENDS (uptime + last error type/
        # age — never a traceback); every pre-existing key above is
        # unrenamed
        "uptime_s": 0.0, "last_error": None,
        # ISSUE 10: drain visibility for the fleet router's /stats
        # poll (plus the rejected_draining counter above)
        "draining": False,
    }
    # a spec engine (ISSUE 7) ADDS exactly its five keys — the
    # non-spec payload above stays byte-identical
    assert not any(k.startswith("spec_") for k in snap)
    m.record_spec(8, 5)
    m.record_tick(3, 8, tokens=8)        # spec tick: 8 committed
    snap2 = m.snapshot(queue_depth=1, slots_active=3, num_slots=8,
                       kv={"layout": "paged", "dtype": "int8",
                           "blocks_total": 16, "blocks_used": 5,
                           "blocks_free": 11, "block_tokens": 64,
                           "bytes": 4096, "fragmentation": 0.25},
                       spec={"mode": "prompt_lookup", "gamma": 4})
    assert snap2 == dict(snap, decode_ticks=2, decode_tokens=11,
                         spec_mode="prompt_lookup", spec_gamma=4,
                         spec_drafted_total=8, spec_accepted_total=5,
                         spec_acceptance_rate=0.625)
    text = render_prometheus(m.registry)
    # wall time of dispatch + run + fetch was neither device nor host
    # time and had no reader
    assert "decode_seconds" not in text
    assert "fstpu_serving_admitted_total 2" in text
    assert 'fstpu_serving_prefills_total{bucket="64"} 2' in text
    assert "fstpu_serving_queue_depth 1" in text
    assert "fstpu_kv_blocks_total 16" in text
    assert "fstpu_kv_blocks_used 5" in text
    assert "fstpu_kv_fragmentation 0.25" in text
    assert "fstpu_serving_spec_drafted_total 8" in text
    assert "fstpu_serving_spec_accepted_total 5" in text
    assert "fstpu_spec_accepted_ratio 0.625" in text
    # the kv-less form (bare EngineMetrics) defaults to an empty pool
    assert m.snapshot(1, 3, 8)["kv_blocks_total"] == 0
    # two independent engines never share counts
    m2 = EngineMetrics()
    assert m2.snapshot(0, 0, 8)["admitted"] == 0


# -- trainer integration (the acceptance bar) -----------------------------

def _parse(argv):
    from fengshen_tpu.data.universal_datamodule import UniversalDataModule
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import add_trainer_args
    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    return parser.parse_args(argv)


def test_trainer_fit_logs_finite_mfu_and_goodput(tmp_path):
    """Tiny CPU fit: every step entry carries a finite `mfu` computed
    by the estimator (nominal CPU peak) and a goodput of 1.0 on a
    clean run; the exporter flag serves the same numbers over HTTP."""
    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule

    args = _parse(["--train_batchsize", "4", "--learning_rate", "1e-3",
                   "--warmup_steps", "1", "--log_every_n_steps", "1",
                   "--max_steps", "2", "--metrics_port", "0",
                   "--default_root_dir", str(tmp_path)])
    cfg = LlamaConfig(vocab_size=64, hidden_size=16,
                      intermediate_size=32, num_hidden_layers=1,
                      num_attention_heads=2,
                      max_position_embeddings=32, dtype="float32")
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 63, 16).tolist()}
            for _ in range(16)]

    class DS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    module = CausalLMModule(args, LlamaForCausalLM(cfg), cfg)
    dm = UniversalDataModule(args=args, datasets={"train": DS()})
    trainer = Trainer(args)
    state = trainer.fit(module, dm)
    assert int(state.step) == 2

    lines = [json.loads(l)
             for l in open(os.path.join(tmp_path, "metrics.jsonl"))]
    steps = [l for l in lines if "mfu" in l]
    assert len(steps) == 2
    for entry in steps:
        assert np.isfinite(entry["mfu"]) and entry["mfu"] > 0
        assert entry["goodput"] == 1.0
        assert np.isfinite(entry["tokens_per_sec"])
    # the estimator (not 6N) provided flops_per_token: cross-check the
    # published gauge against a recomputation from the entry
    from fengshen_tpu.observability import get_registry
    reg = get_registry()
    assert reg.get("fstpu_train_mfu") is not None
    assert reg.get("fstpu_train_step").value() == 2
    # spans recorded for load/step (checkpoint span needs a ckpt cb)
    span_labels = {v[0] for v, _ in
                   reg.get("fstpu_span_seconds").children()}
    assert "train/load" in span_labels
    assert "train/step" in span_labels


def test_trainer_loop_spans_name_log_callbacks_and_real_saves(
        tmp_path, monkeypatch):
    """The step loop after the dispatch is covered: `train/log` around
    the fetch of the logged metrics, each callback under
    `train/callback` with its class, `train/checkpoint` only where a
    save happens; `train/step` and `train/load` keep their names, and
    the step program keeps the name jit gives it from its function."""
    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.observability import get_registry
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule

    seen = _record_annotations(monkeypatch)
    modules = []

    build = Trainer._build_train_step

    def naming_build(self, *a, **kw):
        jitted, batch_sh = build(self, *a, **kw)

        def call(*args):
            if not modules:
                modules.append(jitted.lower(*args).as_text()[:80])
            return jitted(*args)
        return call, batch_sh
    monkeypatch.setattr(Trainer, "_build_train_step", naming_build)

    args = _parse(["--train_batchsize", "4", "--learning_rate", "1e-3",
                   "--warmup_steps", "1", "--log_every_n_steps", "1",
                   "--max_steps", "3",
                   "--default_root_dir", str(tmp_path)])
    cfg = LlamaConfig(vocab_size=64, hidden_size=16,
                      intermediate_size=32, num_hidden_layers=1,
                      num_attention_heads=2,
                      max_position_embeddings=32, dtype="float32")
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 63, 16).tolist()}
            for _ in range(16)]

    class DS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    class Probe:
        def on_train_step_end(self, trainer, state):
            pass

    class EverySecondStep:
        saved = []

        def save_due(self, trainer):
            return trainer.global_step % 2 == 0

        def on_train_step_end(self, trainer, state):
            if self.save_due(trainer):
                self.saved.append(trainer.global_step)

    def counts():
        metric = get_registry().get("fstpu_span_seconds")
        return {} if metric is None else {
            v[0]: c.count for v, c in metric.children()}

    before = counts()
    trainer = Trainer(args)
    trainer.callbacks += [Probe(), EverySecondStep()]
    state = trainer.fit(CausalLMModule(args, LlamaForCausalLM(cfg), cfg),
                        UniversalDataModule(args=args,
                                            datasets={"train": DS()}))
    assert int(state.step) == 3
    grew = {k: n - before.get(k, 0) for k, n in counts().items()
            if n > before.get(k, 0)}
    assert grew["train/step"] == 3 and grew["train/load"] >= 3
    assert grew["train/log"] == 3
    assert grew["train/callback"] == 3 + 2     # Probe, and no save due
    assert grew["train/checkpoint"] == 1 and EverySecondStep.saved == [2]
    assert grew["train/validate"] == 1         # the epoch's end
    assert [a for n, a in seen if n == "train/log"] == [
        {"step": 1}, {"step": 2}, {"step": 3}]
    assert [a["callback"] for n, a in seen if n == "train/checkpoint"] \
        == ["EverySecondStep"]
    assert {a["callback"] for n, a in seen if n == "train/callback"} == \
        {"Probe", "EverySecondStep"}
    assert modules and modules[0].startswith("module @jit_train_step")
