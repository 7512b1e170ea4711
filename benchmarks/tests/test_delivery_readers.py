"""The readers of the handler threads' account (`lib/delivery.py` and the
files that name it) against counters made by hand: every answer below
can be checked on paper. Then the same on what the parent of the PR that
added the counters gives: the scheduler's account without the handlers'."""

import json
import os

import pytest

from benchmarks.lib import manifest

CELLS = ("chat", "serve")
METRICS = ("handler_cpu_ms_per_tick", "taken_unexplained_ms_per_tick",
           "handler_admit_cpu_ms_per_request", "stream_delivery_lag_ms",
           "stream_tokens_per_wakeup", "process_cpu_ms_per_tick")
NEW = [f"{m}.{c}" for m in METRICS for c in CELLS]

#: the scheduler's account as `test_sched_readers.py` has it: 50 s of
#: wall, 25 of CPU, 15 waited by design, so 10 TAKEN; 2,500 ticks
SCHED_OPEN = {"fstpu_serving_decode_ticks_total": 1000.0,
              "fstpu_serving_scheduler_wall_seconds_total": 100.0,
              "fstpu_serving_scheduler_cpu_seconds_total": 10.0,
              "fstpu_serving_scheduler_wait_seconds_total": 5.0,
              "fstpu_serving_admitted_total": 40.0}
SCHED_CLOSE = {"fstpu_serving_decode_ticks_total": 3500.0,
               "fstpu_serving_scheduler_wall_seconds_total": 150.0,
               "fstpu_serving_scheduler_cpu_seconds_total": 35.0,
               "fstpu_serving_scheduler_wait_seconds_total": 20.0,
               "fstpu_serving_admitted_total": 140.0}
#: the handlers': 0.5 s of admission CPU over 100 requests and 5.5 s of
#: delivery CPU; 80,000 tokens in 20,000 wake-ups, 160 s of summed lag;
#: the process 60 s of CPU
OPEN = dict(SCHED_OPEN, **{
    "fstpu_serving_handler_admit_cpu_seconds_total": 0.25,
    "fstpu_serving_handler_stream_cpu_seconds_total": 2.0,
    "fstpu_stream_wakeups_total": 5000.0,
    "fstpu_stream_tokens_delivered_total": 20000.0,
    "fstpu_stream_delivery_lag_seconds_total": 30.0,
    "fstpu_serving_process_cpu_seconds_total": 90.0})
CLOSE = dict(SCHED_CLOSE, **{
    "fstpu_serving_handler_admit_cpu_seconds_total": 0.75,
    "fstpu_serving_handler_stream_cpu_seconds_total": 7.5,
    "fstpu_stream_wakeups_total": 25000.0,
    "fstpu_stream_tokens_delivered_total": 100000.0,
    "fstpu_stream_delivery_lag_seconds_total": 190.0,
    "fstpu_serving_process_cpu_seconds_total": 150.0})


def read(name, obs):
    return manifest.reader(name)(obs)


@pytest.mark.parametrize("cell", CELLS)
def test_the_six_metrics_from_a_windows_deltas(cell):
    obs = {"stats_open": OPEN, "stats_close": CLOSE}
    # 0.5 + 5.5 s of handler CPU over 2,500 ticks
    assert read(f"handler_cpu_ms_per_tick.{cell}", obs) == \
        pytest.approx(2.4)
    # 10 s taken from the scheduler less the handlers' 6, a tick
    assert read(f"taken_unexplained_ms_per_tick.{cell}", obs) == \
        pytest.approx(1.6)
    # 0.5 s over 100 requests
    assert read(f"handler_admit_cpu_ms_per_request.{cell}", obs) == \
        pytest.approx(5.0)
    # 160 s over 80,000 tokens
    assert read(f"stream_delivery_lag_ms.{cell}", obs) == \
        pytest.approx(2.0)
    assert read(f"stream_tokens_per_wakeup.{cell}", obs) == \
        pytest.approx(4.0)
    # 60 s over 2,500 ticks
    assert read(f"process_cpu_ms_per_tick.{cell}", obs) == \
        pytest.approx(24.0)


@pytest.mark.parametrize("cell", CELLS)
def test_a_scheduler_that_lost_nothing_reads_the_handlers_cpu_negative(cell):
    """The CPU rehearsal, and a cell the device paces: the handlers work
    inside the scheduler's declared waits."""
    close = dict(CLOSE, fstpu_serving_scheduler_wait_seconds_total=30.0)
    obs = {"stats_open": OPEN, "stats_close": close}
    # wall 50 = CPU 25 + wait 25: nothing taken, 6 s of handler CPU
    assert read(f"taken_unexplained_ms_per_tick.{cell}", obs) == \
        pytest.approx(-2.4)
    assert read(f"handler_cpu_ms_per_tick.{cell}", obs) == \
        pytest.approx(2.4)


def test_a_window_without_a_tick_a_request_or_a_token_reads_nothing():
    """A denominator that did not move leaves the metric out; the
    others keep their readings."""
    still = {"fstpu_serving_decode_ticks_total": (
                 "handler_cpu_ms_per_tick", "taken_unexplained_ms_per_tick",
                 "process_cpu_ms_per_tick"),
             "fstpu_serving_admitted_total": (
                 "handler_admit_cpu_ms_per_request",),
             "fstpu_stream_tokens_delivered_total": (
                 "stream_delivery_lag_ms",),
             "fstpu_stream_wakeups_total": ("stream_tokens_per_wakeup",)}
    for counter, silent in still.items():
        obs = {"stats_open": OPEN,
               "stats_close": dict(CLOSE, **{counter: OPEN[counter]})}
        for metric in METRICS:
            value = read(f"{metric}.serve", obs)
            assert (value is None) == (metric in silent), (counter, metric)


PARENT_SHAPED = {
    "the parent's counters: the scheduler's account and no handler's": {
        "stats_open": SCHED_OPEN, "stats_close": SCHED_CLOSE},
    "a window with no counters": {"stats_open": {}, "stats_close": {}},
    "nothing at all": {},
}


@pytest.mark.parametrize("shape", sorted(PARENT_SHAPED))
@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_returns_nothing_where_there_is_nothing_to_read(
        name, shape):
    assert read(name, dict(PARENT_SHAPED[shape])) is None


def test_every_metric_has_its_two_files_one_function_and_one_entry_each():
    """`.chat` moves `gap_p50_ms` in the open loop; `.serve` moves
    `serve_tokens_per_s` in every cell that reports it; both files of a
    metric name the same function of `lib/delivery.py`."""
    man = manifest.load()
    entries = {m["name"]: m for m in man["per_layer"]}
    saturated = next(m["workloads"] for m in man["end_to_end"]
                     if m["name"] == "serve_tokens_per_s")
    for metric in METRICS:
        assert read(f"{metric}.chat", {}) is None
        assert manifest.reader(f"{metric}.chat").__code__ is \
            manifest.reader(f"{metric}.serve").__code__
        assert manifest.reader(f"{metric}.serve").__module__ == \
            "benchmarks.lib.delivery"
    for name in NEW:
        entry = entries[name]
        cell = name.rsplit(".", 1)[1]
        assert entry["workloads"] == (["mistral_chat_steady"]
                                      if cell == "chat" else saturated)
        assert entry["layer"] == "delivery"
        assert entry["source"] == "program_counter"
        assert entry["moves"] == ("gap_p50_ms" if cell == "chat"
                                  else "serve_tokens_per_s")
        assert os.path.exists(os.path.join(
            manifest.BENCH, "metrics", name + ".py"))
    # appended: nothing that was there moved
    assert sorted(m["name"] for m in man["per_layer"][-len(NEW):]) == \
        sorted(NEW)
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw) < 64 * 1024 and json.loads(raw) == man
