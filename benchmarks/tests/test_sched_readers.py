"""The readers of the scheduler thread's cycle (`lib/sched.py` and the
files that name it) against a trace and counters made by hand: every
answer below can be checked on paper. Then the same on what the parent
of the PR that added the spans and counters gives: the trace without the
new spans, the counters without the new names."""

import json
import os

import pytest

from benchmarks.lib import manifest

CELLS = ("chat", "serve")
COUNTER_METRICS = ("sched_cpu_ms_per_tick", "sched_taken_share",
                   "sched_lock_wait_ms_per_tick",
                   "decode_dispatch_cpu_share", "commit_cpu_share")
SPAN_METRICS = ("sched_cycle_ms", "sched_uncovered_share")
ALIASES = ("decode_dispatch_ms.serve", "commit_ms.serve")
NEW = [f"{m}.{c}" for m in SPAN_METRICS + COUNTER_METRICS
       for c in CELLS] + list(ALIASES)
ADDED_SPANS = ("serving/reclaim", "serving/tail", "serving/alloc",
               "serving/decode/dispatch/call",
               "serving/decode/dispatch/copy_back")


def _ms(name, start, dur):
    return [name, start * 1e-3, dur * 1e-3]


#: milliseconds on the trace's clock, window [0, 1000). Four cycles of
#: the scheduler thread, each from one `serving/lock_wait` to the next:
#:   A [100, 120)  pure decode; nothing covers [116, 120): a 4 ms hole
#:   B [120, 150)  a prefill and an assign: not pure
#:   C [150, 174)  pure decode, its lock wait 2 ms; a hole [172, 174)
#:   D [174, 190)  an idle wait and no dispatch: not pure
#: and a submitter's thread whose spans lie over both holes.
HOST = [
    _ms("bench/traced", 0, 1000),
    # A
    _ms("serving/lock_wait", 100, 0.1), _ms("serving/reclaim", 100.1, 0.1),
    _ms("serving/decode", 100.2, 11.8),
    _ms("serving/decode/dispatch", 100.2, 6.8),
    _ms("serving/decode/dispatch/call", 100.2, 6.3),
    _ms("serving/decode/dispatch/copy_back", 106.5, 0.5),
    _ms("serving/decode/fetch", 107, 5), _ms("serving/commit", 112, 2),
    _ms("serving/tail", 114, 2),
    # B
    _ms("serving/lock_wait", 120, 0.1), _ms("serving/reclaim", 120.1, 0.1),
    _ms("serving/alloc", 120.5, 0.2), _ms("serving/prefill", 121, 19),
    _ms("serving/assign", 140, 2), _ms("serving/decode", 142, 6),
    _ms("serving/decode/dispatch", 142, 3),
    _ms("serving/decode/fetch", 145, 3), _ms("serving/commit", 148, 1),
    _ms("serving/tail", 149, 1),
    # C
    _ms("serving/lock_wait", 150, 2), _ms("serving/reclaim", 152, 0.1),
    _ms("serving/decode", 152.1, 12.9),
    _ms("serving/decode/dispatch", 152.1, 5.9),
    _ms("serving/decode/dispatch/call", 152.1, 5.4),
    _ms("serving/decode/dispatch/copy_back", 157.5, 0.5),
    _ms("serving/decode/fetch", 158, 7), _ms("serving/commit", 165, 5),
    _ms("serving/tail", 170, 2),
    # D
    _ms("serving/lock_wait", 174, 0.1), _ms("serving/reclaim", 174.1, 0.1),
    _ms("serving/idle_wait", 175, 14.5),
    _ms("serving/lock_wait", 190, 0.1),
    # a client thread
    _ms("serving/admit", 115, 7), _ms("serving/admit/lock_wait", 115, 6.5),
    _ms("serving/admit", 168, 8), _ms("serving/admit/lock_wait", 168, 7),
]
DEVICES = {"/device:TPU:0": [["fusion.2 bf16[64,2048]", 0.100, 0.09]]}


def _obs(host=HOST, window=(0.0, 1.0), **more):
    return dict({"trace": {"devices": DEVICES, "host": host},
                 "trace_window": window}, **more)


def read(name, obs):
    return manifest.reader(name)(obs)


@pytest.mark.parametrize("cell", CELLS)
def test_cycle_and_uncovered_share_of_the_pure_decode_cycles(cell):
    obs = _obs()
    # A is 20 ms and C 24: B holds a prefill, D no dispatch
    assert read(f"sched_cycle_ms.{cell}", obs) == pytest.approx(22.0)
    # 4 + 2 ms of holes in 44 ms; the submitter's spans over both holes
    # are not the scheduler thread's
    assert read(f"sched_uncovered_share.{cell}", obs) == \
        pytest.approx(100 * 6 / 44)
    # a window that cuts the last lock wait off ends the cycles at C
    assert read(f"sched_cycle_ms.{cell}", _obs(window=(0.0, 0.180))) == \
        pytest.approx(22.0)
    # ... and one that opens inside A leaves C alone
    assert read(f"sched_cycle_ms.{cell}", _obs(window=(0.110, 1.0))) == \
        pytest.approx(24.0)
    assert read(f"sched_uncovered_share.{cell}",
                _obs(window=(0.110, 1.0))) == pytest.approx(100 * 2 / 24)


def test_the_parents_trace_reads_the_same_cycle_and_a_larger_hole():
    """Without the spans this PR adds the cycle is found all the same
    (by `serving/lock_wait` and `serving/decode/dispatch`) and the
    reclaim and tail stretches are uncovered too."""
    parent = [e for e in HOST if e[0] not in ADDED_SPANS]
    obs = _obs(host=parent)
    assert read("sched_cycle_ms.serve", obs) == pytest.approx(22.0)
    assert read("sched_uncovered_share.serve", obs) == \
        pytest.approx(100 * (6 + 2 * 2.1) / 44)
    assert read("decode_dispatch_ms.serve", obs) == pytest.approx(5.9)
    assert read("commit_ms.serve", obs) == pytest.approx(2.0)


OPEN = {"fstpu_serving_decode_ticks_total": 1000.0,
        "fstpu_serving_scheduler_wall_seconds_total": 100.0,
        "fstpu_serving_scheduler_cpu_seconds_total": 10.0,
        "fstpu_serving_scheduler_wait_seconds_total": 5.0,
        "fstpu_serving_lock_wait_seconds_total": 1.0,
        "fstpu_serving_dispatch_seconds_total": 20.0,
        "fstpu_serving_dispatch_cpu_seconds_total": 2.0,
        "fstpu_serving_commit_seconds_total": 4.0,
        "fstpu_serving_commit_cpu_seconds_total": 3.0}
CLOSE = {"fstpu_serving_decode_ticks_total": 3500.0,
         "fstpu_serving_scheduler_wall_seconds_total": 150.0,
         "fstpu_serving_scheduler_cpu_seconds_total": 35.0,
         "fstpu_serving_scheduler_wait_seconds_total": 20.0,
         "fstpu_serving_lock_wait_seconds_total": 3.5,
         "fstpu_serving_dispatch_seconds_total": 40.0,
         "fstpu_serving_dispatch_cpu_seconds_total": 7.0,
         "fstpu_serving_commit_seconds_total": 8.0,
         "fstpu_serving_commit_cpu_seconds_total": 6.0}


@pytest.mark.parametrize("cell", CELLS)
def test_the_five_counter_metrics_from_a_windows_deltas(cell):
    obs = {"stats_open": OPEN, "stats_close": CLOSE}
    # 50 s of wall: 25 of CPU, 15 waited by design, 10 taken; 2,500 ticks
    assert read(f"sched_cpu_ms_per_tick.{cell}", obs) == pytest.approx(10.0)
    assert read(f"sched_taken_share.{cell}", obs) == pytest.approx(20.0)
    assert read(f"sched_lock_wait_ms_per_tick.{cell}", obs) == \
        pytest.approx(1.0)
    # dispatch: 5 s of CPU in 20 s of wall; commit: 3 in 4
    assert read(f"decode_dispatch_cpu_share.{cell}", obs) == \
        pytest.approx(25.0)
    assert read(f"commit_cpu_share.{cell}", obs) == pytest.approx(75.0)


def test_a_window_without_a_tick_or_a_wall_second_reads_nothing():
    obs = {"stats_open": OPEN, "stats_close": dict(
        OPEN, fstpu_serving_scheduler_cpu_seconds_total=11.0)}
    for name in COUNTER_METRICS:
        assert read(f"{name}.chat", obs) is None


PARENT_STATS = {"fstpu_serving_decode_ticks_total": 1000.0,
                "fstpu_serving_decode_seconds_total": 12.0}
PARENT_SHAPED = {
    "no trace, the parent's counters": {
        "trace": None, "stats_open": PARENT_STATS,
        "stats_close": dict(PARENT_STATS,
                            fstpu_serving_decode_ticks_total=3500.0)},
    "a trace with no device plane (the CPU rehearsal)": {
        "trace": {"devices": {}, "host": HOST}, "trace_window": (0.0, 1.0),
        "stats_open": {}, "stats_close": {}},
    "a trace with no span of the scheduler": _obs(
        host=[_ms("bench/traced", 0, 1000), _ms("train/step", 10, 5)]),
    "nothing at all": {},
}


@pytest.mark.parametrize("shape", sorted(PARENT_SHAPED))
@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_returns_nothing_where_there_is_nothing_to_read(
        name, shape):
    assert read(name, dict(PARENT_SHAPED[shape])) is None


def test_every_metric_has_its_file_and_one_entry_an_end_to_end_metric():
    """`.chat` moves `gap_p50_ms` in the open loop; `.serve` moves
    `serve_tokens_per_s` in every cell that reports it."""
    man = manifest.load()
    entries = {m["name"]: m for m in man["per_layer"]}
    saturated = next(m["workloads"] for m in man["end_to_end"]
                     if m["name"] == "serve_tokens_per_s")
    for name in NEW:
        entry = entries[name]
        cell = name.rsplit(".", 1)[1]
        assert entry["workloads"] == (["mistral_chat_steady"]
                                      if cell == "chat" else saturated)
        assert entry["layer"] == "scheduler"
        assert entry["moves"] == ("gap_p50_ms" if cell == "chat"
                                  else "serve_tokens_per_s")
        assert os.path.exists(os.path.join(
            manifest.BENCH, "metrics", name + ".py"))
    # the manifest stays loadable JSON well inside its size limit
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw) < 64 * 1024 and json.loads(raw) == man
