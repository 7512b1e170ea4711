"""The trace reduction against a small recorded trace (0.4 s of the
saturated serving cell on the chip) and against hand-made intervals."""

import json
import os

import pytest

from benchmarks.lib import xplane, xplane_attrs

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures", "doc_trace_400ms.json")) as f:
        return json.load(f)


def test_recorded_trace_busy_idle_and_time_under_spans(recorded):
    lo, hi = xplane.window(recorded)
    assert (lo, hi) == (0.0, 0.4)
    busy = xplane.busy_seconds(recorded, lo, hi)
    assert busy == pytest.approx(0.345047427, rel=1e-6)
    index = xplane_attrs.Busy(recorded, lo, hi)

    def under(span):
        return [index.seconds(a, b)
                for a, b in xplane.spans(recorded, span, lo, hi)]
    decode = under("serving/decode")
    assert len(decode) == 5
    assert all(d == pytest.approx(0.0521, abs=4e-4) for d in decode)
    prefill = under("serving/prefill")
    assert prefill == [pytest.approx(0.069535389, rel=1e-6)]
    # device work under the engine thread's spans is nearly all of it
    assert sum(decode) + sum(prefill) == pytest.approx(busy, rel=0.05)


def test_recorded_trace_breakdown_takes_children_out_of_their_parents(
        recorded):
    lo, hi = xplane.window(recorded)
    own = xplane.self_seconds(xplane.first_device(recorded), lo, hi)
    # a `while` holds its body's operations: its own time is what is
    # left, and the sum over names is the busy time, not twice it
    assert sum(own.values()) == pytest.approx(
        xplane.busy_seconds(recorded, lo, hi), rel=0.02)
    top = xplane.top_ops(recorded, lo, hi, 3)
    assert top[0][0] == "self_attn.7 bf16[32,1,32,128]"
    assert top[0][1] == pytest.approx(0.033553278, rel=1e-6)
    gaps = dict(xplane.idle_gaps(recorded, lo, hi))
    assert sum(gaps.values()) == pytest.approx(
        0.4 - xplane.busy_seconds(recorded, lo, hi), rel=1e-6)
    assert gaps["serving/decode"] == pytest.approx(0.021923162, rel=1e-6)


def test_hand_made_intervals():
    trace = {"devices": {"/device:TPU:0": [["a", 0.0, 1.0], ["b", 0.5, 1.0],
                                           ["c", 3.0, 1.0]],
                         "/device:TPU:1": [["a", 0.0, 4.0]]},
             "host": [["bench/traced", 0.0, 4.0], ["x/step", 0.0, 2.0],
                      ["x/wait", 1.5, 1.4]]}
    assert xplane.merged(trace["devices"]["/device:TPU:0"], 0, 4) == \
        [[0.0, 1.5], [3.0, 4.0]]
    # chip 0 is busy 2.5 s, chip 1 4 s: the mean over the chips
    assert xplane.busy_seconds(trace, 0, 4) == pytest.approx(3.25)
    assert xplane.busy_seconds(trace, 1, 3.5) == pytest.approx(
        (0.5 + 0.5 + 2.5) / 2)
    assert xplane_attrs.Busy(trace, 0, 4).seconds(0.0, 2.0) == \
        pytest.approx(1.5)
    # the one gap, 1.5..3.0, lies mostly under x/wait
    assert xplane.idle_gaps(trace, 0, 4) == [("x/wait", pytest.approx(1.5))]


def test_nested_operations_and_collectives():
    events = [["while.1 s32[]", 0.0, 10.0], ["fusion.2 f32[8]", 1.0, 3.0],
              ["all-gather-done.3 bf16[4]", 5.0, 2.0], ["copy.4 f32[2]", 11, 1]]
    own = xplane.self_seconds(events, 0, 20)
    assert own == {"while.1 s32[]": pytest.approx(5.0),
                   "fusion.2 f32[8]": pytest.approx(3.0),
                   "all-gather-done.3 bf16[4]": pytest.approx(2.0),
                   "copy.4 f32[2]": pytest.approx(1.0)}
    trace = {"devices": {"/device:TPU:0": events}, "host": []}
    assert xplane.op_seconds(trace, xplane.COLLECTIVE, 0, 6) == \
        pytest.approx(1.0)


def test_the_hlo_text_is_cut_to_a_name_and_a_shape():
    text = ("%fusion.155 = bf16[2048,14336]{1,0:T(8,128)(2,1)} fusion("
            "bf16[2048,4096]{1,0} %x), kind=kOutput, calls=%fused")
    assert xplane.short_name(text) == "fusion.155 bf16[2048,14336]"
    assert xplane.short_name("%while.3 = (s32[]{:T(128)}, bf16[32,1,4096]"
                             "{2,0,1}) while(...)") == "while.3 s32[]"
    assert xplane.short_name("train/step") == "train/step"
