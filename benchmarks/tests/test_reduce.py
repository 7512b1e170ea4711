"""Token crediting at the window's edges, open-loop timing from the due
instant and percentiles, on synthetic event logs."""

import math

import pytest

from benchmarks.lib import reduce as R


def rec(prompt_len, times, due=0.0, failed=False, sent=None):
    return {"prompt_len": prompt_len, "token_times": times, "due": due,
            "failed": failed, "sent": due if sent is None else sent}


def test_a_request_counts_for_the_part_inside_the_window():
    records = [
        rec(100, [9.0, 9.5, 10.0, 10.5, 11.0]),    # starts before the edge
        rec(200, [10.2, 10.4, 19.9, 20.0, 20.1]),  # ends after it
        rec(300, [12.0, 13.0]),                    # wholly inside
        rec(400, [20.0, 21.0]),                    # wholly after
        rec(500, []),                              # never answered
    ]
    got = R.credited_tokens(records, 10.0, 20.0)
    # prompts count at their first token: 200 and 300 are inside
    assert got["prompt"] == 500
    # output tokens at their own arrival: 3 + 3 + 2
    assert got["output"] == 8
    assert got["total"] == 508


def test_adjacent_windows_add_up_to_the_whole():
    records = [rec(7, [0.5 + 0.37 * i for i in range(40)]),
               rec(11, [3.1 + 0.21 * i for i in range(60)])]
    whole = R.credited_tokens(records, 0.0, 30.0)["total"]
    parts = sum(R.credited_tokens(records, a, a + 5.0)["total"]
                for a in range(0, 30, 5))
    assert whole == parts == 7 + 11 + 100


def test_ttft_runs_from_the_due_instant_not_from_the_send():
    late = rec(10, [5.30], due=5.0, sent=5.2)      # generator ran late
    ok = rec(10, [7.05], due=7.0)
    lost = rec(10, [], due=8.0)
    bad = rec(10, [9.1], due=9.0, failed=True)
    outside = rec(10, [31.0], due=30.5)
    due = R.due_in_window([late, ok, lost, bad, outside], 0.0, 30.0)
    assert len(due) == 4
    t = R.ttfts(due)
    assert t[0] == pytest.approx(0.30) and t[1] == pytest.approx(0.05)
    assert t[2] == math.inf and t[3] == math.inf
    # a missing request counts as missing the tail
    assert R.percentile(t, 0.90) == math.inf
    assert R.percentile(t, 0.50) == pytest.approx(0.30)


def test_gaps_pool_over_requests_and_belong_to_their_later_token():
    a = rec(1, [0.9, 1.0, 1.1, 1.4])
    b = rec(1, [1.95, 2.05])
    gaps = R.token_gaps([a, b], 1.0, 2.0)
    assert sorted(round(g, 6) for g in gaps) == [0.1, 0.1, 0.3]


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert R.percentile(values, 0.90) == 90
    assert R.percentile(values, 0.95) == 95
    assert R.percentile([5.0], 0.9) == 5.0
    with pytest.raises(ValueError):
        R.percentile([], 0.5)
