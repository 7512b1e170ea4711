"""From a run's event log to numbers: token crediting, first-token
times from the due instant, token gaps and percentiles. Pure
Python over plain records so it can be checked on synthetic logs.

A request record is a dict with `prompt_len`, `due` (open loop),
`sent`, `token_times` (client clock, one per streamed token) and
`failed`.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in (0, 1]."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def credited_tokens(records, t_open: float, t_close: float) -> dict:
    """Tokens PRODUCED inside [t_open, t_close): a request's prompt
    tokens (its real length) count at its first token, each output
    token at its own arrival. A request in flight at an edge counts
    for the part inside."""
    prompt = output = 0
    for r in records:
        times = r["token_times"]
        if times and t_open <= times[0] < t_close:
            prompt += r["prompt_len"]
        output += sum(1 for t in times if t_open <= t < t_close)
    return {"prompt": prompt, "output": output, "total": prompt + output}


def due_in_window(records, t_open: float, t_close: float) -> list:
    return [r for r in records if t_open <= r["due"] < t_close]


def ttfts(records) -> list:
    """Seconds from the instant each request was DUE to its first
    token at the client; a failed or unanswered request is infinite."""
    return [r["token_times"][0] - r["due"]
            if r["token_times"] and not r["failed"] else math.inf
            for r in records]


def token_gaps(records, t_open: float, t_close: float) -> list:
    """Gaps between consecutive streamed tokens, pooled over requests,
    each credited to the window its later token arrived in."""
    gaps = []
    for r in records:
        times = r["token_times"]
        gaps.extend(b - a for a, b in zip(times, times[1:])
                    if t_open <= b < t_close)
    return gaps
