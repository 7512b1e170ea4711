"""What every serving cell's common readers read of the engine's
`/metrics` counters and of the harness's polls: one function a metric,
so a cell's entry (`<metric>.serve` for the saturated cells, `.chat` for
the open loop, whose entries move another end-to-end metric) is a file
that names the function and nothing else. A function that finds no
counter (the parent of the PR that added it) returns None."""

from __future__ import annotations

from benchmarks.lib import obsutil

TICKS = "fstpu_serving_decode_ticks_total"


def _share(obs, part: str, whole: str):
    """100 x delta of `part` over delta of `whole`, over the window."""
    a, b = obsutil.counter_delta(obs, part), obsutil.counter_delta(obs, whole)
    if a is None or not b:
        return None
    return 100.0 * a / b


def lane_occupancy(obs):
    """Occupied over total slot ticks, %."""
    return _share(obs, "fstpu_serving_occupied_slot_ticks_total",
                  "fstpu_serving_slot_ticks_total")


def deferred_admissions(obs):
    """Admissions that waited for KV blocks, a count over the window."""
    return obsutil.counter_delta(obs,
                                 "fstpu_serving_deferred_admissions_total")


def kv_blocks_peak_share(obs):
    """Highest `blocks_used / blocks_total` polled through the window."""
    if "window" not in obs:
        return None
    lo, hi = obs["window"]
    shares = [used / total for t, used, total, _, _ in obs.get("polls", [])
              if lo <= t <= hi and total]
    return 100.0 * max(shares) if shares else None


def prefill_padding_share(obs):
    """Share of the prefilled bucket widths that was padding: 100 x (1 -
    real prompt tokens over bucket widths prefilled)."""
    real = _share(obs, "fstpu_serving_prefill_tokens_total",
                  "fstpu_serving_prefill_padded_tokens_total")
    return None if real is None else 100.0 - real


def decode_ahead_share(obs):
    """Decode ticks enqueued while the previous tick's tokens were still
    unfetched, over all decode ticks: the share of ticks whose host part
    ran under the device's."""
    return _share(obs, "fstpu_serving_decode_ticks_ahead_total", TICKS)


def decode_live_block_share(obs):
    """Blocks of the lanes' table rows a paged decode kernel walks (up
    to each lane's physical cursor; a free lane's one null block) over
    every block the rows name (lanes x table width): the share of the
    table that still costs a step, a fetch and a matmul."""
    return _share(obs, "fstpu_serving_kv_blocks_live_total",
                  "fstpu_serving_kv_blocks_tabled_total")
