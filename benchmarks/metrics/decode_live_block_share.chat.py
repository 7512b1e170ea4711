"""Blocks of the lanes' table rows the paged decode kernel walks (up to
each lane's physical cursor; a free lane's one null block) over every
block the rows name (lanes x table width): counter deltas over the
window. The share of the table that still costs a step, a fetch and a
matmul."""
from benchmarks.lib import obsutil


def read(obs):
    live = obsutil.counter_delta(obs, "fstpu_serving_kv_blocks_live_total")
    tabled = obsutil.counter_delta(
        obs, "fstpu_serving_kv_blocks_tabled_total")
    if live is None or not tabled:
        return None
    return 100.0 * live / tabled
