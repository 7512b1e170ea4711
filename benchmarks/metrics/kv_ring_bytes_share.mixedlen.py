"""K/V bytes the two pools hold in use over what ONE table for all
layers would hold for the same lanes (`costs_trinity.ring_bytes_share`):
deltas of `fstpu_serving_kv_blocks_held_total` (the full layers'
lane-long blocks) and `fstpu_serving_kv_ring_blocks_held_total` (the
window layers' ring blocks), each a per-tick sum over the lanes. 100
would be a pool that keeps every layer's rows for the whole context."""
from benchmarks.lib import costs_trinity, obsutil


def read(obs):
    blocks = obsutil.counter_delta(obs, "fstpu_serving_kv_blocks_held_total")
    ring = obsutil.counter_delta(
        obs, "fstpu_serving_kv_ring_blocks_held_total")
    if not blocks or ring is None:
        return None
    return 100.0 * costs_trinity.ring_bytes_share(blocks, ring,
                                                  obs["config"])
