"""Tokens delivered a block forward: deltas of
`fstpu_serving_decode_tokens_total` (every output token of a block
engine, credited at its block's commit) over
`fstpu_serving_block_forwards_total` (live lanes summed over the ticks).
A block of `L` positions takes `denoise_steps` reveal forwards and one
commit forward: `L / (steps + 1)` at its best (4 / 3 here), less what a
request's cut last block and its prompt's tail leave unused."""
from benchmarks.lib import obsutil


def read(obs):
    forwards = obsutil.counter_delta(
        obs, "fstpu_serving_block_forwards_total")
    tokens = obsutil.counter_delta(obs, "fstpu_serving_decode_tokens_total")
    if not forwards or tokens is None:
        return None
    return tokens / forwards
