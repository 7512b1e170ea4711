"""Device seconds of the reveal (`fstpu_block_reveal`: the argmax, the
confidence and the pick among the masked positions of a block) and of
the head it reads (`lm_head`: `lanes x L` = 256 rows onto the whole
vocabulary every tick, a commit forward's included, whose logits nobody
reads) over the device's busy seconds, in the traced window."""
from benchmarks.lib import costs_sdar, trace_lines


def read(obs):
    return trace_lines.share_of_busy(obs, costs_sdar.REVEAL_SCOPES)
