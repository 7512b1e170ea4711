"""Tokens a layer's decode query is left to read by the indexer over the
tokens it scored to choose them: deltas of
`fstpu_index_tokens_selected_total` over `fstpu_index_tokens_scored_total`
(host arithmetic on the cursors; 2,048 of 8k-33k here)."""
from benchmarks.lib import obsutil


def read(obs):
    scored = obsutil.counter_delta(obs, "fstpu_index_tokens_scored_total")
    selected = obsutil.counter_delta(
        obs, "fstpu_index_tokens_selected_total")
    if not scored or selected is None:
        return None
    return 100.0 * selected / scored
