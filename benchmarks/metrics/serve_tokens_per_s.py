"""Prompt tokens plus output tokens PRODUCED inside the window, over its
seconds: a prompt counts at its first token, an output token at its own
arrival at the client."""
from benchmarks.lib import reduce as R


def read(obs):
    lo, hi = obs["window"]
    return R.credited_tokens(obs["records"], lo, hi)["total"] / (hi - lo)
