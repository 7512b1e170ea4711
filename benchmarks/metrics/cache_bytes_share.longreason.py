"""Bytes the cache holds in use over what a latent row in EVERY one of
the model's layers would hold for the same cached tokens
(`costs_kimi.cache_bytes_share`): the mean over the window's polls of
the engine's own gauges, `kv_blocks_used` (the ONE latent layer's
blocks) and `slots_active` (each live lane's delta and convolution
state in the four KDA layers). 100 would be a row a token in all five
layers; a state does not grow with the context, so the share falls as
the lanes fill."""
from benchmarks.lib import costs_kimi


def read(obs):
    if "window" not in obs or "engine_args" not in obs.get("config", {}):
        return None
    lo, hi = obs["window"]
    polls = [(used, lanes) for t, used, _, lanes, _ in obs.get("polls", [])
             if lo <= t <= hi and used]
    if not polls:
        return None
    blocks = sum(u for u, _ in polls) / len(polls)
    lanes = sum(n for _, n in polls) / len(polls)
    return 100.0 * costs_kimi.cache_bytes_share(
        blocks, lanes, obs["config"]["engine_args"]["kv_block_size"],
        obs["config"])
