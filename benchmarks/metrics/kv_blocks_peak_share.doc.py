"""Highest `blocks_used / blocks_total` polled through the window."""


def read(obs):
    lo, hi = obs["window"]
    shares = [used / total for t, used, total, _, _ in obs.get("polls", [])
              if lo <= t <= hi and total]
    return 100.0 * max(shares) if shares else None
