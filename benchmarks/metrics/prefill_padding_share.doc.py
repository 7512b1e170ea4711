"""Share of the prefilled bucket widths that was padding: 100 x (1 -
real prompt tokens over bucket widths prefilled), deltas of the
engine's counters over the window."""
from benchmarks.lib import obsutil


def read(obs):
    padded = obsutil.counter_delta(
        obs, "fstpu_serving_prefill_padded_tokens_total")
    if not padded:
        return None
    return 100.0 * (1.0 - obsutil.counter_delta(
        obs, "fstpu_serving_prefill_tokens_total") / padded)
