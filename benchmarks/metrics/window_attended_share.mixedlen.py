"""Keys a window layer's decode query reads over the tokens cached when
it reads them: deltas of `fstpu_serving_kv_window_tokens_attended_total`
over `fstpu_serving_kv_tokens_attended_total` (host arithmetic on the
cursors; min(cursor + 1, 4,096) of 1k-34k here). What a full layer in
the window layers' place would read is 100."""
from benchmarks.lib import obsutil


def read(obs):
    cached = obsutil.counter_delta(
        obs, "fstpu_serving_kv_tokens_attended_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_serving_kv_window_tokens_attended_total")
    if not cached or attended is None:
        return None
    return 100.0 * attended / cached
