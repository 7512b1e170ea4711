"""MEAN delivery lag of a streamed token, ms:
`fstpu_stream_delivery_lag_seconds_total` (summed over delivered tokens,
the frame's `flush()` returned less the commit that brought the token)
over `fstpu_stream_tokens_delivered_total`. The mean, not a median: a
median is blind to the tick in some tens that waits."""
from benchmarks.lib import delivery

read = delivery.delivery_lag_ms
