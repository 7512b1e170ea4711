"""Tokens delivered a wake-up of a stream's reader
(`fstpu_stream_tokens_delivered_total` over
`fstpu_stream_wakeups_total`): 1 where every tick wakes every stream
for one token, a block's tokens where a commit delivers a block."""
from benchmarks.lib import delivery

read = delivery.tokens_per_wakeup
