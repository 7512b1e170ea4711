"""Blocks the paged decode kernel walks over the blocks the lanes'
table rows name, counter deltas over the window (as
`decode_live_block_share.chat`)."""
from benchmarks.lib import manifest

read = manifest.reader("decode_live_block_share.chat")
