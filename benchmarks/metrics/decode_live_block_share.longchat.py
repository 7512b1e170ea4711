"""Blocks the folded decode kernel walks (up to each lane's physical
cursor) over the blocks the lanes' 144-wide table rows name, counter
deltas over the window (as `decode_live_block_share.chat`)."""
from benchmarks.lib import manifest

read = manifest.reader("decode_live_block_share.chat")
