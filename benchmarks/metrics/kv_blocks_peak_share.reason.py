"""Highest `blocks_used / blocks_total` polled through the window (as
`kv_blocks_peak_share.doc`)."""
from benchmarks.lib import manifest

read = manifest.reader("kv_blocks_peak_share.doc")
