"""`serving/prefill` span time over `serving/prefill` + `serving/decode`
span time, from the trace's host spans (as `prefill_host_share.doc`)."""
from benchmarks.lib import manifest

read = manifest.reader("prefill_host_share.doc")
