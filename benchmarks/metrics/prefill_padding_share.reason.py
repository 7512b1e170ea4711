"""Share of the prefilled bucket widths that was padding, deltas of the
engine's counters (as `prefill_padding_share.doc`)."""
from benchmarks.lib import manifest

read = manifest.reader("prefill_padding_share.doc")
