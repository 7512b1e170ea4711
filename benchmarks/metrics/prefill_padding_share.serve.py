"""Share of the prefilled bucket widths that was padding: 100 x (1 -
real prompt tokens over bucket widths prefilled), deltas of the
engine's counters over the window."""
from benchmarks.lib.serving import prefill_padding_share as read  # noqa: F401
