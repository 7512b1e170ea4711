"""Of the pure decode cycles of the traced window (see
`sched_cycle_ms.serve`), the share of their seconds that no span of the
scheduler thread covers: 1 - union of the `serving/*` spans (the
submitters' `serving/admit*` out) over the cycles' length, %."""
from benchmarks.lib import sched

read = sched.uncovered_share
