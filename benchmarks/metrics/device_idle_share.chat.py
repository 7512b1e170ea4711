"""1 - the union of device operation intervals over the traced window."""
from benchmarks.lib.obsutil import idle_share as read  # noqa: F401
