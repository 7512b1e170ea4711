"""1 - the union of device operation intervals over the traced window.
Four of the deployment's forty-eight layers run here, so the host's part of
a tick, and with it this share, is larger than in the deployment."""
from benchmarks.lib.obsutil import idle_share as read  # noqa: F401
