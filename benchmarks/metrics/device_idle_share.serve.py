"""1 - the union of device operation intervals over the traced window.
The cells run 4-16 of their deployments' layers, so the host's part of a
tick, and with it this share, is larger than in the deployment."""
from benchmarks.lib.obsutil import idle_share as read  # noqa: F401
