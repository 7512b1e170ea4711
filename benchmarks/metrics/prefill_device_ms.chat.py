"""Device time under one `serving/prefill` span, median."""
from benchmarks.lib import obsutil


def read(obs):
    return obsutil.device_ms_under(obs, "serving/prefill")
