"""Device time under one `serving/decode` span, median."""
from benchmarks.lib import obsutil


def read(obs):
    return obsutil.device_ms_under(obs, "serving/decode")
