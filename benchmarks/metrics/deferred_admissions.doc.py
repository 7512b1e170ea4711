"""Admissions that waited for KV blocks, counter delta over the window."""
from benchmarks.lib import obsutil


def read(obs):
    return obsutil.counter_delta(obs, "fstpu_serving_deferred_admissions_total")
