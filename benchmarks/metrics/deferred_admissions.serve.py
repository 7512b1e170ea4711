"""Admissions that waited for KV blocks, counter delta over the window."""
from benchmarks.lib.serving import deferred_admissions as read  # noqa: F401
