"""Admissions that waited for KV blocks, counter delta over the window
(as `deferred_admissions.doc`)."""
from benchmarks.lib import manifest

read = manifest.reader("deferred_admissions.doc")
