"""Device time under one `serving/prefill` span, median (as
`prefill_device_ms.doc`)."""
from benchmarks.lib import manifest

read = manifest.reader("prefill_device_ms.doc")
