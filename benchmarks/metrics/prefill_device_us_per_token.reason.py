"""Device time under the traced `serving/prefill` spans over the sum of
their `bucket` attributes (as `prefill_device_us_per_token.doc`)."""
from benchmarks.lib import manifest

read = manifest.reader("prefill_device_us_per_token.doc")
