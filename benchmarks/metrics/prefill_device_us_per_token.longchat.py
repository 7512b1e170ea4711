"""As `prefill_device_us_per_token.longdoc`: device time of the window
program over the tokens its runs were wide."""
from benchmarks.lib import manifest

read = manifest.reader("prefill_device_us_per_token.longdoc")
