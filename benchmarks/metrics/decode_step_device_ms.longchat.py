"""As `decode_step_device_ms.longdoc`: device time of one run of the decode
program on the trace's module line, median over the traced ticks."""
from benchmarks.lib import manifest

read = manifest.reader("decode_step_device_ms.longdoc")
