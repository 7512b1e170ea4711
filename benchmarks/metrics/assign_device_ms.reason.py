"""Device time of one run of the engine's assign program, from the
trace's module line, median (as `assign_device_ms.doc`): the flat
scatter of a prefilled prompt's latent rows into the pool."""
from benchmarks.lib import manifest

read = manifest.reader("assign_device_ms.doc")
