"""Seconds the first `jax.devices()` of the process took: the TPU
runtime coming up, before any line of the program or the benchmark
touches the chip. It is left out of `setup_s` and shown here."""


def read(obs):
    return obs["runtime_start_seconds"]
