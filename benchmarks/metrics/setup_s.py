"""Process start to window open, compilation and ramp included. Two
spans are taken out, because every run pays them whatever the program
does: the TPU runtime's own start (the first `jax.devices()`, reported
as `runtime_start_s`) and the reference's seconds, where it ran before
the window."""


def read(obs):
    return obs["seconds_to_open"]
