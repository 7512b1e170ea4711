"""Seconds in backend compilation or in loading compiled programs, whole
run, from jax's monitoring events."""


def read(obs):
    return obs["compile_seconds"]
