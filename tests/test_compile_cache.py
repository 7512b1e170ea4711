"""The persistent compilation cache is placed from outside
(`JAX_COMPILATION_CACHE_DIR`) or at one fixed in-checkout path — never
at a path that moves, because the path is part of the cache key."""

import os
import tempfile

import jax
import pytest

from fengshen_tpu import compile_cache
from fengshen_tpu.compile_cache import (CACHE_DIR_ENV, DEFAULT_CACHE_DIR,
                                        ensure_compile_cache)


@pytest.fixture
def cache_config(monkeypatch):
    """Run with the variable unset and put jax's setting back after."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_placed_cache_is_left_alone(cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    cache_config.setenv(CACHE_DIR_ENV, str(tmp_path))
    assert ensure_compile_cache() == str(tmp_path)
    # jax reads the variable itself; no code sets another directory
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_one_fixed_path_in_the_checkout(cache_config):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert ensure_compile_cache() == DEFAULT_CACHE_DIR
    assert ensure_compile_cache() == DEFAULT_CACHE_DIR     # idempotent
    assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    assert DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_compile_cache")
    assert not DEFAULT_CACHE_DIR.startswith(tempfile.gettempdir())
    # git-ignored: caches are made at run time, never committed
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_trainer_and_engine_place_the_cache_before_compiling(
        cache_config):
    """`Trainer.__init__` and `create_continuous_engine` both call it
    first."""
    import argparse

    from fengshen_tpu.api.main import create_continuous_engine
    from fengshen_tpu.parallel import set_mesh
    from fengshen_tpu.trainer import Trainer, add_trainer_args

    calls = []
    cache_config.setattr(
        "fengshen_tpu.trainer.trainer.ensure_compile_cache",
        lambda: calls.append("trainer"))
    cache_config.setattr(compile_cache, "ensure_compile_cache",
                         lambda: calls.append("engine"))
    parser = argparse.ArgumentParser()
    add_trainer_args(parser)
    try:
        Trainer(parser.parse_args([]))
    finally:
        set_mesh(None)
    with pytest.raises(ValueError, match="generation pipeline"):
        create_continuous_engine(object(), {})   # not a pipeline
    assert calls == ["trainer", "engine"]
