"""The one compile cache: jax's own persistent compilation cache.

- It is placed from outside (`JAX_COMPILATION_CACHE_DIR`) or at one
  fixed in-checkout path — never at a path that moves, because the path
  is part of the cache key.
- A restarted engine and a second `Trainer.fit` load what the first
  compiled: zero `cache_misses` and the same outputs.
- jax keys an entry on the lowered program and the compile options, so
  a different rules table, offload placement, kernel dispatch, draft
  depth or engine config is a different entry — no layer keeps a
  fingerprint string up for it.
- A damaged cache costs a recompile, never a start.
- The options of the hand-written cache that this one replaced are
  refused by name.

jax persists on the CPU backend too, and says what it did through
`jax.monitoring` (`/jax/compilation_cache/cache_hits`, `cache_misses`).
"""

import argparse
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
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


# ---- the cache at work --------------------------------------------------

_SETTINGS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")


class _Cache:
    """One test's cache directory and what jax said it did with it."""

    def __init__(self, path):
        self.path = str(path)
        self._events = []

    def listen(self, event, **kwargs):
        self._events.append(event)

    def take(self) -> dict:
        """`cache_hits` / `cache_misses` since the last call."""
        counts = {name: self._events.count(
            "/jax/compilation_cache/" + name)
            for name in ("cache_hits", "cache_misses")}
        self._events.clear()
        return counts

    def entries(self, program: str = "") -> set:
        """The entries of `jit(<program>)`, one a distinct key."""
        return {f for f in os.listdir(self.path)
                if f.startswith(f"jit_{program}") and f.endswith("-cache")}

    def point_at(self, path) -> None:
        from jax.experimental.compilation_cache import compilation_cache
        self.path = str(path)
        jax.config.update("jax_compilation_cache_dir", self.path)
        compilation_cache.reset_cache()


@pytest.fixture
def cache(tmp_path, monkeypatch):
    """jax's persistent cache, on (tests/conftest.py turns it off), at a
    directory of this test's own, keeping every program however small;
    the four settings are put back after."""
    from jax.experimental.compilation_cache import compilation_cache
    before = {name: getattr(jax.config, name) for name in _SETTINGS}
    cache = _Cache(tmp_path / "cache")
    os.mkdir(cache.path)
    # the way a deployment places it: `ensure_compile_cache` then
    # leaves jax's setting alone
    monkeypatch.setenv(CACHE_DIR_ENV, cache.path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache.point_at(cache.path)
    jax.monitoring.register_event_listener(cache.listen)
    try:
        yield cache
    finally:
        jax.monitoring.unregister_event_listener(cache.listen)
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def tiny():
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.utils.generate import generate
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=3, num_attention_heads=4,
                      max_position_embeddings=64, dtype="float32")
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.RandomState(6)
    prompts = [rng.randint(3, 96, n).astype(np.int32) for n in (5, 11)]
    refs = [np.asarray(generate(model, params, jnp.asarray(p)[None],
                                max_new_tokens=6))[0, len(p):].tolist()
            for p in prompts]
    return model, params, prompts, refs


def _serve(tiny, **engine_kw):
    """A replica's start: build, warm up, serve. Greedy tokens must be
    the sequential decode's, whatever the programs came from."""
    from fengshen_tpu.serving import (ContinuousBatchingEngine,
                                      EngineConfig)
    model, params, prompts, refs = tiny
    kw = dict(num_slots=2, buckets=(8, 16), max_new_tokens=6, max_queue=8)
    engine = ContinuousBatchingEngine(model, params,
                                      EngineConfig(**{**kw, **engine_kw}))
    engine.warmup()
    assert engine.generate_all(prompts) == refs
    return engine


ENGINES = {
    "slot-fp32": {},
    "slot-int8": dict(kv_dtype="int8"),
    "paged-fp32": dict(kv_layout="paged", kv_block_size=16),
    "paged-int8": dict(kv_layout="paged", kv_block_size=16,
                       kv_dtype="int8"),
    "prompt_lookup": dict(spec_mode="prompt_lookup", spec_gamma=3),
    "self_draft": dict(spec_mode="self_draft", spec_gamma=3,
                       spec_draft_layers=1),
}
ENGINE_PROGRAMS = ("prefill_fn", "assign_fn", "decode_fn")


@pytest.mark.parametrize("case", ENGINES)
def test_engine_restart_hits_the_persistent_cache(cache, tiny, case):
    _serve(tiny, **ENGINES[case])
    cold = cache.take()
    assert cold["cache_misses"] >= len(ENGINE_PROGRAMS) + 1
    kept = {p: cache.entries(p) for p in ENGINE_PROGRAMS}
    assert len(kept["prefill_fn"]) == 2         # one a bucket
    assert len(kept["assign_fn"]) == len(kept["decode_fn"]) == 1

    _serve(tiny, **ENGINES[case])               # the restart
    warm = cache.take()
    assert warm["cache_misses"] == 0
    assert warm["cache_hits"] >= len(ENGINE_PROGRAMS) + 1
    assert {p: cache.entries(p) for p in ENGINE_PROGRAMS} == kept


def _parse(argv):
    from fengshen_tpu.data.universal_datamodule import UniversalDataModule
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import add_trainer_args
    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    return parser.parse_args(argv)


def _fit(root, extra=()):
    """Four steps of a one-layer llama on a 2x2x2 (data, fsdp, tensor)
    mesh; returns the final state and the logged losses."""
    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.parallel import set_mesh
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule

    args = _parse(["--train_batchsize", "8", "--learning_rate", "1e-3",
                   "--warmup_steps", "1", "--log_every_n_steps", "1",
                   "--max_steps", "4", "--data_parallel_size", "2",
                   "--fsdp_parallel_size", "2",
                   "--tensor_model_parallel_size", "2",
                   "--default_root_dir", str(root), *extra])
    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=1, num_attention_heads=2,
                      max_position_embeddings=32, dtype="float32")
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 63, 16).tolist()}
            for _ in range(64)]

    class Rows:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]
    try:
        state = Trainer(args).fit(
            CausalLMModule(args, LlamaForCausalLM(cfg), cfg),
            UniversalDataModule(args=args, datasets={"train": Rows()}))
    finally:
        set_mesh(None)
    with open(os.path.join(root, "metrics.jsonl")) as f:
        entries = [json.loads(line) for line in f]
    return state, [e["loss"] for e in entries if "loss" in e]


STEP_PROGRAMS = {
    "train_step": [],
    "multi_step": ["--steps_per_execution", "2"],
    "accumulated": ["--accumulate_grad_batches", "2"],
}


@pytest.mark.parametrize("program", STEP_PROGRAMS)
def test_second_fit_hits_the_persistent_cache(cache, tmp_path, program):
    """The restart and the rewind: the second fit loads the step the
    first compiled and trains identically."""
    name = "train_step" if program == "accumulated" else program
    state_a, losses_a = _fit(tmp_path / "a", STEP_PROGRAMS[program])
    assert cache.take()["cache_misses"] >= 1
    kept = cache.entries(name)
    assert len(kept) == 1

    state_b, losses_b = _fit(tmp_path / "b", STEP_PROGRAMS[program])
    warm = cache.take()
    assert warm["cache_misses"] == 0 and warm["cache_hits"] >= 1
    assert cache.entries(name) == kept
    assert int(state_a.step) == int(state_b.step) == 4
    assert losses_a == losses_b
    for a, b in zip(jax.tree_util.tree_leaves(state_a.params),
                    jax.tree_util.tree_leaves(state_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# what the removed fingerprints defended: (the changed configuration's
# program, whose entry must be a new one; a run of the configuration,
# plain or changed, returning what tells the two apart)

def _run_rules_table(tiny, root, changed, monkeypatch):
    from fengshen_tpu.sharding import (DEFAULT_LOGICAL_AXIS_RULES,
                                       use_rules)
    table = tuple((k, None) if changed and k == "mlp" else (k, v)
                  for k, v in DEFAULT_LOGICAL_AXIS_RULES)
    with use_rules(table):
        state, _ = _fit(root)
    specs = {str(leaf.sharding.spec)
             for leaf in jax.tree_util.tree_leaves(state.params)}
    return sorted(specs)


def _run_offload_placement(tiny, root, changed, monkeypatch):
    state, _ = _fit(root, ["--offload", "opt" if changed else "none"])
    return sorted({leaf.sharding.memory_kind for leaf in
                   jax.tree_util.tree_leaves(state.opt_state)
                   if hasattr(leaf, "sharding")})


def _run_kernel_dispatch(tiny, root, changed, monkeypatch):
    """One call site, one set of shapes; the probe's answer alone moves
    (off the TPU the Mosaic kernel runs in interpret mode)."""
    from fengshen_tpu.ops.pallas import FORCE_ENV, dispatch_table, probe
    from fengshen_tpu.ops.pallas.decode_attention import (
        decode_attention, xla_decode_attention)
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 1, 8, 128) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(2, 128, 8, 128) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(2, 128, 8, 128) * 0.3, jnp.float32)
    valid = jnp.asarray(rng.rand(2, 1, 128) < 0.7)
    if changed:
        monkeypatch.setenv(FORCE_ENV, "pallas")
    else:
        monkeypatch.delenv(FORCE_ENV, raising=False)
    probe(refresh=True)
    try:
        def decode_read(q, k, v, valid):
            return decode_attention(q, k, v, valid, interpret=True)
        out = jax.jit(decode_read)(q, k, v, valid)
        took = dispatch_table()["decode_attention"]
    finally:
        monkeypatch.delenv(FORCE_ENV, raising=False)
        probe(refresh=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(xla_decode_attention(q, k, v, valid)),
        rtol=2e-5, atol=2e-5)
    return took


def _run_draft_depth(tiny, root, changed, monkeypatch):
    engine = _serve(tiny, spec_mode="self_draft", spec_gamma=3,
                    spec_draft_layers=2 if changed else 1)
    return engine._draft_model.config.num_hidden_layers


def _run_engine_config(tiny, root, changed, monkeypatch):
    engine = _serve(tiny, num_slots=3 if changed else 2)
    return engine.stats()["kv_blocks_total"]


DIFFERENT_PROGRAMS = {
    "rules_table": ("train_step", _run_rules_table),
    "offload_placement": ("grad_step", _run_offload_placement),
    "kernel_dispatch": ("decode_read", _run_kernel_dispatch),
    "draft_depth": ("decode_fn", _run_draft_depth),
    "engine_config": ("decode_fn", _run_engine_config),
}


@pytest.mark.parametrize("case", DIFFERENT_PROGRAMS)
def test_a_different_program_never_hits(cache, tiny, tmp_path,
                                        monkeypatch, case):
    program, run = DIFFERENT_PROGRAMS[case]
    plain = run(tiny, tmp_path / "cold", False, monkeypatch)
    cache.take()
    assert run(tiny, tmp_path / "warm", False, monkeypatch) == plain
    assert cache.take()["cache_misses"] == 0        # a warm start
    kept = cache.entries(program)

    changed = run(tiny, tmp_path / "changed", True, monkeypatch)
    assert changed != plain
    assert cache.take()["cache_misses"] >= 1
    assert cache.entries(program) > kept            # a key of its own

    # and the two coexist: the plain configuration still starts warm
    assert run(tiny, tmp_path / "again", False, monkeypatch) == plain
    assert cache.take()["cache_misses"] == 0


def _damage_corrupt_entry(cache, tmp_path):
    for entry in cache.entries():
        with open(os.path.join(cache.path, entry), "wb") as f:
            f.write(b"not an executable")


def _damage_unwritable_dir(cache, tmp_path):
    # a file stands where the directory should be (a mode bit would
    # not stop root, which the tests run as)
    blocked = tmp_path / "blocked"
    blocked.write_text("")
    cache.point_at(blocked)


@pytest.mark.parametrize("damage", [_damage_corrupt_entry,
                                    _damage_unwritable_dir],
                         ids=["corrupt_entry", "unwritable_dir"])
def test_a_damaged_cache_never_fails_a_start(cache, tiny, tmp_path,
                                             damage):
    """jax reports the entry it could not read or write as a warning
    and compiles; the engine warms up and serves the right tokens."""
    _serve(tiny)
    damage(cache, tmp_path)
    cache.take()
    with pytest.warns(UserWarning,
                      match="persistent compilation cache"):
        _serve(tiny)
    assert cache.take()["cache_hits"] == 0


# ---- what this cache replaced -------------------------------------------

def _refused_server_block(tmp_path, capsys):
    from fengshen_tpu.api.main import load_config
    path = tmp_path / "server.json"
    path.write_text(json.dumps({
        "SERVER": {"engine": "continuous"},
        "PIPELINE": {"task": "text_generation"},
        "AOT": {"cache_dir": str(tmp_path)}}))
    with pytest.raises(ValueError) as refusal:
        load_config(str(path))
    return str(refusal.value)


def _refused_trainer_flag(tmp_path, capsys):
    from fengshen_tpu.trainer import add_trainer_args
    parser = argparse.ArgumentParser()
    add_trainer_args(parser)
    with pytest.raises(SystemExit) as refusal:
        parser.parse_args(["--aot_cache_dir", str(tmp_path)])
    assert refusal.value.code == 2
    return capsys.readouterr().err


@pytest.mark.parametrize("refused", [_refused_server_block,
                                     _refused_trainer_flag],
                         ids=["server_AOT_block", "aot_cache_dir_flag"])
def test_removed_options_are_refused(tmp_path, capsys, refused):
    """Input from outside is checked, not silently ignored: a launch
    script written for the removed cache is told where the cache is
    placed now."""
    message = refused(tmp_path, capsys)
    assert "removed" in message and CACHE_DIR_ENV in message
