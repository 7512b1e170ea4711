"""Resilience subsystem tests (fast CPU lane — NOT marked slow).

Every behavior is driven by the deterministic fault-injection harness
(`fengshen_tpu.resilience.faults.FaultPlan`): injected NaN losses hit
the in-graph step guard, injected loader faults hit ResilientLoader's
retry/backoff, a real SIGTERM hits the preemption autosave, and a
truncated checkpoint step hits maybe_restore's newest→oldest fallback.
"""

import argparse
import json
import os
import signal

import jax
import numpy as np
import pytest

from fengshen_tpu.resilience import (FaultPlan, InjectedLoaderFault,
                                     ResilientLoader,
                                     truncate_checkpoint_step)


# -- ResilientLoader unit tests (no jit, no model) -----------------------

class _FlakyLoader:
    """Yields `data`, raising `fail_at[pos] -> times` before yielding
    that position; advance-before-yield like a storage-backed loader
    whose read fails AFTER the cursor moved when `advance_first`."""

    def __init__(self, data, fail_at, advance_first=False):
        self.data = list(data)
        self.fail_at = dict(fail_at)
        self.advance_first = advance_first
        self.pos = 0

    def skip_next(self):
        if self.pos < len(self.data):
            self.pos += 1

    def __iter__(self):
        while self.pos < len(self.data):
            i = self.pos
            if self.advance_first:
                self.pos += 1
            if self.fail_at.get(i, 0) > 0:
                self.fail_at[i] -= 1
                raise IOError(f"flaky read at {i}")
            if not self.advance_first:
                self.pos += 1
            yield self.data[i]


def test_resilient_loader_retries_with_backoff():
    sleeps = []
    inner = _FlakyLoader(range(5), {2: 3})
    loader = ResilientLoader(inner, max_retries=3, backoff_base=0.1,
                             sleep=sleeps.append, resumable=True)
    assert list(loader) == [0, 1, 2, 3, 4]  # nothing lost
    assert loader.retries_total == 3
    assert loader.skipped_total == 0
    assert len(sleeps) == 3
    # exponential backoff with bounded jitter: base*2^(n-1) .. 1.25x
    for n, s in enumerate(sleeps, start=1):
        assert 0.1 * 2 ** (n - 1) <= s <= 0.1 * 2 ** (n - 1) * 1.25


def test_resilient_loader_exhausts_then_raises():
    inner = _FlakyLoader(range(3), {1: 99})
    loader = ResilientLoader(inner, max_retries=2, backoff_base=0,
                             sleep=lambda s: None, resumable=True)
    with pytest.raises(IOError):
        list(loader)
    assert loader.retries_total == 3  # 1 initial + 2 retries counted


def test_resilient_loader_skip_budget():
    # a batch failing deterministically at the SAME position exhausts
    # its retries, then the skip budget kicks in via the cooperative
    # skip_next() protocol: the poison batch is dropped, the epoch
    # completes
    events = []
    inner = _FlakyLoader(range(4), {1: 99})
    loader = ResilientLoader(inner, max_retries=1, backoff_base=0,
                             skip_batch_budget=1, sleep=lambda s: None,
                             log=events.append, resumable=True)
    assert list(loader) == [0, 2, 3]
    assert loader.skipped_total == 1
    kinds = [e["event"] for e in events]
    assert "loader_retry" in kinds and "loader_skip_batch" in kinds


class _RestartingLoader:
    """Restarts from batch 0 on every iter() — like a val loader over
    `_SimpleBatchSampler`; deterministic, not mid-epoch resumable."""

    def __init__(self, data, fail_at):
        self.data = list(data)
        self.fail_at = dict(fail_at)

    def __iter__(self):
        for i, x in enumerate(self.data):
            if self.fail_at.get(i, 0) > 0:
                self.fail_at[i] -= 1
                raise IOError(f"flaky read at {i}")
            yield x


def test_resilient_loader_fast_forwards_non_resumable():
    """A non-resumable (restart-on-iter) loader must not re-deliver
    already-yielded batches after a retry — the val path would
    double-count losses otherwise."""
    inner = _RestartingLoader(range(4), {2: 1})
    loader = ResilientLoader(inner, max_retries=2, backoff_base=0,
                             sleep=lambda s: None)
    assert not loader.resumable  # auto-detected: no stateful sampler
    assert list(loader) == [0, 1, 2, 3]  # no [0, 1, 0, 1, ...] replay
    assert loader.retries_total == 1


def test_resilient_loader_retries_same_batch_on_real_dataloader():
    """The production path: DataLoader + stateful PretrainingRandomSampler
    with a dataset whose fetch fails transiently. The sampler advances
    only AFTER a batch is fully delivered, so the retry re-fetches the
    SAME indices — no data is silently dropped."""
    from fengshen_tpu.data import (DataLoader, PretrainingRandomSampler)

    fail = {"remaining": 2, "at_call": 5}
    calls = {"n": 0}

    class FlakyDS:
        def __len__(self):
            return 16

        def __getitem__(self, i):
            calls["n"] += 1
            if calls["n"] == fail["at_call"] and fail["remaining"] > 0:
                fail["remaining"] -= 1
                fail["at_call"] = calls["n"] + 1  # fail the retry once too
                raise IOError("flaky storage read")
            return {"input_ids": [i] * 4}

    sampler = PretrainingRandomSampler(16, 0, 4, 0, 1, epoch_seed=3)
    loader = ResilientLoader(DataLoader(FlakyDS(), sampler,
                                        global_batch_size=4),
                             max_retries=3, backoff_base=0,
                             sleep=lambda s: None)
    assert loader.resumable  # auto-detected from the stateful sampler
    got = [b["input_ids"][:, 0].tolist() for b in loader]

    # clean reference epoch: identical batches, nothing dropped
    ref_sampler = PretrainingRandomSampler(16, 0, 4, 0, 1, epoch_seed=3)
    ref = [sorted(idx) for idx in ref_sampler]
    assert [sorted(b) for b in got] == ref
    assert loader.retries_total == 2


def test_resilient_loader_skip_budget_on_real_dataloader():
    """A deterministically-poisoned sample on the production DataLoader:
    retries exhaust (unconsume keeps retrying the SAME batch), then the
    skip budget drops exactly that batch via DataLoader.skip_next and
    the epoch completes."""
    from fengshen_tpu.data import DataLoader, PretrainingRandomSampler

    POISON = 11

    class PoisonDS:
        def __len__(self):
            return 16

        def __getitem__(self, i):
            if i == POISON:
                raise IOError("permanently corrupt row")
            return {"input_ids": [i] * 4}

    sampler = PretrainingRandomSampler(16, 0, 4, 0, 1, epoch_seed=3)
    loader = ResilientLoader(DataLoader(PoisonDS(), sampler,
                                        global_batch_size=4),
                             max_retries=2, backoff_base=0,
                             skip_batch_budget=1, sleep=lambda s: None)
    got = [i for b in loader for i in b["input_ids"][:, 0].tolist()]
    assert loader.skipped_total == 1
    assert POISON not in got
    # the 3 clean batches (12 rows) all arrived, nothing else dropped
    assert len(got) == 12 and len(set(got)) == 12
    # the skip advanced the sampler cursor past the poison batch too
    assert sampler.consumed_samples == 16


def test_resilient_loader_no_fake_skips_on_non_resumable():
    """A restart-on-iter loader re-produces a poison batch on every
    re-entry, so no wrapper can skip it: the budget must NOT be burned
    on skips that never happen — the error surfaces instead."""
    inner = _RestartingLoader(range(4), {2: 99})
    loader = ResilientLoader(inner, max_retries=1, backoff_base=0,
                             skip_batch_budget=3, sleep=lambda s: None)
    with pytest.raises(IOError):
        list(loader)
    assert loader.skipped_total == 0  # no phantom skips logged


def test_resilient_loader_proxies_loader_surface():
    class L:
        num_samples = 12
        global_batch_size = 4

        def __init__(self):
            self.epoch = None

        def __len__(self):
            return 3

        def set_epoch(self, e):
            self.epoch = e

        def peek(self):
            return "peeked"

        def __iter__(self):
            return iter([])

    loader = ResilientLoader(L(), max_retries=1)
    assert len(loader) == 3
    assert loader.num_samples == 12
    assert loader.global_batch_size == 4
    assert loader.peek() == "peeked"
    loader.set_epoch(7)
    assert loader.loader.epoch == 7


# -- trainer-integrated tests (tiny model, CPU mesh) ---------------------

def _parse(argv):
    from fengshen_tpu.data.universal_datamodule import UniversalDataModule
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import add_trainer_args
    from fengshen_tpu.utils import UniversalCheckpoint
    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    UniversalCheckpoint.add_argparse_args(parser)
    return parser.parse_args(argv)


def _tiny_cfg():
    from fengshen_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=64, hidden_size=16,
                       intermediate_size=32, num_hidden_layers=1,
                       num_attention_heads=2,
                       max_position_embeddings=32, dtype="float32")


def _dataset(n=64, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    rows = [{"input_ids": rng.randint(0, 63, seq).tolist()}
            for _ in range(n)]

    class DS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    return DS()


def _fit(tmp_path, argv, plan=None, with_ckpt=True, fault_datamodule=False):
    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint

    args = _parse(["--train_batchsize", "4", "--learning_rate", "1e-3",
                   "--warmup_steps", "1", "--log_every_n_steps", "1",
                   "--default_root_dir", str(tmp_path)] + argv)
    cfg = _tiny_cfg()
    module = CausalLMModule(args, LlamaForCausalLM(cfg), cfg)
    dm = UniversalDataModule(args=args, datasets={"train": _dataset()})
    trainer = Trainer(args)
    if with_ckpt:
        trainer.callbacks.append(UniversalCheckpoint(args))
    if plan is not None:
        plan.install(trainer)
        if fault_datamodule:
            plan.wrap_datamodule(dm)
    state = trainer.fit(module, dm)
    return trainer, state, module


def _events(tmp_path):
    with open(os.path.join(tmp_path, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f]


def test_nan_step_guard_skips_update(tmp_path):
    """Injected NaN loss at (0-based) step 2: the update is skipped —
    final params are bit-for-bit the params checkpointed at the end of
    step 2 (global) — and bad_step_count lands in state + metrics.
    Composes with --accumulate_grad_batches."""
    ck = tmp_path / "ck"
    plan = FaultPlan(nan_loss_at_steps={2})
    trainer, state, _ = _fit(
        tmp_path,
        ["--max_steps", "3", "--accumulate_grad_batches", "2",
         "--every_n_train_steps", "2",
         "--save_ckpt_path", str(ck), "--load_ckpt_path",
         str(tmp_path / "none")],
        plan=plan)
    assert trainer.global_step == 3 and int(state.step) == 3
    assert int(state.bad_step_count) == 1

    import orbax.checkpoint as ocp
    mgr = ocp.CheckpointManager(str(ck))
    restored = mgr.restore(
        2, args=ocp.args.Composite(state=ocp.args.StandardRestore()))
    good = jax.tree_util.tree_leaves(restored["state"]["params"])
    final = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, state.params))
    assert len(good) == len(final)
    for a, b in zip(good, final):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    rows = [e for e in _events(tmp_path) if "bad_step_count" in e]
    assert rows and rows[-1]["bad_step_count"] == 1
    assert not np.isfinite(rows[-1]["loss"])  # the NaN was real


def test_nan_step_guard_under_steps_per_execution(tmp_path):
    """The guard lives inside the lax.scan body, so a bad substep in a
    K-step execution skips ONLY its own update and the cumulative
    bad_step_count survives the scan."""
    plan = FaultPlan(nan_loss_at_steps={2})
    trainer, state, _ = _fit(
        tmp_path, ["--max_steps", "4", "--steps_per_execution", "2"],
        plan=plan, with_ckpt=False)
    assert trainer.global_step == 4 and int(state.step) == 4
    assert int(state.bad_step_count) == 1
    leaves = jax.tree_util.tree_leaves(state.params)
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in leaves)


def test_rewind_after_consecutive_bad_steps(tmp_path):
    """K consecutive guarded-away steps trigger a logged rewind: restore
    the last checkpoint, advance consumed_samples past the offending
    window, finish the run clean."""
    ck = tmp_path / "ck"
    plan = FaultPlan(nan_loss_at_steps={1, 2})
    trainer, state, _ = _fit(
        tmp_path,
        ["--max_steps", "4", "--every_n_train_steps", "2",
         "--max_consecutive_bad_steps", "2",
         "--save_ckpt_path", str(ck), "--load_ckpt_path", str(ck)],
        plan=plan)
    assert trainer.global_step == 4 and int(state.step) == 4
    assert int(state.bad_step_count) == 2
    rewinds = [e for e in _events(tmp_path) if e.get("event") == "rewind"]
    assert len(rewinds) == 1
    assert rewinds[0]["from_step"] == 3 and rewinds[0]["to_step"] == 2
    assert ("nan_disarmed", [1, 2]) in plan.fired
    # clean run consumes 4 batches x 4 rows; the rewound run paid 1
    # extra (skipped) batch for the bad window
    assert trainer.consumed_samples == 20
    leaves = jax.tree_util.tree_leaves(state.params)
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in leaves)


def test_loader_fault_retry_completes_fit(tmp_path):
    """A train loader raising twice (transiently) completes fit under
    --loader_max_retries, batch-for-batch identical to a clean run."""
    clean_args = ["--max_steps", "3", "--loader_max_retries", "3",
                  "--loader_backoff_base", "0.01"]
    _, clean_state, _ = _fit(tmp_path / "clean", clean_args,
                             with_ckpt=False)

    plan = FaultPlan(loader_raise_at={1: 2})
    trainer, state, _ = _fit(tmp_path / "faulty", clean_args, plan=plan,
                             with_ckpt=False, fault_datamodule=True)
    assert trainer.global_step == 3 and int(state.step) == 3
    assert plan.loader_raise_at == {1: 0}  # both injections consumed
    retries = [e for e in _events(tmp_path / "faulty")
               if e.get("event") == "loader_retry"]
    assert len(retries) == 2
    assert all("InjectedLoaderFault" in e["error"] for e in retries)
    for a, b in zip(jax.tree_util.tree_leaves(clean_state.params),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_skip_budget_fit_keeps_consumed_samples_aligned(tmp_path):
    """--loader_skip_batches alone (no retries) wraps the loader, drops
    the poison batch, and folds the skipped stream position into
    trainer.consumed_samples so resumes stay aligned with the sampler."""
    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule

    args = _parse(["--train_batchsize", "4", "--learning_rate", "1e-3",
                   "--warmup_steps", "1", "--log_every_n_steps", "1",
                   "--default_root_dir", str(tmp_path),
                   "--max_steps", "3", "--max_epochs", "3",
                   "--loader_max_retries", "0",
                   "--loader_skip_batches", "1"])
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 63, 16).tolist()}
            for _ in range(64)]
    poison = {"row": None, "remaining": 1}

    class PoisonDS:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            if i == poison["row"] and poison["remaining"] > 0:
                poison["remaining"] -= 1
                raise IOError("transient poison row")
            return rows[i]

    cfg = _tiny_cfg()
    trainer = Trainer(args)
    module = CausalLMModule(args, LlamaForCausalLM(cfg), cfg)
    dm = UniversalDataModule(args=args, datasets={"train": PoisonDS()})
    dm.trainer = trainer
    # poison a row of the SECOND batch the run's own sampler will draw
    probe = dm.train_dataloader()
    batches = [b for _, b in zip(range(2), iter(probe.sampler))]
    poison["row"] = batches[1][0]
    world_batch = probe.global_batch_size

    state = trainer.fit(module, dm)
    assert int(state.step) == 3
    assert poison["remaining"] == 0  # the poison actually fired
    skips = [e for e in _events(tmp_path)
             if e.get("event") == "loader_skip_batch"]
    assert len(skips) == 1
    # 3 trained + 1 skipped global batches all count as consumed
    assert trainer.consumed_samples == 4 * world_batch


def test_loader_fault_exhausted_raises(tmp_path):
    """More failures than the retry bound (and no skip budget) must
    surface — a dead loader is an error, not a zero-step epoch."""
    plan = FaultPlan(loader_raise_at={1: 99})
    with pytest.raises(InjectedLoaderFault):
        _fit(tmp_path, ["--max_steps", "3", "--loader_max_retries", "2",
                        "--loader_backoff_base", "0"],
             plan=plan, with_ckpt=False, fault_datamodule=True)


def test_truncated_checkpoint_falls_back_to_previous(tmp_path):
    """A truncated newest checkpoint is rejected (logged) and restore
    falls back to the previous step instead of crashing."""
    from fengshen_tpu.models.llama import LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint

    ck = tmp_path / "ck"
    argv = ["--max_steps", "4", "--every_n_train_steps", "2",
            "--save_ckpt_path", str(ck), "--load_ckpt_path", str(ck)]
    _fit(tmp_path, argv)

    removed = truncate_checkpoint_step(str(ck), 4)
    assert removed

    args = _parse(["--train_batchsize", "4", "--default_root_dir",
                   str(tmp_path / "resume"), "--save_ckpt_path", str(ck),
                   "--load_ckpt_path", str(ck)])
    cfg = _tiny_cfg()
    trainer2 = Trainer(args)
    trainer2.callbacks.append(UniversalCheckpoint(args))
    module2 = CausalLMModule(args, LlamaForCausalLM(cfg), cfg)
    trainer2.restore_for_predict(module2)
    assert trainer2.global_step == 2  # fell back past the corrupt 4
    rejected = [e for e in _events(tmp_path / "resume")
                if e.get("event") == "checkpoint_restore_rejected"]
    assert len(rejected) == 1 and rejected[0]["ckpt_step"] == 4
    # the owned corrupt step was deleted, so a future boundary save at
    # step 4 is possible again instead of shadowed forever
    import orbax.checkpoint as ocp
    assert 4 not in ocp.CheckpointManager(str(ck)).all_steps()


def test_structural_mismatch_surfaces_immediately(tmp_path):
    """Restoring into a differently-shaped model is a config error: it
    must raise CheckpointStructureMismatch at once, not burn a full
    restore attempt per step before failing with 'corrupt'."""
    import optax

    from fengshen_tpu.trainer.train_state import TrainState
    from fengshen_tpu.utils import UniversalCheckpoint
    from fengshen_tpu.utils.universal_checkpoint import (
        CheckpointStructureMismatch)

    ck = tmp_path / "ck"
    _fit(tmp_path, ["--max_steps", "4", "--every_n_train_steps", "2",
                    "--save_ckpt_path", str(ck),
                    "--load_ckpt_path", str(ck)])

    args = _parse(["--train_batchsize", "4", "--default_root_dir",
                   str(tmp_path), "--save_ckpt_path", str(ck),
                   "--load_ckpt_path", str(ck)])
    wrong = TrainState.create(
        apply_fn=lambda: None,
        params={"w": np.zeros((2, 2), np.float32)},
        tx=optax.adamw(1e-3))

    class _T:
        global_step = 0
        consumed_samples = 0

    with pytest.raises(CheckpointStructureMismatch):
        UniversalCheckpoint(args).maybe_restore(wrong, _T())


def test_kill_and_resume_matches_uninterrupted(tmp_path):
    """Crash-at-step-k via a REAL SIGTERM + resume must finish with
    final params bit-for-bit identical to an uninterrupted run: the
    autosaved checkpoint, the resumable sampler, and the step-folded
    rng together make recovery exact."""
    prev = signal.getsignal(signal.SIGTERM)
    try:
        _, state_a, _ = _fit(tmp_path / "a", ["--max_steps", "6"],
                             with_ckpt=False)

        ck = tmp_path / "b" / "ck"
        argv = ["--max_steps", "6", "--save_ckpt_path", str(ck),
                "--load_ckpt_path", str(ck)]
        plan = FaultPlan(sigterm_at_step=3)
        trainer1, state1, _ = _fit(tmp_path / "b", argv, plan=plan)
        assert trainer1.global_step == 3 and int(state1.step) == 3
        assert plan.fired == [("sigterm", 3)]
        assert any(e.get("event") == "preempted_saved"
                   for e in _events(tmp_path / "b"))

        trainer2, state2, _ = _fit(tmp_path / "b", argv)
        assert trainer2.global_step == 6 and int(state2.step) == 6
    finally:
        signal.signal(signal.SIGTERM, prev)

    leaves_a = jax.tree_util.tree_leaves(state_a.params)
    leaves_b = jax.tree_util.tree_leaves(state2.params)
    assert len(leaves_a) == len(leaves_b)
    for a, b in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sigterm_chains_previous_handler():
    """Trainer's preemption handler must chain the handler it replaced
    (SLURM re-queue shims and pod managers keep working)."""
    from fengshen_tpu.trainer import Trainer

    calls = []
    orig = signal.getsignal(signal.SIGTERM)
    signal.signal(signal.SIGTERM, lambda s, f: calls.append(s))
    try:
        args = _parse(["--default_root_dir", "/tmp/fstpu_sigterm_test"])
        trainer = Trainer(args)
        os.kill(os.getpid(), signal.SIGTERM)
        assert trainer._preempted
        assert calls == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, orig)


def test_save_verifies_commit(tmp_path):
    """A sync save whose step never committed must raise, not let the
    manager prune good older steps around a phantom restore point."""
    from fengshen_tpu.utils import UniversalCheckpoint

    args = _parse(["--save_ckpt_path", str(tmp_path / "ck"),
                   "--default_root_dir", str(tmp_path)])
    cb = UniversalCheckpoint(args)

    class _Mgr:
        def save(self, step, args=None):
            pass  # lost write

        def wait_until_finished(self):
            pass

        def reload(self):
            pass

        def all_steps(self):
            return []

    cb._manager = _Mgr()

    class _T:
        global_step = 5
        consumed_samples = 20

    class _S:
        params = {"w": np.zeros(2)}
        opt_state = ()

    with pytest.raises(RuntimeError, match="did not commit"):
        cb.save(_S(), _T(), sync=True)
