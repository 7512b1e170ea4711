"""End-to-end trainer + sampler + datamodule tests on the 8-device mesh."""

import argparse
import json
import os



import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.data import (PretrainingSampler, PretrainingRandomSampler,
                               UniversalDataModule, DataLoader)

pytestmark = pytest.mark.slow  # full-fit/e2e lane: run with -m slow or no -m filter


def _parse(argv, extra=None):
    from fengshen_tpu.trainer import add_trainer_args
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.data.universal_datamodule import UniversalDataModule
    from fengshen_tpu.utils import UniversalCheckpoint
    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    UniversalCheckpoint.add_argparse_args(parser)
    return parser.parse_args(argv)


# -- samplers (math parity with reference universal_sampler.py) ----------

def test_pretraining_sampler_resume():
    s = PretrainingSampler(total_samples=20, consumed_samples=8,
                           micro_batch_size=2, data_parallel_rank=0,
                           data_parallel_size=2)
    batches = list(s)
    # starts at 8: global batch [8,9,10,11] → rank0 gets [8,9]
    assert batches[0] == [8, 9]
    s1 = PretrainingSampler(total_samples=20, consumed_samples=8,
                            micro_batch_size=2, data_parallel_rank=1,
                            data_parallel_size=2)
    assert list(s1)[0] == [10, 11]


def test_pretraining_sampler_validates():
    with pytest.raises(ValueError):
        PretrainingSampler(0, 0, 1, 0, 1)
    with pytest.raises(ValueError):
        PretrainingSampler(10, 10, 1, 0, 1)
    with pytest.raises(ValueError):
        PretrainingSampler(10, 0, 1, 3, 2)


def test_random_sampler_resume_mid_epoch():
    """Resuming from consumed_samples must continue the same permutation —
    the property the reference relies on for mid-epoch restart
    (reference: universal_sampler.py:99-122)."""
    full = PretrainingRandomSampler(total_samples=32, consumed_samples=0,
                                    micro_batch_size=2, data_parallel_rank=0,
                                    data_parallel_size=2, epoch_seed=7)
    all_batches = []
    for i, b in enumerate(full):
        all_batches.append(b)
        if i == 7:
            break

    resumed = PretrainingRandomSampler(total_samples=32, consumed_samples=16,
                                       micro_batch_size=2,
                                       data_parallel_rank=0,
                                       data_parallel_size=2, epoch_seed=7)
    resumed_batches = [b for _, b in zip(range(4), resumed)]
    assert resumed_batches == all_batches[4:8]


def test_random_sampler_disjoint_ranks():
    r0 = PretrainingRandomSampler(32, 0, 2, 0, 2, epoch_seed=1)
    r1 = PretrainingRandomSampler(32, 0, 2, 1, 2, epoch_seed=1)
    i0 = {i for b in r0 for i in b}
    i1 = {i for b in r1 for i in b}
    assert i0.isdisjoint(i1)
    assert len(i0 | i1) == 32


def test_random_sampler_epoch_reshuffle():
    e0 = list(PretrainingRandomSampler(16, 0, 2, 0, 1, epoch_seed=3))
    e1 = list(PretrainingRandomSampler(16, 16, 2, 0, 1, epoch_seed=3))
    assert e0 != e1  # new epoch, new permutation
    assert sorted(i for b in e0 for i in b) == \
        sorted(i for b in e1 for i in b)


# -- datamodule ----------------------------------------------------------

def test_datamodule_from_json(tmp_path):
    train = tmp_path / "train.json"
    with open(train, "w") as f:
        for i in range(32):
            f.write(json.dumps({"input_ids": list(range(i, i + 8))}) + "\n")
    args = _parse(["--train_file", str(train), "--train_batchsize", "4",
                   "--sampler_type", "single"])
    dm = UniversalDataModule(args=args)
    loader = dm.train_dataloader()
    batch = next(iter(loader))
    assert batch["input_ids"].shape == (4, 8)
    assert loader.global_batch_size == 4


# -- end-to-end fit ------------------------------------------------------

def test_fit_tiny_llama_8dev(mesh8, tmp_path):
    """Full fit(): sharded init, jit train step with accumulation, metrics
    log — the minimum end-to-end slice of SURVEY.md §7 step 3."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule

    cfg = LlamaConfig.small_test_config(dtype="float32")
    model = LlamaForCausalLM(cfg)

    rng = np.random.RandomState(0)
    data = [{"input_ids": rng.randint(0, 255, 16).tolist()}
            for _ in range(64)]

    class ListDS:
        def __len__(self):
            return len(data)

        def __getitem__(self, i):
            return data[i]

    args = _parse(["--max_steps", "4", "--train_batchsize", "8",
                   "--accumulate_grad_batches", "2",
                   "--learning_rate", "1e-3", "--warmup_steps", "1",
                   "--log_every_n_steps", "1",
                   "--default_root_dir", str(tmp_path)])
    module = CausalLMModule(args, model, cfg)
    dm = UniversalDataModule(args=args, datasets={"train": ListDS()})
    trainer = Trainer(args)
    state = trainer.fit(module, dm)
    assert int(state.step) == 4
    lines = [json.loads(l) for l in
             open(os.path.join(tmp_path, "metrics.jsonl"))]
    losses = [l["loss"] for l in lines if "loss" in l]
    assert len(losses) == 4
    assert all(np.isfinite(losses))
    # params actually sharded per the rules
    flat = jax.tree_util.tree_leaves_with_path(state.params)
    from jax.sharding import PartitionSpec as P
    specs = {jax.tree_util.keystr(k): v.sharding.spec for k, v in flat}
    assert any(s != P() and s != P(None, None) for s in specs.values())


def test_dataloader_peek_does_not_advance():
    data = [{"input_ids": [i] * 4} for i in range(16)]

    class DS:
        def __len__(self):
            return 16

        def __getitem__(self, i):
            return data[i]

    s = PretrainingRandomSampler(16, 0, 2, 0, 1, epoch_seed=5)
    loader = DataLoader(DS(), s, global_batch_size=2)
    peeked = loader.peek()
    assert peeked["input_ids"].shape == (2, 4)
    assert s.consumed_samples == 0
    first = next(iter(loader))
    # a fresh sampler must yield the same first batch
    s2 = PretrainingRandomSampler(16, 0, 2, 0, 1, epoch_seed=5)
    first2 = next(iter(DataLoader(DS(), s2, global_batch_size=2)))
    np.testing.assert_array_equal(first["input_ids"], first2["input_ids"])


def test_total_steps_epochs_not_squared():
    from fengshen_tpu.models.model_utils import get_total_steps
    args = argparse.Namespace(max_steps=-1, max_epochs=3)
    assert get_total_steps(args, dataset_len=100, world_batch=10) == 30


def test_scan_export_roundtrip():
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.models.llama.convert import (params_to_torch_state,
                                                   torch_to_params)
    cfg = LlamaConfig.small_test_config(dtype="float32", scan_layers=True)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    state = params_to_torch_state(params, cfg)
    back = torch_to_params(state, cfg)
    k0 = params["model"]["layers"]["layer"]["self_attn"]["q_proj"]["kernel"]
    k1 = back["model"]["layers"]["layer"]["self_attn"]["q_proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(k0), np.asarray(k1), atol=1e-6)


def test_preemption_autosave(mesh8, tmp_path):
    """SIGTERM-style preemption flag triggers a checkpoint and clean exit."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint

    cfg = LlamaConfig.small_test_config(dtype="float32")
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    data = [{"input_ids": rng.randint(0, 255, 16).tolist()}
            for _ in range(64)]

    class DS:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            return data[i]

    args = _parse(["--max_steps", "50", "--train_batchsize", "8",
                   "--log_every_n_steps", "1", "--warmup_steps", "1",
                   "--default_root_dir", str(tmp_path),
                   "--save_ckpt_path", str(tmp_path / "ck"),
                   "--load_ckpt_path", str(tmp_path / "none")])
    from fengshen_tpu.data import UniversalDataModule
    module = CausalLMModule(args, model, cfg)
    dm = UniversalDataModule(args=args, datasets={"train": DS()})
    trainer = Trainer(args)
    cb = UniversalCheckpoint(args)
    trainer.callbacks.append(cb)

    # preempt after step 2 via the step-end hook
    class Preemptor:
        def on_train_step_end(self, tr, state):
            if tr.global_step == 2:
                tr._preempted = True

    trainer.callbacks.append(Preemptor())
    state = trainer.fit(module, dm)
    assert int(state.step) == 2  # stopped early
    import orbax.checkpoint as ocp
    mgr = ocp.CheckpointManager(str(tmp_path / "ck"))
    assert mgr.latest_step() == 2  # autosaved at preemption


def test_offload_optimizer_state_lives_on_host(tmp_path, mesh8):
    """ZeRO-offload analog (VERDICT r1 item 7): with --offload_optimizer,
    adam moments live in host memory, device bytes shrink accordingly, and
    training still runs end-to-end."""
    import argparse
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import Trainer, add_trainer_args
    from fengshen_tpu.trainer.modules import CausalLMModule

    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    args = parser.parse_args([
        "--max_steps", "2", "--train_batchsize", "4",
        "--log_every_n_steps", "1", "--warmup_steps", "1",
        "--default_root_dir", str(tmp_path), "--offload_optimizer",
        "--fsdp_parallel_size", "2", "--tensor_model_parallel_size", "2",
        "--data_parallel_size", "2"])

    config = LlamaConfig(vocab_size=128, hidden_size=32,
                         intermediate_size=64, num_hidden_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=32, dtype="float32")
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 127, 16).tolist()}
            for _ in range(16)]

    class ListDS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    module = CausalLMModule(args, LlamaForCausalLM(config), config)
    dm = UniversalDataModule(args=args, datasets={"train": ListDS()})
    trainer = Trainer(args)
    state = trainer.fit(module, dm)
    assert int(state.step) == 2

    from fengshen_tpu.trainer.memory import probe_memory_capabilities
    caps = probe_memory_capabilities()
    host_kind = caps.host_kind  # probe-resolved (docs/offload.md):
    # pinned_host where the backend has it, unpinned_host on this build

    def mem_kinds(tree):
        return {leaf.sharding.memory_kind
                for leaf in jax.tree_util.tree_leaves(tree)
                if hasattr(leaf, "sharding")}

    assert mem_kinds(state.opt_state) == {host_kind}
    assert mem_kinds(state.params) == {caps.device_memory_kind}

    # the device footprint must equal params ALONE: every optimizer-state
    # byte lives on the host (vs params+opt on device without offload).
    # Byte accounting by kind is only meaningful when the host space is
    # DISTINCT from the device default (on the CPU backend they are the
    # same space, so placement there is a no-op by construction)
    def nbytes(tree, kind=None):
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree)
                   if hasattr(leaf, "sharding") and
                   (kind is None or leaf.sharding.memory_kind == kind))

    params_total = nbytes(state.params)
    opt_total = nbytes(state.opt_state)
    assert opt_total > 0
    assert nbytes(state.opt_state, host_kind) == opt_total
    if host_kind != caps.device_memory_kind:
        device_bytes = nbytes(state.params, caps.device_memory_kind) + \
            nbytes(state.opt_state, caps.device_memory_kind)
        assert nbytes(state.opt_state, caps.device_memory_kind) == 0
        assert device_bytes == params_total
        assert device_bytes < params_total + opt_total


def test_offload_levels_bit_identical_to_monolithic_step(tmp_path, mesh8):
    """Parity across the offload ladder (docs/offload.md): the
    offloaded two-program step at every resolvable level — and the
    deprecated --offload_optimizer spelling, and --offload=auto —
    produces BIT-identical params to the monolithic fused optax step.
    Placement moves bytes, never math."""
    import argparse

    import jax
    import numpy as np

    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import Trainer, add_trainer_args
    from fengshen_tpu.trainer.modules import CausalLMModule

    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 127, 16).tolist()}
            for _ in range(16)]

    class ListDS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    config = LlamaConfig(vocab_size=128, hidden_size=32,
                         intermediate_size=64, num_hidden_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=32, dtype="float32")

    def fit(tag, extra):
        parser = argparse.ArgumentParser()
        add_module_args(parser)
        add_trainer_args(parser)
        UniversalDataModule.add_data_specific_args(parser)
        args = parser.parse_args([
            "--max_steps", "3", "--train_batchsize", "4",
            "--log_every_n_steps", "1", "--warmup_steps", "1",
            "--default_root_dir", str(tmp_path / tag),
            "--fsdp_parallel_size", "2",
            "--tensor_model_parallel_size", "2",
            "--data_parallel_size", "2", *extra])
        module = CausalLMModule(args, LlamaForCausalLM(config), config)
        dm = UniversalDataModule(args=args, datasets={"train": ListDS()})
        trainer = Trainer(args)
        state = trainer.fit(module, dm)
        return state, trainer._offload_policy

    ref, ref_policy = fit("none", ["--offload", "none"])
    assert ref_policy.level == "none"
    ref_leaves = jax.tree_util.tree_leaves(ref.params)
    variants = {
        "auto": ["--offload", "auto"],
        "opt": ["--offload", "opt"],
        "opt_master": ["--offload", "opt_master"],
        "legacy": ["--offload_optimizer"],
    }
    expected_level = {"auto": "none", "opt": "opt",
                      "opt_master": "opt_master", "legacy": "opt"}
    for tag, extra in variants.items():
        state, policy = fit(tag, extra)
        assert policy.level == expected_level[tag], tag
        for a, b in zip(ref_leaves,
                        jax.tree_util.tree_leaves(state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"--offload {tag}")


def test_profiler_trace_hook(tmp_path, mesh8):
    """--profile_steps captures a jax.profiler trace during fit
    (VERDICT r1 item 10)."""
    import argparse
    import os
    import numpy as np

    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import Trainer, add_trainer_args
    from fengshen_tpu.trainer.modules import CausalLMModule

    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    args = parser.parse_args([
        "--max_steps", "3", "--train_batchsize", "2",
        "--log_every_n_steps", "1", "--warmup_steps", "1",
        "--default_root_dir", str(tmp_path), "--profile_steps", "1,2"])

    config = LlamaConfig(vocab_size=64, hidden_size=32,
                         intermediate_size=64, num_hidden_layers=1,
                         num_attention_heads=4,
                         max_position_embeddings=16, dtype="float32")
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 63, 8).tolist()}
            for _ in range(8)]

    class ListDS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    module = CausalLMModule(args, LlamaForCausalLM(config), config)
    dm = UniversalDataModule(args=args, datasets={"train": ListDS()})
    state = Trainer(args).fit(module, dm)
    assert int(state.step) == 3
    prof_dir = tmp_path / "profile"
    assert prof_dir.is_dir()
    traced = [f for _, _, fs in os.walk(prof_dir) for f in fs]
    assert traced, "no trace files written"


def test_two_process_distributed_initialize():
    """The multi-host bootstrap rendezvous works: two CPU processes join
    one jax.distributed cluster and see the combined device count
    (docs/multihost.md dry-run recipe; VERDICT r1 item 9)."""
    import subprocess
    import sys

    code = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
from fengshen_tpu.parallel import distributed_initialize

distributed_initialize("127.0.0.1:29876", num_processes=2,
                       process_id=int(sys.argv[1]))
print("DEVICES", jax.device_count(), "PROC", jax.process_count())
"""
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd="/root/repo")
        for i in range(2)]
    outs = [p.communicate(timeout=120)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "PROC 2" in out, out


def test_offload_optimizer_checkpoint_roundtrip(tmp_path, mesh8):
    """Offloaded (host-resident) optimizer state must survive an orbax
    save + restore and come back onto the host memory space."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint

    def build_args(extra=()):
        return _parse([
            "--train_batchsize", "4", "--log_every_n_steps", "1",
            "--warmup_steps", "1", "--default_root_dir", str(tmp_path),
            "--save_ckpt_path", str(tmp_path / "ckpt"),
            "--load_ckpt_path", str(tmp_path / "ckpt"),
            "--offload_optimizer", *extra])

    config = LlamaConfig(vocab_size=64, hidden_size=32,
                         intermediate_size=64, num_hidden_layers=1,
                         num_attention_heads=4,
                         max_position_embeddings=16, dtype="float32")
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 63, 8).tolist()}
            for _ in range(16)]

    class ListDS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    # run 2 steps and save
    args = build_args(["--max_steps", "2", "--every_n_train_steps", "2"])
    trainer = Trainer(args)
    trainer.callbacks.append(UniversalCheckpoint(args))
    module = CausalLMModule(args, LlamaForCausalLM(config), config)
    dm = UniversalDataModule(args=args, datasets={"train": ListDS()})
    state = trainer.fit(module, dm)
    assert int(state.step) == 2

    # fresh trainer restores and continues, moments back on the host
    args2 = build_args(["--max_steps", "4"])
    trainer2 = Trainer(args2)
    trainer2.callbacks.append(UniversalCheckpoint(args2))
    module2 = CausalLMModule(args2, LlamaForCausalLM(config), config)
    dm2 = UniversalDataModule(args=args2, datasets={"train": ListDS()})
    state2 = trainer2.fit(module2, dm2)
    assert trainer2.global_step == 4 and int(state2.step) == 4
    from fengshen_tpu.trainer.memory import probe_memory_capabilities
    kinds = {leaf.sharding.memory_kind
             for leaf in jax.tree_util.tree_leaves(state2.opt_state)
             if hasattr(leaf, "sharding")}
    # host kind is probe-resolved (docs/offload.md): pinned_host where
    # the backend has it, unpinned_host on this CPU build
    assert kinds == {probe_memory_capabilities().host_kind}


def test_async_checkpoint_save_and_resume(tmp_path, mesh8):
    """--async_save: periodic saves return without blocking, the final
    flush lands a complete restorable checkpoint."""
    import argparse
    import time

    import jax
    import numpy as np

    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import Trainer, add_trainer_args
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint

    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    UniversalCheckpoint.add_argparse_args(parser)
    ckpt_dir = tmp_path / "ckpt"
    args = parser.parse_args([
        "--max_steps", "4", "--train_batchsize", "4",
        "--every_n_train_steps", "2", "--async_save",
        "--log_every_n_steps", "1", "--warmup_steps", "1",
        "--save_ckpt_path", str(ckpt_dir),
        "--load_ckpt_path", str(ckpt_dir),
        "--default_root_dir", str(tmp_path)])
    config = LlamaConfig(vocab_size=64, hidden_size=16,
                         intermediate_size=32, num_hidden_layers=1,
                         num_attention_heads=2,
                         max_position_embeddings=32, dtype="float32")
    rows = [{"input_ids":
             np.random.RandomState(i).randint(0, 63, 16).tolist()}
            for i in range(32)]

    class DS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    trainer = Trainer(args)
    module = CausalLMModule(args, LlamaForCausalLM(config), config)
    cb = UniversalCheckpoint(args)
    trainer.callbacks.append(cb)
    state = trainer.fit(module, UniversalDataModule(
        args=args, datasets={"train": DS()}))
    cb.wait()
    # both periodic steps landed and are restorable
    import orbax.checkpoint as ocp
    mgr = ocp.CheckpointManager(str(ckpt_dir.resolve()))
    assert mgr.latest_step() == 4
    trainer2 = Trainer(args)
    trainer2.callbacks.append(UniversalCheckpoint(args))
    state2 = trainer2.restore_for_predict(module)
    leaves1 = jax.tree_util.tree_leaves(state.params)
    leaves2 = jax.tree_util.tree_leaves(state2.params)
    np.testing.assert_allclose(np.asarray(leaves1[0]),
                               np.asarray(leaves2[0]), rtol=1e-6)


def _fit_tiny(tmp_path, extra_args, seed_data=7):
    """Shared driver for the steps_per_execution parity test."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule

    cfg = LlamaConfig.small_test_config(dtype="float32")
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(seed_data)
    data = [{"input_ids": rng.randint(0, 255, 16).tolist()}
            for _ in range(64)]

    class ListDS:
        def __len__(self):
            return len(data)

        def __getitem__(self, i):
            return data[i]

    args = _parse(["--max_steps", "4", "--train_batchsize", "8",
                   "--learning_rate", "1e-3", "--warmup_steps", "1",
                   "--log_every_n_steps", "1",
                   "--default_root_dir", str(tmp_path)] + extra_args)
    module = CausalLMModule(args, model, cfg)
    dm = UniversalDataModule(args=args, datasets={"train": ListDS()})
    state = Trainer(args).fit(module, dm)
    lines = [json.loads(l) for l in
             open(os.path.join(tmp_path, "metrics.jsonl"))]
    losses = [l["loss"] for l in lines if "loss" in l]
    return state, losses


def test_steps_per_execution_parity(mesh8, tmp_path):
    """--steps_per_execution K runs K optimizer steps per jitted
    dispatch (lax.scan over stacked batches) and must match the K=1
    run step for step: the rng fold_in(step) keeps substep dropout
    identical, so final params agree to float tolerance and the
    windowed loss logs are the per-window means of the K=1 losses."""
    state1, losses1 = _fit_tiny(tmp_path / "a", [])
    state2, losses2 = _fit_tiny(
        tmp_path / "b", ["--steps_per_execution", "2"])

    assert int(state1.step) == int(state2.step) == 4
    # spe=2 logs once per execution (steps 2 and 4), each the mean of
    # its two substeps
    assert len(losses1) == 4 and len(losses2) == 2
    np.testing.assert_allclose(
        losses2, [np.mean(losses1[:2]), np.mean(losses1[2:])],
        rtol=2e-5)
    flat1 = jax.tree_util.tree_leaves(state1.params)
    flat2 = jax.tree_util.tree_leaves(state2.params)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-4)


def test_steps_per_execution_resume_clamps_to_remaining(mesh8, tmp_path):
    """Resuming with fewer steps left than one K-group must shrink K to
    the remainder (finishing the schedule exactly), and resuming at or
    past the budget must run ZERO steps — the loop body only checks
    max_steps after an execution, so without the pre-loop guard a
    restored run overshoots the LR schedule by a whole group."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint

    cfg = LlamaConfig(vocab_size=64, hidden_size=16,
                      intermediate_size=32, num_hidden_layers=1,
                      num_attention_heads=2,
                      max_position_embeddings=32, dtype="float32")
    rng = np.random.RandomState(11)
    data = [{"input_ids": rng.randint(0, 63, 16).tolist()}
            for _ in range(64)]

    class ListDS:
        def __len__(self):
            return len(data)

        def __getitem__(self, i):
            return data[i]

    ckpt_dir = tmp_path / "ckpt"

    def fit(argv):
        args = _parse([
            "--train_batchsize", "4", "--learning_rate", "1e-3",
            "--warmup_steps", "1", "--log_every_n_steps", "1",
            "--every_n_train_steps", "3",
            "--save_ckpt_path", str(ckpt_dir),
            "--load_ckpt_path", str(ckpt_dir),
            "--default_root_dir", str(tmp_path)] + argv)
        trainer = Trainer(args)
        trainer.callbacks.append(UniversalCheckpoint(args))
        module = CausalLMModule(args, LlamaForCausalLM(cfg), cfg)
        dm = UniversalDataModule(args=args, datasets={"train": ListDS()})
        state = trainer.fit(module, dm)
        return trainer, state

    # leg 1: plain 3-step run, checkpoint lands at step 3
    t1, s1 = fit(["--max_steps", "3"])
    assert t1.global_step == 3 and int(s1.step) == 3

    # leg 2: resume at step 3 with budget 4 and K=5: K shrinks to the
    # single remaining step — exactly one more optimizer step, never
    # 4 or 5 more
    t2, s2 = fit(["--max_steps", "4", "--steps_per_execution", "5"])
    assert t2.global_step == 4 and int(s2.step) == 4

    # leg 3: resume at step 3 with K=2 and budget 3 (K-rounding would
    # push the effective budget BELOW the restored step): zero steps
    t3, s3 = fit(["--max_steps", "3", "--steps_per_execution", "2"])
    assert t3.global_step == 3 and int(s3.step) == 3

    # leg 4: resume at step 3 with budget 5 and K=2 — the remaining 2
    # steps are exactly one K-group, so the run must reach the full
    # budget. Double-rounding (align from step 0 before restore, then
    # re-align after) would trim 5->4 and finish a step short
    t4, s4 = fit(["--max_steps", "5", "--steps_per_execution", "2"])
    assert t4.global_step == 5 and int(s4.step) == 5


def test_grouped_prefetch_drops_partial_tail(capsys):
    from fengshen_tpu.trainer.trainer import _prefetch_grouped

    batches = [{"x": np.full((2,), i)} for i in range(5)]
    dev = jax.devices("cpu")[0]
    sh = jax.tree_util.tree_map(
        lambda _: jax.sharding.SingleDeviceSharding(dev), {"x": 0})
    out = list(_prefetch_grouped(iter(batches), sh["x"], 2))
    assert len(out) == 2
    group, stacked, _skips = out[0]
    assert len(group) == 2 and stacked["x"].shape == (2, 2)
    assert "dropping 1 tail batch" in capsys.readouterr().out


def test_grouped_prefetch_ragged_drops_but_loader_bugs_raise(capsys):
    """A ragged group (short final batch) drops loudly; a tree-structure
    mismatch (loader bug) must RAISE — swallowing it would turn a crash
    into a zero-step 'successful' run."""
    from fengshen_tpu.trainer.trainer import _prefetch_grouped

    dev = jax.devices("cpu")[0]
    sh = jax.sharding.SingleDeviceSharding(dev)

    # ragged shapes, same structure: dropped with the loud message
    ragged = [{"x": np.zeros((2,))}, {"x": np.zeros((3,))}]
    assert list(_prefetch_grouped(iter(ragged), {"x": sh}, 2)) == []
    assert "mismatched batch shapes" in capsys.readouterr().out

    # structure mismatch (missing key): surfaces, never swallowed
    bad = [{"x": np.zeros((2,))}, {"y": np.zeros((2,))}]
    with pytest.raises(ValueError):
        list(_prefetch_grouped(iter(bad), {"x": sh}, 2))


def test_every_n_checkpoint_fires_on_crossed_boundary():
    """Under steps_per_execution the global step jumps K at a time;
    every-n checkpointing must fire when a multiple of n falls INSIDE
    the execution span, not only on exact hits."""
    from fengshen_tpu.utils import UniversalCheckpoint

    class _T:
        pass

    cb = UniversalCheckpoint.__new__(UniversalCheckpoint)
    cb.every_n_train_steps = 8
    saved = []
    cb.save = lambda state, trainer, **kw: saved.append(
        trainer.global_step)

    t = _T()
    for prev, cur in [(0, 5), (5, 10), (10, 15), (15, 20), (20, 25)]:
        t.prev_global_step, t.global_step = prev, cur
        cb.on_train_step_end(t, state=None)
    # multiples of 8 (8, 16, 24) fall inside spans (5,10], (15,20],
    # (20,25] -> saves at global steps 10, 20, 25
    assert saved == [10, 20, 25]

    # K=1 semantics unchanged: exact-multiple steps save, others don't
    saved.clear()
    for cur in range(1, 17):
        t.prev_global_step, t.global_step = cur - 1, cur
        cb.on_train_step_end(t, state=None)
    assert saved == [8, 16]


def test_save_due_is_the_callbacks_own_save_decision():
    """The Trainer names a callback's span `train/checkpoint` only where
    `save_due` says a save happens: it has to agree with what
    `on_train_step_end` then does, and read nothing but the counters."""
    from fengshen_tpu.utils import UniversalCheckpoint

    class _T:
        pass

    cb = UniversalCheckpoint.__new__(UniversalCheckpoint)
    saved = []
    cb.save = lambda state, trainer, **kw: saved.append(
        trainer.global_step)
    t = _T()
    for every in (0, 1, 4):
        cb.every_n_train_steps = every
        for prev, cur in [(0, 3), (3, 6), (6, 7), (7, 8), (8, 9)]:
            t.prev_global_step, t.global_step = prev, cur
            saved.clear()
            due = cb.save_due(t)
            cb.on_train_step_end(t, state=None)
            assert due == bool(saved), (every, prev, cur)


def test_sigterm_preemption_saves_and_resumes(mesh8, tmp_path):
    """A REAL SIGTERM mid-fit (delivered by the fault-injection
    harness) saves a sync checkpoint at the next step boundary and
    exits cleanly; a fresh fit resumes from the saved global_step /
    consumed_samples and finishes the budget."""
    import signal

    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.resilience import FaultPlan
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint

    cfg = LlamaConfig(vocab_size=64, hidden_size=16,
                      intermediate_size=32, num_hidden_layers=1,
                      num_attention_heads=2,
                      max_position_embeddings=32, dtype="float32")
    rng = np.random.RandomState(3)
    data = [{"input_ids": rng.randint(0, 63, 16).tolist()}
            for _ in range(64)]

    class DS:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            return data[i]

    ck = tmp_path / "ck"
    argv = ["--max_steps", "5", "--train_batchsize", "4",
            "--log_every_n_steps", "1", "--warmup_steps", "1",
            "--default_root_dir", str(tmp_path),
            "--save_ckpt_path", str(ck), "--load_ckpt_path", str(ck)]

    def run(plan=None):
        args = _parse(argv)
        trainer = Trainer(args)
        trainer.callbacks.append(UniversalCheckpoint(args))
        if plan is not None:
            plan.install(trainer)
        module = CausalLMModule(args, LlamaForCausalLM(cfg), cfg)
        dm = UniversalDataModule(args=args, datasets={"train": DS()})
        return trainer, trainer.fit(module, dm)

    prev = signal.getsignal(signal.SIGTERM)
    try:
        trainer1, state1 = run(FaultPlan(sigterm_at_step=2))
    finally:
        signal.signal(signal.SIGTERM, prev)
    assert trainer1._preempted
    assert trainer1.global_step == 2 and int(state1.step) == 2
    assert trainer1.consumed_samples == 8
    import orbax.checkpoint as ocp
    assert ocp.CheckpointManager(str(ck)).latest_step() == 2
    lines = [json.loads(l) for l in
             open(os.path.join(tmp_path, "metrics.jsonl"))]
    assert any(l.get("event") == "preempted_saved" and l["step"] == 2
               for l in lines)

    trainer2, state2 = run()
    assert trainer2.global_step == 5 and int(state2.step) == 5
    assert trainer2.consumed_samples == 20  # resumed at 8, not replayed


def test_grouped_prefetch_drops_ragged_group(capsys):
    """A loader's short final batch inside a full K-group must degrade
    (loud drop), not crash the run mid-epoch."""
    from fengshen_tpu.trainer.trainer import _prefetch_grouped

    batches = [{"x": np.zeros((2,))}, {"x": np.zeros((2,))},
               {"x": np.zeros((2,))}, {"x": np.zeros((1,))}]  # ragged
    dev = jax.devices("cpu")[0]
    sh = jax.sharding.SingleDeviceSharding(dev)
    out = list(_prefetch_grouped(iter(batches), sh, 2))
    assert len(out) == 1  # first group ok, ragged second group dropped
    assert "mismatched batch shapes" in capsys.readouterr().out
