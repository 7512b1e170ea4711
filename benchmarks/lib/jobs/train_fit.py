"""Job kind `train_fit`: `Trainer(args).fit(CausalLMModule,
UniversalDataModule)` as every example builds them, driven from the
seed. One fit is the whole run: its first steps are followed by the
plain reference (set-up), the window opens on a later step of the SAME
compiled step and state, and closes `--seconds` later on a step whose
loss has been fetched. The fit is then ended the way a preemption
notice ends it (SIGTERM: stop at the next step boundary; there is no
checkpoint to save: the callback's `save` keeps nothing).
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import signal
import time

import numpy as np

from benchmarks.lib import check, placement, traffic, weights
from benchmarks.lib.runlog import say
from benchmarks.lib.tracing import Traced


#: more steps than any window holds; the window ends the fit
MAX_STEPS = 100000


class SeededRows:
    """Row i of the token stream, made when asked for."""

    def __init__(self, seed, rows, seq, vocab):
        self.seed, self.rows, self.seq, self.vocab = seed, rows, seq, vocab

    def __len__(self):
        return self.rows

    def __getitem__(self, i):
        return {"input_ids": traffic.token_rows(self.seed, int(i), 1,
                                                self.seq, self.vocab)[0]}


def follow_reference(ctx, family, rows: int, seq: int, matmul: str) -> dict:
    """The plain reference over the first steps, before the program's
    state exists; everything it held is freed on return."""
    import jax
    config, seed, chips = ctx["config"], ctx["seed"], ctx["chips"]
    reference = importlib.import_module(family.REFERENCE)
    ref_cfg = family.reference_config(config)
    steps = config["reference"]["steps"]
    place = placement.placer(chips)
    shapes = reference.param_shapes(ref_cfg)
    key = weights.base_key(seed)
    out_sh = None if place is None else {
        p: place(s, "sharding") for p, (s, _) in shapes.items()}
    params = jax.jit(lambda k: weights.fill(k, shapes),
                     out_shardings=out_sh)(key)
    batches = [traffic.token_rows(seed, k * rows, rows, seq,
                                  config["vocab_size"])
               for k in range(steps)]
    opt = config["optimizer"]
    out = reference.follow_steps(
        ref_cfg, matmul, params, batches,
        {"b1": opt["adam_beta1"], "b2": opt["adam_beta2"],
         "eps": opt["adam_epsilon"], "lr": opt["learning_rate"]},
        config["reference"]["rows_per_block"] * chips, place)
    out["change_norm"] = change_norms(out.pop("params"), key)
    del params
    gc.collect()
    return out


def change_norms(params: dict, key) -> dict:
    """Per leaf, the norm of (parameters now - parameters as the seed
    made them), one leaf at a time so no second tree is alive."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, leaf in params.items():
        def norm(p, k, path=path):
            start = weights.make_leaf(k, path, p.shape, p.dtype)
            start = jax.lax.with_sharding_constraint(start, leaf.sharding)
            return jnp.sqrt(jnp.sum(jnp.square(
                p.astype(jnp.float32) - start.astype(jnp.float32))))
        out[path] = float(jax.jit(norm)(leaf, key))
    return out


class WindowCallback:
    """Rides `Trainer.callbacks`: reads the followed steps' state,
    opens and closes the window on fetched steps, ends the fit."""

    def __init__(self, ctx, follow_steps: int, warm_steps: int, b1: float):
        self.ctx, self.follow, self.warm, self.b1 = (ctx, follow_steps,
                                                     warm_steps, b1)
        self.key = weights.base_key(ctx["seed"])
        self.step_end = {}
        self.grad_norm = self.change_norm = None
        self.t_open = self.t_close = None
        self.open_step = self.close_step = None
        self.traced = None
        self.trace_until = None
        self.compile_mark = None
        self.compiles_in_window = 0

    def maybe_restore(self, state, trainer, **_kw):
        """The Trainer's restore hook, used as every checkpoint callback
        uses it: the parameters `fit` goes on with are the benchmark's,
        filled from `--seed` leaf by leaf into the shardings the Trainer
        chose. The key is an ARGUMENT of each small program, so a new
        seed compiles nothing (baked into the Trainer's own init
        program, as `--seed` is, it cost 17 s a new seed)."""
        import jax

        def fresh(path, old):
            made = jax.jit(
                lambda k: weights.make_leaf(k, weights.path_str(path),
                                            old.shape, old.dtype),
                out_shardings=old.sharding)(self.key)
            old.delete()
            return made
        return state.replace(params=jax.tree_util.tree_map_with_path(
            fresh, state.params))

    def save(self, *_a, **_kw):
        """The preemption path saves through the restore callback; a
        benchmark run keeps nothing."""

    def on_train_step_end(self, trainer, state):
        import jax
        import jax.numpy as jnp
        step = int(trainer.global_step)
        if step == 1:
            adam = next(s for s in jax.tree_util.tree_leaves(
                state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                if hasattr(s, "mu"))
            norms = jax.jit(lambda mu: {
                k: jnp.sqrt(jnp.sum(jnp.square(v))) / (1 - self.b1)
                for k, v in weights.flat(mu).items()})(adam.mu)
            self.grad_norm = {k: float(v) for k, v in norms.items()}
        if step == self.follow:
            self.change_norm = change_norms(weights.flat(state.params),
                                            self.key)
        now = time.perf_counter()
        self.step_end[step] = now
        if step == max(self.warm, self.follow) and self.t_open is None:
            self.t_open, self.open_step = now, step
            self.compile_mark = self.ctx["meter"].mark()
            say(f"window opens at the end of step {step}")
        elif self.t_open is not None and self.t_close is None:
            if self.ctx["trace"]:
                self._trace(step)
            if now - self.t_open >= self.ctx["seconds"]:
                self.t_close, self.close_step = now, step
                self.compiles_in_window = \
                    self.ctx["meter"].mark()[1] - self.compile_mark[1]
                os.kill(os.getpid(), signal.SIGTERM)

    def _trace(self, step):
        # a few steps from the start of the window, on step boundaries
        if self.traced is None and step == self.open_step + 1:
            self.traced = Traced(self.ctx["trace_dir"])
            self.traced.__enter__()
            self.trace_until = step + self.ctx["trace_steps"]
        elif self.traced is not None and step == self.trace_until:
            self.traced.__exit__(None, None, None)
            self.trace_until = None


def _args(ctx, root: str, rows: int):
    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import add_trainer_args
    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    opt, mesh = ctx["config"]["optimizer"], ctx["config"]["mesh"]
    argv = ["--offload", "none",
            "--fsdp_parallel_size", str(mesh["fsdp"]),
            "--max_steps", str(MAX_STEPS), "--max_epochs", "1",
            "--train_batchsize", str(rows), "--sampler_type", "single",
            # the Trainer's own seed feeds its init program as a constant
            # and dropout (off here); the run's seed reaches the weights
            # through `maybe_restore` and the rows through the dataset
            "--log_every_n_steps", "1", "--seed", "0",
            "--scheduler_type", "constant", "--warmup_ratio", "0",
            "--default_root_dir", root]
    for k, v in opt.items():
        argv += [f"--{k}", str(v)]
    return parser.parse_args(argv)


def run(ctx: dict) -> dict:
    import jax

    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.parallel import set_mesh
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule

    config, mix, seed, chips = (ctx["config"], ctx["mix"], ctx["seed"],
                                ctx["chips"])
    family, phases, obs = ctx["family"], ctx["phases"], ctx["obs"]
    if config["mesh"]["fsdp"] != chips:
        raise ValueError("the configuration's mesh and the cell's chips "
                         "differ")
    rows, seq = mix["rows_per_chip"] * chips, mix["seq"]
    follow = config["reference"]["steps"]

    t = time.perf_counter()
    reference = follow_reference(ctx, family, rows, seq, "highest")
    phases["reference_s"] = time.perf_counter() - t
    say(f"reference followed {follow} steps in {phases['reference_s']:.1f}s:"
        f" losses {' '.join(f'{x:.6f}' for x in reference['losses'])}")
    obs["reference_peak_bytes"] = ctx["memory_peak"]()

    model, model_cfg = family.build(config)
    root = os.path.join(ctx["run_dir"], "fit")
    shutil.rmtree(root, ignore_errors=True)
    args = _args(ctx, root, rows)
    trainer = Trainer(args)
    module = CausalLMModule(args, model, model_cfg)
    data = UniversalDataModule(args=args, datasets={
        "train": SeededRows(seed, rows * MAX_STEPS, seq,
                            config["vocab_size"])})
    window = WindowCallback(ctx, follow, mix["warm_steps"],
                            config["optimizer"]["adam_beta1"])
    trainer.callbacks.append(window)
    say(f"fit: mesh {dict(trainer.mesh.shape)}, {rows} rows x {seq} "
        f"tokens a step")
    t = time.perf_counter()
    state = trainer.fit(module, data)
    jax.block_until_ready(state.params)
    if window.t_close is None:
        raise RuntimeError("the fit ended before the window closed")
    phases["fit_to_open_s"] = window.t_open - t
    obs["memory_peak_bytes"] = ctx["memory_peak"]()
    policy = trainer._offload_policy.level

    with open(os.path.join(root, "metrics.jsonl")) as f:
        entries = [json.loads(line) for line in f]
    by_step = {e["step"]: e for e in entries if "loss" in e and "step" in e}
    program = {"losses": [by_step[s]["loss"] for s in range(1, follow + 1)],
               "grad_norm": window.grad_norm,
               "change_norm": window.change_norm}
    in_window = [s for s in sorted(window.step_end)
                 if window.open_step < s <= window.close_step]
    obs.update(window=(window.t_open, window.t_close),
               steps_in_window=len(in_window),
               step_ends=[window.step_end[s] for s in
                          [window.open_step] + in_window],
               tokens_per_step=rows * seq, seq=seq,
               compiles_in_window=window.compiles_in_window)
    say(f"window: {len(in_window)} steps of {rows * seq} tokens in "
        f"{window.t_close - window.t_open:.3f}s on {chips} chip(s)")
    if window.traced is not None:
        if window.trace_until is not None:
            window.traced.__exit__(None, None, None)
        obs["trace"], obs["trace_window"] = window.traced.load()
        obs["pc_minus_trace"] = window.traced.pc_minus_trace

    numbers = check.training_numbers(program, reference, ctx["limits"])
    bad = [by_step[s].get("bad_step_count", 0) for s in in_window]
    finite = all(np.isfinite(by_step[s]["loss"]) for s in in_window)
    numbers.append(("steps of the window skipped by the guards or with a "
                    "loss that is not finite",
                    int(max(bad, default=0)) + (0 if finite else 1), 0,
                    finite and max(bad, default=0) == 0))
    numbers.append(("programs compiled inside the window",
                    window.compiles_in_window, 0,
                    window.compiles_in_window == 0))
    numbers.append((f"offload level resolved ({policy})",
                    int(policy != "none"), 0, policy == "none"))
    del state, trainer, module, data
    set_mesh(None)
    gc.collect()
    shutil.rmtree(root, ignore_errors=True)
    return {"numbers": numbers, "attempted": len(in_window), "failed": 0,
            "t_open": window.t_open}
