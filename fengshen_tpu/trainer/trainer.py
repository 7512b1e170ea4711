"""The Trainer: sharded jit train loop with accumulation, logging, ckpt.

Replaces `pl.Trainer` + DeepSpeedStrategy
(reference: fengshen/strategies/megatron_deepspeed.py; Lightning flag surface
via `Trainer.add_argparse_args` used in every example,
e.g. fengshen/examples/ziya_llama/finetune_ziya_llama.py:191). The argparse
group below keeps the reference's flag names so example scripts port
unchanged (SURVEY.md §5.6 UX-preservation requirement).
"""

from __future__ import annotations

import argparse
import os
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from fengshen_tpu.compile_cache import ensure_compile_cache
from fengshen_tpu.observability import (FlightRecorder, JsonlSink,
                                        StepStats, record_build_info,
                                        span)
# re-exported for compatibility (the table moved to observability.flops,
# the single home of the MFU accounting)
from fengshen_tpu.observability.flops import PEAK_FLOPS  # noqa: F401
from fengshen_tpu.ops.pallas import log_dispatch
from fengshen_tpu.parallel.mesh import MeshConfig, make_mesh, set_mesh
from fengshen_tpu.parallel.partition import make_shardings
from fengshen_tpu.trainer.module import TrainModule
from fengshen_tpu.trainer.train_state import (TrainState,
                                              create_sharded_state,
                                              state_shardings)

#: process-wide SIGTERM plumbing (see _install_preemption_handler):
#: one handler, re-pointed at the latest Trainer via weakref
_SIGTERM_STATE: dict = {"handler": None, "prev": None, "ref": None}


def _prefetch(loader, shardings, depth: int = 2):
    """Double-buffered host→device transfer: the next batch's device_put is
    issued while the current step computes (the device-prefetch contract of
    SURVEY.md §7 step 1; jax transfers are async, so holding `depth`
    in-flight batches overlaps H2D with compute).

    Yields (host_batches, device_batch, skips_at_fetch): the third
    element snapshots the loader's cumulative skipped-batch counter
    (ResilientLoader) at the moment THIS batch was fetched, so the
    consumer can credit skipped stream positions exactly when its
    training frontier passes them — not `depth` batches early."""
    import collections
    queue = collections.deque()
    for batch in loader:
        skips = getattr(loader, "skipped_total", 0)
        queue.append(([batch], jax.device_put(batch, shardings), skips))
        if len(queue) >= depth:
            yield queue.popleft()
    while queue:
        yield queue.popleft()


def _prefetch_grouped(loader, shardings, k: int, depth: int = 2):
    """K-step grouping for --steps_per_execution: stack K host batches on
    a new leading axis and issue ONE device_put; the scan-based K-step
    program then runs K optimizer steps per dispatch. Yields
    (list_of_k_host_batches, stacked_device_batch, skips_at_fetch) —
    see _prefetch for the skip-snapshot contract."""
    import collections
    queue = collections.deque()
    group = []
    for batch in loader:
        group.append(batch)
        if len(group) < k:
            continue
        try:
            stacked = jax.tree_util.tree_map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *group)
        except ValueError:
            # only a genuinely RAGGED group (same tree structure,
            # mismatched leaf shapes — a loader's short final batch)
            # degrades by dropping the group loudly, like the K=1 path
            # degrades shardings. Any other ValueError (tree-structure
            # mismatch, inhomogeneous field) is a loader bug: dropping
            # every group would turn a crash into a "successful"
            # zero-step run, so it must surface.
            try:
                structs = {jax.tree_util.tree_structure(b)
                           for b in group}
                ragged = len(structs) == 1 and len(
                    {tuple(np.asarray(x).shape for x in
                           jax.tree_util.tree_leaves(b))
                     for b in group}) > 1
            except Exception:  # noqa: BLE001 — re-raise the original
                ragged = False
            if not ragged:
                raise
            print(f"[fengshen-tpu] steps_per_execution={k}: dropping a "
                  "group with mismatched batch shapes (short final "
                  "batch?)", flush=True)
            group = []
            continue
        queue.append((group, jax.device_put(stacked, shardings),
                      getattr(loader, "skipped_total", 0)))
        group = []
        if len(queue) >= depth:
            yield queue.popleft()
    while queue:
        yield queue.popleft()
    if group:
        # a partial tail cannot feed the K-step program (a different
        # leading axis means a recompile) — drop it LOUDLY
        print(f"[fengshen-tpu] steps_per_execution={k}: dropping "
              f"{len(group)} tail batch(es) short of a full group",
              flush=True)


def _spanned_iter(it, name: str):
    """Time each `next()` under a trace span — the fetch side of the
    prefetch pipeline shows up as `name` in /metrics span timings and
    on profiler traces, without restructuring the for loop."""
    it = iter(it)
    while True:
        with span(name):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


class _RefuseAotCacheDir(argparse.Action):
    """Refuses `--aot_cache_dir`, whose cache is gone."""

    def __call__(self, parser, namespace, values, option_string=None):
        parser.error(
            f"{option_string} was removed with the AOT executable cache; "
            "the one compile cache is jax's own persistent cache "
            "(fengshen_tpu/compile_cache.py): set "
            "JAX_COMPILATION_CACHE_DIR to place it")


def add_trainer_args(parent_parser: argparse.ArgumentParser):
    """Lightning-Trainer-compatible flag subset actually used by the
    reference examples (SURVEY.md §2.9 pattern)."""
    parser = parent_parser.add_argument_group("Trainer")
    parser.add_argument("--max_steps", default=-1, type=int)
    parser.add_argument("--max_epochs", default=1, type=int)
    parser.add_argument("--val_check_interval", default=0, type=float,
                        help="steps between validation runs (0 = per epoch)")
    parser.add_argument("--limit_val_batches", default=0, type=int)
    parser.add_argument("--log_every_n_steps", default=10, type=int)
    parser.add_argument(
        "--steps_per_execution", default=1, type=int,
        help="run K optimizer steps inside ONE jitted program "
             "(lax.scan over K stacked batches): amortizes host "
             "dispatch / interconnect round-trips when per-step launch "
             "latency is comparable to step compute. Checkpoint, "
             "validation, and preemption checks run between "
             "executions; a tail short of K batches is dropped loudly; "
             "the remaining step budget (after any checkpoint restore) "
             "is rounded DOWN to a multiple of K, and K shrinks to the "
             "remainder when fewer steps than one group are left; "
             "ignored (with a warning) under --offload_optimizer")
    parser.add_argument("--accumulate_grad_batches", default=1, type=int)
    parser.add_argument("--gradient_clip_val", default=0.0, type=float)
    parser.add_argument("--precision", default="bf16", type=str,
                        choices=["bf16", "fp32", "16", "32", "bf16-mixed"])
    parser.add_argument(
        "--offload", default="auto", type=str,
        choices=["auto", "none", "opt", "opt_master", "stream"],
        help="memory-placement ladder (docs/offload.md): none (all "
             "device-resident), opt (adam moments in host memory "
             "between steps), opt_master (moments + master/param "
             "copies host-resident), stream (per-layer parameter "
             "streaming — needs a stream-spec driver; the standard "
             "Trainer degrades it to opt_master loudly). auto probes "
             "the backend's memory kinds + byte budgets and picks the "
             "shallowest level that fits; every level falls back down "
             "the ladder when its memory kind is unsupported")
    parser.add_argument(
        "--offload_memory_kind", default="auto", type=str,
        choices=["auto", "pinned_host", "unpinned_host"],
        help="override the probe's host-memory-kind choice; forcing a "
             "kind the backend lacks raises instead of silently "
             "degrading")
    parser.add_argument(
        "--offload_optimizer", action="store_true", default=False,
        help="DEPRECATED: same as --offload=opt (kept so reference "
             "recipes parse; --offload wins when both are given). "
             "ZeRO-offload analog; reference: "
             "demo_classification_afqmc_erlangshen_offload.sh")
    parser.add_argument(
        "--profile_steps", default=None, type=str,
        help="START,END step range to capture a jax.profiler trace "
             "(saved under default_root_dir/profile; SURVEY.md §5.1)")
    parser.add_argument("--seed", default=42, type=int)
    parser.add_argument("--default_root_dir", default="./runs", type=str)
    parser.add_argument(
        "--metrics_port", default=0, type=int,
        help="serve GET /metrics (Prometheus text) from a stdlib "
             "exporter thread on this port during fit; 0 = off. Only "
             "process_index 0 of a multihost job binds the socket "
             "(docs/observability.md)")
    # removed with the hand-written executable cache: refused by name,
    # so a launch script that still passes it does not lose its cache
    # in silence
    parser.add_argument("--aot_cache_dir", action=_RefuseAotCacheDir,
                        help=argparse.SUPPRESS)
    # resilience (docs/fault_tolerance.md)
    resil = parent_parser.add_argument_group("resilience")
    resil.add_argument(
        "--disable_step_guards", action="store_true", default=False,
        help="apply optimizer updates unconditionally; default is the "
             "in-graph guard that skips steps with a non-finite "
             "loss/grad norm (params and moments untouched, "
             "bad_step_count incremented)")
    resil.add_argument(
        "--skip_steps_with_grad_norm_above", default=0.0, type=float,
        help="spike guard: also skip steps whose global grad norm "
             "exceeds this threshold (0 = off)")
    resil.add_argument(
        "--max_consecutive_bad_steps", default=0, type=int,
        help="after this many consecutive guarded-away steps, restore "
             "the last checkpoint and skip the offending data window "
             "(0 = never rewind)")
    resil.add_argument(
        "--max_rewinds", default=2, type=int,
        help="abort after this many rewinds in one fit — a run that "
             "keeps diverging needs a human, not another replay")
    resil.add_argument(
        "--loader_max_retries", default=0, type=int,
        help="wrap the train/val loaders in ResilientLoader: retry "
             "transient loader errors this many times with exponential "
             "backoff before failing (0 = off)")
    resil.add_argument("--loader_backoff_base", default=0.5, type=float,
                       help="first-retry backoff in seconds; doubles "
                            "per attempt, with jitter")
    resil.add_argument(
        "--loader_skip_batches", default=0, type=int,
        help="per-epoch budget of batches that may be skipped outright "
             "after retries exhaust")
    # mesh flags (replaces strategy=... + DeepSpeed JSON)
    MeshConfig.add_argparse_args(parent_parser)
    return parent_parser


class Trainer:
    def __init__(self, args: Any, mesh_config: Optional[MeshConfig] = None,
                 logger: Optional[Any] = None):
        self.args = args
        ensure_compile_cache()
        self.mesh_config = mesh_config or MeshConfig.from_argparse_args(args)
        self.mesh = make_mesh(self.mesh_config)
        set_mesh(self.mesh)
        self.logger = logger
        self.global_step = 0
        self.consumed_samples = 0
        self.callbacks: list = []
        self._log_path = os.path.join(
            getattr(args, "default_root_dir", "./runs"), "metrics.jsonl")
        #: the unified jsonl event sink (docs/observability.md): same
        #: file, same event names, same echo format as the old ad-hoc
        #: writer — resilience/serving events flow through it too
        self._sink = JsonlSink(path=self._log_path, echo=True,
                               logger=logger)
        #: flight recorder (docs/observability.md "Flight recorder"):
        #: every _log entry also enters a bounded in-memory ring, and a
        #: step-guard rewind dumps it — the last window of step stats —
        #: as a post-mortem bundle under <root>/flightrec
        self._flightrec = FlightRecorder(
            dump_dir=os.path.join(
                getattr(args, "default_root_dir", "./runs"),
                "flightrec"))
        self._flightrec.attach("trainer", self._flight_state)
        self._metrics_server = None
        self._preempted = False
        #: deterministic fault-injection plan (tests/chaos drills); see
        #: fengshen_tpu.resilience.faults.FaultPlan.install
        self.fault_plan = None
        self._install_preemption_handler()

    def _install_preemption_handler(self) -> None:
        """SIGTERM (the preemption notice on TPU pods) sets the flag on
        the most recently constructed Trainer; the train loop
        checkpoints and exits cleanly at the next step boundary. The
        previous handler is CHAINED, not discarded — outer launchers
        (SLURM re-queue shims, pod managers) keep their own SIGTERM
        behavior. ONE process-wide handler is installed (and re-pointed
        via weakref) no matter how many Trainers a sweep driver builds,
        so neither dead Trainers nor chain links accumulate."""
        import signal
        import threading
        import weakref
        if threading.current_thread() is not threading.main_thread():
            return
        st = _SIGTERM_STATE
        st["ref"] = weakref.ref(self)
        try:
            current = signal.getsignal(signal.SIGTERM)
        except (ValueError, OSError):  # restricted env
            return
        if st["handler"] is not None and current is st["handler"]:
            self._prev_sigterm = st["prev"]
            return

        if st["handler"] is None:
            def handler(signum, frame):
                trainer = st["ref"]() if st["ref"] is not None else None
                if trainer is not None:
                    trainer._preempted = True
                if callable(st["prev"]):
                    st["prev"](signum, frame)

            st["handler"] = handler
        try:
            st["prev"] = signal.signal(signal.SIGTERM, st["handler"])
            self._prev_sigterm = st["prev"]
        except (ValueError, OSError):  # non-main thread / restricted env
            pass

    # -- step compilation ------------------------------------------------
    def _make_grad_step(self, module: TrainModule):
        """Shared gradient computation (accumulation + metrics) used by
        both the fused train step and the offloaded two-program step."""
        accum = max(int(getattr(self.args, "accumulate_grad_batches", 1)),
                    1)

        def loss_fn(params, batch, rng):
            return module.training_loss(params, batch, rng)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        # deterministic fault injection (resilience harness): poison the
        # in-graph loss at the planned step numbers so the guard path is
        # exercised exactly where a real numeric blowup would hit. The
        # plan is snapshotted at build time; disarming rebuilds the step.
        plan = getattr(self, "fault_plan", None)
        nan_steps = tuple(sorted(plan.nan_loss_at_steps)) \
            if plan is not None else ()

        def grad_step(params, batch, rng, step):
            rng = jax.random.fold_in(rng, step)
            if accum == 1:
                (loss, metrics), grads = grad_fn(params, batch, rng)
            else:
                def micro(carry, mb):
                    acc_grads, acc_loss, i = carry
                    (l, m), g = grad_fn(params, mb,
                                        jax.random.fold_in(rng, i))
                    acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads,
                                                       g)
                    return (acc_grads, acc_loss + l, i + 1), m

                batch = jax.tree_util.tree_map(
                    lambda x: x.reshape((accum, x.shape[0] // accum) +
                                        x.shape[1:]), batch)
                zero = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss, _), metrics = jax.lax.scan(
                    micro, (zero, 0.0, 0), batch)
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                loss = loss / accum
                metrics = jax.tree_util.tree_map(
                    lambda m: m.mean() if jnp.issubdtype(m.dtype,
                                                         jnp.floating)
                    else m[-1], metrics)
            metrics = dict(metrics)
            metrics["loss"] = loss
            metrics["grad_norm"] = optax.global_norm(grads)
            if nan_steps:
                hit = jnp.any(jnp.asarray(nan_steps, jnp.int32) == step)
                metrics["loss"] = jnp.where(hit, jnp.float32(jnp.nan),
                                            metrics["loss"])
            return grads, metrics

        return grad_step

    def _guard_config(self) -> tuple[bool, float]:
        """(guards_enabled, spike_threshold) from the flags — single
        source for the fused, scanned, and offloaded step builders."""
        return (not getattr(self.args, "disable_step_guards", False),
                float(getattr(self.args,
                              "skip_steps_with_grad_norm_above", 0.0)
                      or 0.0))

    def _make_update_applier(self):
        """The (state, grads, metrics) -> (state, metrics) tail of a
        train step: guarded by default (skip non-finite/spiking
        updates in-graph, docs/fault_tolerance.md), unconditional
        under --disable_step_guards. Shared by the fused K=1 step and
        the steps_per_execution scan body."""
        from fengshen_tpu.resilience.guards import guarded_apply, step_ok
        guards_on, spike = self._guard_config()

        def apply_update(state: TrainState, grads, metrics):
            if guards_on:
                new_state = guarded_apply(state, grads,
                                          step_ok(metrics, spike))
            else:
                new_state = state.apply_gradients(grads)
            metrics["bad_step_count"] = new_state.bad_step_count
            return new_state, metrics

        return apply_update

    def _build_train_step(self, module: TrainModule, state_sh, batch_spec,
                          sample_batch=None):
        mesh = self.mesh
        grad_step = self._make_grad_step(module)
        apply_update = self._make_update_applier()

        def train_step(state: TrainState, batch, rng):
            grads, metrics = grad_step(state.params, batch, rng,
                                       state.step)
            return apply_update(state, grads, metrics)

        # fit specs to actual shapes: a debug batch smaller than the batch
        # axes degrades to replicated instead of erroring
        from fengshen_tpu.parallel.partition import _spec_fits

        def to_sharding(spec, leaf):
            shape = tuple(np.shape(leaf)) if leaf is not None else ()
            return NamedSharding(mesh, _spec_fits(spec, mesh, shape))

        if sample_batch is not None:
            batch_shardings = jax.tree_util.tree_map(
                to_sharding, batch_spec, sample_batch,
                is_leaf=lambda x: isinstance(x, P))
        else:
            batch_shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec), batch_spec,
                is_leaf=lambda x: isinstance(x, P))

        # eval/predict always feed single batches — stash the per-batch
        # shardings regardless of which train feed shape is returned
        self._batch_sh = batch_shardings

        spe = max(int(getattr(self.args, "steps_per_execution", 1)), 1)
        policy = getattr(self, "_offload_policy", None)
        offloaded = (policy.offloads_opt_state if policy is not None
                     else bool(getattr(self.args, "offload_optimizer",
                                       False)))
        if offloaded:
            if spe > 1:
                import sys
                print("[fengshen-tpu] --steps_per_execution is ignored "
                      "with optimizer offload (the offloaded update is "
                      "a two-program step with a host round-trip per "
                      "step — scanning K steps on-device would keep the "
                      "moments in HBM and defeat the offload)",
                      file=sys.stderr, flush=True)
            return self._build_offloaded_train_step(
                module, state_sh, batch_shardings,
                policy=policy), batch_shardings

        if spe > 1:
            # K steps per dispatch: scan over K stacked batches. The rng
            # fold_in(rng, state.step) inside grad_step makes substep
            # randomness identical to the K=1 path step for step.
            def multi_step(state: TrainState, batches, rng):
                def body(st, batch):
                    grads, m = grad_step(st.params, batch, rng, st.step)
                    return apply_update(st, grads, m)
                state, metrics = jax.lax.scan(body, state, batches)
                # same reduction policy as grad accumulation: floats
                # average over the K substeps, counts keep the last
                # (bad_step_count is cumulative, so last == end-of-group)
                metrics = jax.tree_util.tree_map(
                    lambda m: m.mean() if jnp.issubdtype(
                        m.dtype, jnp.floating) else m[-1], metrics)
                return state, metrics

            def to_stacked(spec, leaf):
                shape = (spe,) + tuple(np.shape(leaf)) \
                    if leaf is not None else ()
                return NamedSharding(
                    mesh, _spec_fits(P(None, *spec), mesh, shape))

            if sample_batch is not None:
                stacked_sh = jax.tree_util.tree_map(
                    to_stacked, batch_spec, sample_batch,
                    is_leaf=lambda x: isinstance(x, P))
            else:
                stacked_sh = jax.tree_util.tree_map(
                    lambda spec: NamedSharding(mesh, P(None, *spec)),
                    batch_spec, is_leaf=lambda x: isinstance(x, P))
            return jax.jit(
                multi_step,
                in_shardings=(state_sh, stacked_sh, None),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ), stacked_sh

        return jax.jit(
            train_step,
            in_shardings=(state_sh, batch_shardings, None),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        ), batch_shardings

    def _build_offloaded_train_step(self, module, state_sh, batch_sh,
                                    policy=None):
        """ZeRO-offload analog: the optimizer state lives in HOST memory
        between steps, so the gradient pass runs with HBM holding only
        params + grads + activations (reference capability:
        DeepSpeed offload_optimizer, fengshen/examples/classification/
        demo_classification_afqmc_erlangshen_offload.sh:9-33). Under
        the policy's "opt_master" level the master/param copies ALSO
        park host-side between steps — device memory holds the model
        only transiently during one grad+update (docs/offload.md).

        XLA in this build cannot annotate memory spaces inside an SPMD
        program, so the H2D/D2H moves happen BETWEEN two jitted programs:
        grad_step (device-only) and update_step (donated; moments are
        device-resident only transiently during the update).
        """
        from fengshen_tpu.trainer.memory import (
            probe_memory_capabilities, resolve_offload_policy)
        if policy is None:
            policy = resolve_offload_policy("opt", log=self._log)
        grad_step = self._make_grad_step(module)
        # "bring it back on-device" = the device's DEFAULT memory kind:
        # the literal "device" raises on backends whose default space
        # has another name (the CPU backend's is "unpinned_host")
        device_kind = probe_memory_capabilities().device_memory_kind
        param_sh = state_sh.params
        opt_host_sh = jax.tree_util.tree_map(
            lambda s: s.with_memory_kind(policy.opt_state_kind),
            state_sh.opt_state)
        opt_dev_sh = jax.tree_util.tree_map(
            lambda s: s.with_memory_kind(device_kind), state_sh.opt_state)
        park_params = policy.offloads_params
        param_host_sh = jax.tree_util.tree_map(
            lambda s: s.with_memory_kind(policy.master_kind),
            param_sh) if park_params else None
        param_dev_sh = jax.tree_util.tree_map(
            lambda s: s.with_memory_kind(device_kind), param_sh)

        grad_jit = jax.jit(
            grad_step,
            in_shardings=(param_sh, batch_sh, None, None),
            out_shardings=(param_sh, None))

        def update(params, grads, opt_state, step, tx):
            updates, new_opt = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_opt,
                    step + 1)

        update_jit = None
        from fengshen_tpu.resilience.guards import step_ok
        guards_on, spike = self._guard_config()

        def step_fn(state, batch, rng):
            nonlocal update_jit
            # H2D (opt_master): master/param copies park host-side
            # between steps — bring them on-device for this step only
            params_dev = jax.device_put(state.params, param_dev_sh) \
                if park_params else state.params
            grads, metrics = grad_jit(params_dev, batch, rng, state.step)
            if guards_on:
                # host-side guard, same predicate as the fused step:
                # this path already pays a host round-trip per step for
                # the moments, so pulling the scalar costs no extra
                # dispatch
                if not bool(step_ok(metrics, spike)):
                    new_state = state.replace(
                        step=state.step + 1,
                        bad_step_count=state.bad_step_count + 1)
                    metrics = dict(metrics)
                    metrics["bad_step_count"] = new_state.bad_step_count
                    return new_state, metrics
            # H2D: bring the moments on-device only for the update
            opt_dev = jax.device_put(state.opt_state, opt_dev_sh)
            if update_jit is None:
                import functools
                update_jit = jax.jit(
                    functools.partial(update, tx=state.tx),
                    in_shardings=(param_sh, param_sh, opt_dev_sh, None),
                    out_shardings=(param_sh, opt_dev_sh, None),
                    donate_argnums=(0, 1, 2))
            new_params, new_opt_dev, new_step = update_jit(
                params_dev, grads, opt_dev, state.step)
            # D2H: park the moments (and under opt_master the params)
            # back in host memory
            if park_params:
                new_params = jax.device_put(new_params, param_host_sh)
            new_opt = jax.device_put(new_opt_dev, opt_host_sh)
            new_state = state.replace(step=new_step, params=new_params,
                                      opt_state=new_opt)
            metrics = dict(metrics)
            metrics["bad_step_count"] = new_state.bad_step_count
            return new_state, metrics

        return step_fn

    # -- shared state building -------------------------------------------
    def _make_init_fn(self, module: TrainModule, rng, total_steps: int,
                      eval_only: bool = False):
        """The TrainState factory fit() and validate() share. Eval-only
        states carry a zero-size optimizer (no adam moments — a model
        that only fits with --offload_optimizer must still be
        evaluable), and restore falls back to weights-only through the
        checkpoint callback's existing opt_state-mismatch path."""
        import optax

        def init_fn():
            params = module.init_params(rng)
            if eval_only:
                tx = optax.set_to_zero()
            else:
                tx, _ = module.configure_optimizers(total_steps, params)
            return TrainState.create(
                apply_fn=getattr(module, "model", None) and
                module.model.apply or (lambda *a, **k: None),
                params=params, tx=tx)

        return init_fn

    def _restore_callback(self):
        return next((c for c in self.callbacks
                     if hasattr(c, "maybe_restore")), None)

    # -- resilience ------------------------------------------------------
    def _wrap_loader(self, loader, stage: str = "train"):
        """Wrap a loader in ResilientLoader when --loader_max_retries
        asks for it (transient read errors cost a backoff, not the
        run); identity otherwise."""
        retries = int(getattr(self.args, "loader_max_retries", 0) or 0)
        skips = int(getattr(self.args, "loader_skip_batches", 0) or 0)
        # a skip budget alone still needs the wrapper — silently
        # ignoring --loader_skip_batches would be a misconfig trap
        if loader is None or (retries <= 0 and skips <= 0):
            return loader
        from fengshen_tpu.resilience import ResilientLoader
        wrapped = ResilientLoader(
            loader, max_retries=retries,
            backoff_base=float(getattr(self.args, "loader_backoff_base",
                                       0.5)),
            skip_batch_budget=skips,
            log=self._log, stage=stage,
            # per-host jitter: identical seeds would re-hit the storage
            # in lockstep from every process on a retry (the thundering
            # herd the jitter exists to break up)
            jitter_seed=jax.process_index())
        if skips > 0 and stage == "train" and not wrapped.resumable:
            # the budget only works on loaders that can be advanced
            # past a poison batch — say so instead of silently never
            # skipping (e.g. --sampler_type single)
            self._log({"event": "loader_skip_budget_inert",
                       "reason": "train loader is not mid-epoch "
                                 "resumable; skips need the stateful "
                                 "random sampler"})
        return wrapped

    def _rewind(self, state: TrainState, ckpt_cb, bad_steps: int
                ) -> TrainState:
        """Rewind-on-divergence: restore the last checkpoint (its params
        predate the bad window — the step guard skipped every bad
        update) and advance consumed_samples PAST the offending data so
        the replay sees fresh batches. Raises instead of replaying
        forever: a run that keeps diverging needs a human."""
        if ckpt_cb is None:
            raise RuntimeError(
                f"{bad_steps} consecutive bad steps at step "
                f"{self.global_step} and no checkpoint callback to "
                "rewind from — aborting instead of optimizing on "
                "garbage")
        if self._rewinds_left <= 0:
            raise RuntimeError(
                f"rewind budget exhausted (--max_rewinds="
                f"{getattr(self.args, 'max_rewinds', 2)}) and still "
                f"seeing {bad_steps} consecutive bad steps at step "
                f"{self.global_step}")
        self._rewinds_left -= 1
        pre_step = int(self.global_step)
        pre_consumed = int(self.consumed_samples)
        if hasattr(ckpt_cb, "wait"):
            ckpt_cb.wait()  # an in-flight async save must land first
        # rewind to THIS run's latest checkpoint: maybe_restore reads
        # load_ckpt_path, which may point at a stale warm-start dir —
        # the run's own saves are the only valid rewind targets
        orig_load = getattr(ckpt_cb, "load_path", None)
        if getattr(ckpt_cb, "save_path", None):
            ckpt_cb.load_path = ckpt_cb.save_path
        try:
            restored = ckpt_cb.maybe_restore(state, self)
        finally:
            if hasattr(ckpt_cb, "load_path"):
                ckpt_cb.load_path = orig_load
        if restored is state and int(self.global_step) == pre_step:
            raise RuntimeError(
                f"rewind after {bad_steps} consecutive bad steps found "
                "no restorable checkpoint (set --save_ckpt_path/"
                "--every_n_train_steps)")
        # the window [checkpoint, pre_step] produced the divergence —
        # keep the data cursor ahead of it
        self.consumed_samples = max(pre_consumed,
                                    int(self.consumed_samples))
        if getattr(self, "_stepstats", None) is not None:
            # goodput ledger: the replayed window counts against the
            # attempted-steps denominator
            self._stepstats.record_rewind(pre_step,
                                          int(self.global_step))
        self._log({"event": "rewind", "from_step": pre_step,
                   "to_step": int(self.global_step),
                   "bad_steps": int(bad_steps),
                   "consumed_samples": int(self.consumed_samples),
                   "rewinds_left": self._rewinds_left})
        try:
            # post-mortem bundle (docs/fault_tolerance.md): the ring
            # holds the step entries — tokens/s, mfu, goodput,
            # bad_step_count — leading into the divergence. Process-0
            # only, like every other writer (a collective divergence
            # would otherwise have N hosts clobbering one bundle path)
            if jax.process_index() == 0:
                from fengshen_tpu.observability import get_registry
                self._flightrec.snapshot_metrics([get_registry()],
                                                 force=True)
                self._flightrec.dump(
                    reason="rewind",
                    extra={"from_step": pre_step,
                           "to_step": int(self.global_step),
                           "bad_steps": int(bad_steps)})
        except Exception as e:  # noqa: BLE001 — telemetry must never
            # fail the rewind that is saving the run
            self._log({"event": "flightrec_dump_error",
                       "error": str(e)[:200]})
        return restored

    # -- predict state ---------------------------------------------------
    def restore_for_predict(self, module: TrainModule,
                            stage: str = "predict") -> TrainState:
        """Build + restore an eval-only TrainState WITHOUT running a
        validation sweep — the cheap entry for predict-only drivers
        (e.g. classification --do_predict_only), and the shared
        state-construction path of validate()."""
        module.setup(stage)
        rng = jax.random.PRNGKey(getattr(self.args, "seed", 42))
        state, state_sh = create_sharded_state(
            self._make_init_fn(module, rng, 1, eval_only=True),
            module.partition_rules(), self.mesh)
        self._state_sh = state_sh
        ckpt_cb = self._restore_callback()
        prev_step = self.global_step
        if ckpt_cb is not None:
            state = ckpt_cb.maybe_restore(state, self, weights_only=True)
        if self.global_step == prev_step:
            # restore silently skipped (no checkpoint found): the run
            # proceeds on init_params — legitimate for HF-imported
            # weights, surprising otherwise, so SAY it
            self._log({"event": f"{stage}_no_checkpoint_restored"})
        return state

    # -- validate --------------------------------------------------------
    def validate(self, module: TrainModule, datamodule) -> TrainState:
        """Eval-only entry (the reference's `--do_eval_only` path,
        reference: fengshen/examples/pretrain_t5/
        pretrain_mt5_small_predict.sh): build/restore the state, run ONE
        validation sweep over the val loader, no training."""
        args = self.args
        datamodule.trainer = self
        loader = getattr(datamodule, "val_dataloader", lambda: None)()
        if loader is None:
            # mid-fit a missing val loader is skippable; here it IS the
            # whole job
            raise ValueError(
                "validate() has no validation data — pass --val_file / "
                "a 'validation' split (val_datasets_field="
                f"{getattr(args, 'val_datasets_field', 'validation')!r})")
        state = self.restore_for_predict(module, stage="validate")
        rng = jax.random.PRNGKey(getattr(args, "seed", 42))
        self._log({"event": "validate_start",
                   "step": self.global_step})
        self._run_validation(module, datamodule, state, rng)
        return state

    # -- fit -------------------------------------------------------------
    def fit(self, module: TrainModule, datamodule) -> TrainState:
        try:
            return self._fit(module, datamodule)
        finally:
            # the --metrics_port exporter must not outlive the fit: a
            # leaked daemon socket serves stale metrics and makes the
            # next Trainer on the same port die with EADDRINUSE
            self._close_metrics_server()

    def _close_metrics_server(self) -> None:
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None

    def _fit(self, module: TrainModule, datamodule) -> TrainState:
        args = self.args
        module.setup("fit")
        # wire the datamodule so resumable samplers can read
        # consumed_samples (reference: universal_datamodule.py:8-17)
        datamodule.trainer = self
        rng = jax.random.PRNGKey(getattr(args, "seed", 42))

        meta_loader = datamodule.train_dataloader()
        dataset_len = getattr(meta_loader, "num_samples",
                              None) or len(meta_loader)
        world_batch = getattr(meta_loader, "global_batch_size", 1)
        from fengshen_tpu.models.model_utils import get_total_steps
        total_steps = get_total_steps(args, dataset_len, world_batch)

        max_steps = getattr(args, "max_steps", -1)
        if max_steps is None or max_steps <= 0:
            max_steps = total_steps

        # build sharded state (peek never advances the stateful sampler)
        sample_batch = meta_loader.peek() if hasattr(meta_loader, "peek") \
            else next(iter(meta_loader))
        rules = module.partition_rules()

        # memory placement (docs/offload.md): probe the backend's
        # memory kinds, size the state from eval_shape (no buffers),
        # resolve the offload ladder level BEFORE anything compiles —
        # the policy decides the state shardings and which step program
        # is built
        from fengshen_tpu.trainer.memory import (offload_request_from_args,
                                                 record_offload_metrics,
                                                 resolve_offload_policy)
        init_fn = self._make_init_fn(module, rng, total_steps)
        abstract = jax.eval_shape(init_fn)
        mesh_shape = dict(self.mesh.shape)
        policy = resolve_offload_policy(
            offload_request_from_args(args),
            abstract_state=abstract,
            memory_kind=getattr(args, "offload_memory_kind", "auto"),
            can_stream=False,  # the standard Trainer has no stream spec
            # one state replica shards over the model axes only — the
            # data/sequence axes REPLICATE it, so counting every device
            # would overestimate capacity by the DP factor
            state_shard_ways=(mesh_shape.get("fsdp", 1) *
                              mesh_shape.get("tensor", 1) *
                              mesh_shape.get("pipe", 1)),
            log=self._log)
        self._offload_policy = policy
        spe = 1 if policy.offloads_opt_state else \
            max(int(getattr(args, "steps_per_execution", 1)), 1)

        state, state_sh = create_sharded_state(
            init_fn, rules, self.mesh, policy=policy, abstract=abstract)

        # observability (docs/observability.md): ladder level, probed
        # kinds, and the bytes actually parked host-side between steps
        host_bytes = 0
        if policy.offloads_opt_state:
            host_bytes += sum(
                leaf.nbytes
                for leaf in jax.tree_util.tree_leaves(state.opt_state)
                if hasattr(leaf, "nbytes"))
        if policy.offloads_params:
            host_bytes += sum(
                leaf.nbytes
                for leaf in jax.tree_util.tree_leaves(state.params)
                if hasattr(leaf, "nbytes"))
        record_offload_metrics(policy, host_resident_bytes=host_bytes)
        _, self._schedule = module.configure_optimizers(total_steps,
                                                        state.params)

        # restore (updates self.global_step / self.consumed_samples)
        ckpt_cb = self._restore_callback()
        if ckpt_cb is not None:
            state = ckpt_cb.maybe_restore(state, self)
        # K-step programs only stop on execution boundaries, so the
        # REMAINING budget (after any restore — a fresh run resumes at
        # 0) must be a multiple of K. Align ONCE, here, from the
        # original max_steps: aligning before restore and again after
        # double-rounds and can silently lose up to 2(K-1) steps.
        # Clamp/round DOWN and say so rather than overshooting the LR
        # schedule (parity contract with the K=1 run); the step program
        # is built below, after this point, so a clamped K takes effect.
        remaining = max_steps - self.global_step
        if spe > 1 and 0 < remaining < spe:
            # fewer steps left than one K-group: shrink K to the
            # remainder rather than either overshooting the schedule by
            # a full group or rounding the tail steps away
            self._log({"event": "steps_per_execution_clamped",
                       "from": spe, "to": int(remaining),
                       "resumed_at": int(self.global_step)})
            spe = int(remaining)
            args.steps_per_execution = spe
        elif spe > 1 and remaining > 0 and remaining % spe:
            # not K-aligned: round the budget down to a whole number of
            # K-groups past the current step
            new_max = self.global_step + (remaining // spe) * spe
            self._log({"event": "max_steps_rounded_down",
                       "from": int(max_steps), "to": int(new_max),
                       "steps_per_execution": spe,
                       "resumed_at": int(self.global_step)})
            max_steps = new_max
        # (re)create the train loader AFTER restore so the resumable
        # sampler starts from the restored consumed_samples
        train_loader = self._wrap_loader(datamodule.train_dataloader())

        batch_spec = module.batch_spec(sample_batch)
        step_fn, batch_sh = self._build_train_step(module, state_sh,
                                                   batch_spec, sample_batch)
        self._state_sh = state_sh
        # eval/predict always feed SINGLE batches — under
        # steps_per_execution>1 the train feed (batch_sh) is stacked;
        # _build_train_step stashed the per-batch shardings for the
        # validation path in self._batch_sh either way

        n_params = sum(np.prod(p.shape) for p in
                       jax.tree_util.tree_leaves(state.params))
        self._log({"event": "fit_start", "n_params": int(n_params),
                   "total_steps": int(total_steps),
                   "mesh": dict(self.mesh.shape)})

        # step-stats pipeline (docs/observability.md): tokens/s, MFU
        # against the resolved per-chip peak (always finite — nominal
        # fallback off-TPU), and goodput fed by the guards'
        # bad_step_count + the rewind ledger
        flops_per_tok = module.flops_per_token() or 6.0 * float(n_params)
        self._stepstats = StepStats(
            flops_per_token=flops_per_tok,
            n_devices=len(jax.devices()),
            device_kind=jax.devices()[0].device_kind)
        record_build_info()
        self._maybe_start_metrics_server()
        log_every = max(int(getattr(args, "log_every_n_steps", 10)), 1)
        val_interval = int(getattr(args, "val_check_interval", 0) or 0)

        profile_range = None
        if getattr(args, "profile_steps", None):
            lo, hi = (int(x) for x in str(args.profile_steps).split(","))
            profile_range = (lo, hi)
            self._profiling = False

        def crossed(prev: int, cur: int, every: int) -> bool:
            # did [prev+1, cur] contain a multiple of `every`? (an
            # execution advances global_step by spe, which may jump
            # over the exact multiple)
            return every > 0 and (cur // every) > (prev // every)

        # rewind-on-divergence bookkeeping (docs/fault_tolerance.md):
        # only armed via --max_consecutive_bad_steps, because detecting
        # the consecutive run needs the cumulative bad_step_count pulled
        # to the host every execution (a per-step device sync the
        # default fast path must not pay)
        max_consec = int(getattr(args, "max_consecutive_bad_steps", 0)
                         or 0)
        if max_consec and getattr(args, "disable_step_guards", False):
            raise ValueError("--max_consecutive_bad_steps needs the step "
                             "guards; drop --disable_step_guards")
        self._rewinds_left = int(getattr(args, "max_rewinds", 2))
        consec_bad = 0
        prev_bad_total = int(state.bad_step_count) if max_consec else 0
        skips_credited = 0  # loader skips already folded into consumed

        epoch = 0
        first_step = int(self.global_step)
        # a run restored at (or past) its step budget must not execute
        # even one group — the loop body only checks max_steps AFTER an
        # execution, which would overshoot the LR schedule
        done = self.global_step >= max_steps
        while not done:
            if hasattr(train_loader, "set_epoch"):
                train_loader.set_epoch(epoch)
            feed = (_prefetch(train_loader, batch_sh) if spe == 1 else
                    _prefetch_grouped(train_loader, batch_sh, spe))
            rewound = False
            for group, device_batch, skips_snap in _spanned_iter(
                    feed, "train/load"):
                if profile_range is not None:
                    self._maybe_profile(profile_range)
                with span("train/step"):
                    state, metrics = step_fn(state, device_batch, rng)
                prev_step = int(self.global_step)
                if prev_step == first_step:
                    # the first execution traced the step: say which
                    # kernel each attention/CE call site took
                    # (docs/kernels.md)
                    log_dispatch(self._log)
                self.global_step = prev_step + len(group)
                # callbacks (e.g. every-n checkpointing) need the span
                # of this execution to detect crossed boundaries
                self.prev_global_step = prev_step
                self.consumed_samples += world_batch * len(group)
                # credit skipped poison batches exactly when the
                # training frontier passes them (the fetch-time
                # snapshot), so a checkpoint taken inside the prefetch
                # window never records a cursor ahead of the data
                # actually trained on
                if skips_snap > skips_credited:
                    self.consumed_samples += world_batch * (
                        skips_snap - skips_credited)
                    skips_credited = skips_snap
                self._stepstats.record_execution(
                    len(group), sum(module.tokens_in_batch(b)
                                    for b in group))

                if crossed(prev_step, self.global_step, log_every):
                    # float(v) waits for the step just dispatched: the
                    # device idles from that step's end until the next
                    # dispatch, and this span names the gap
                    with span("train/log", step=self.global_step):
                        metrics = {k: float(v)
                                   for k, v in metrics.items()}
                        entry = {"step": self.global_step,
                                 "lr": float(
                                     self._schedule(self.global_step)),
                                 "consumed_samples": self.consumed_samples,
                                 **metrics}
                        # tokens_per_sec / mfu / goodput over the window
                        # since the last entry; closes the window
                        entry.update(self._stepstats.window_entry(
                            self.global_step,
                            bad_step_count=int(
                                metrics.get("bad_step_count", 0))))
                        self._log(entry)

                if crossed(prev_step, self.global_step, val_interval):
                    with span("train/validate"):
                        self._run_validation(module, datamodule, state,
                                             rng)
                for cb in self.callbacks:
                    if hasattr(cb, "on_train_step_end"):
                        # `train/checkpoint` only where a save happens,
                        # so save stalls stand next to step time under
                        # their own name; any other callback's time is
                        # `train/callback`
                        due = getattr(cb, "save_due", None)
                        name = "train/checkpoint" \
                            if due is not None and due(self) \
                            else "train/callback"
                        with span(name, callback=type(cb).__name__):
                            cb.on_train_step_end(self, state)
                if max_consec:
                    bad_total = int(metrics["bad_step_count"])
                    delta, prev_bad_total = (bad_total - prev_bad_total,
                                             bad_total)
                    if delta >= len(group):
                        consec_bad += len(group)  # whole execution bad
                    elif delta > 0:
                        # mixed group: substep order is unknown from the
                        # host; assume the bad run is trailing
                        consec_bad = delta
                    else:
                        consec_bad = 0
                    if consec_bad >= max_consec:
                        state = self._rewind(state, ckpt_cb, consec_bad)
                        prev_bad_total = int(state.bad_step_count)
                        consec_bad = 0
                        plan = getattr(self, "fault_plan", None)
                        if plan is not None and plan.nan_loss_at_steps \
                                and plan.clear_nan_on_rewind:
                            # replayed step numbers must not re-fire the
                            # injected fault: disarm and rebuild the
                            # step program without the injection
                            plan.disarm_nan()
                            step_fn, batch_sh = self._build_train_step(
                                module, state_sh, batch_spec,
                                sample_batch)
                        rewound = True
                        break
                if self._preempted:
                    # preemption-aware autosave (SURVEY.md §5.3: TPU pods
                    # preempt; the reference only had SLURM re-queue).
                    # MUST flush: an async save lost to process exit is
                    # no save at all
                    if ckpt_cb is not None:
                        with span("train/checkpoint"):
                            try:
                                ckpt_cb.save(state, self, sync=True)
                            except TypeError:  # cb without sync kwarg
                                ckpt_cb.save(state, self)
                    self._log({"event": "preempted_saved",
                               "step": self.global_step})
                    return state
                if self.global_step >= max_steps:
                    done = True
                    break
            if rewound:
                # same epoch, fresh loader: the resumable sampler picks
                # up from the advanced consumed_samples, skipping the
                # window that produced the bad steps
                train_loader = self._wrap_loader(
                    datamodule.train_dataloader())
                skips_credited = 0  # fresh wrapper, fresh counter
                continue
            # a skip at the very end of the epoch has no later batch to
            # carry its snapshot — settle the remainder here so the
            # next epoch's loader starts past it. ONLY on a natural
            # epoch end: after a max_steps break the uncredited skips
            # sit beyond the training frontier (prefetch window) and
            # must not advance the cursor a resume will trust
            if not done:
                tail_skips = getattr(train_loader, "skipped_total", 0)
                if tail_skips > skips_credited:
                    self.consumed_samples += world_batch * (
                        tail_skips - skips_credited)
                    skips_credited = tail_skips
            epoch += 1
            if getattr(args, "max_epochs", 1) and \
                    epoch >= max(getattr(args, "max_epochs", 1), 1):
                done = True
            if not val_interval:
                with span("train/validate"):
                    self._run_validation(module, datamodule, state, rng)

        if profile_range is not None and getattr(self, "_profiling", False):
            jax.profiler.stop_trace()
            self._profiling = False
        for cb in self.callbacks:
            if hasattr(cb, "on_fit_end"):
                cb.on_fit_end(self, state)
        self._log({"event": "fit_end", "step": self.global_step})
        return state

    def _maybe_profile(self, profile_range: tuple) -> None:
        """Start/stop a jax.profiler trace over the configured step window
        (SURVEY.md §5.1: trace-guided perf work instead of guesses)."""
        lo, hi = profile_range
        if getattr(self, "_profile_done", False):
            return
        # >= lo (not a range test): under --steps_per_execution the
        # observed global_step values can jump clean over [lo, hi) — a
        # late start still captures at least one full execution
        if not self._profiling and self.global_step >= lo:
            path = os.path.join(
                getattr(self.args, "default_root_dir", "./runs"), "profile")
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
            self._profiling = True
            self._log({"event": "profile_start", "step": self.global_step,
                       "path": path})
        elif self._profiling and self.global_step >= hi:
            jax.profiler.stop_trace()
            self._profiling = False
            self._profile_done = True
            self._log({"event": "profile_stop", "step": self.global_step})

    # -- predict ---------------------------------------------------------
    def predict(self, module: TrainModule, dataloader, state=None,
                params=None, **kwargs) -> list:
        """Prediction loop over `module.predict_step`
        (reference: the Lightning predict path used for TP generation,
        fengshen/examples/ziya_llama/finetune_ziya_llama.py:155-176 +
        strategies/megatron_deepspeed.py:371-399)."""
        if params is None:
            params = state.params if state is not None else None
        if params is None:
            raise ValueError("predict needs state or params")
        if not hasattr(module, "predict_step"):
            raise AttributeError(
                f"{type(module).__name__} defines no predict_step")
        # jit + shard the predict step when the module opts in (generation
        # loops with python control flow stay eager); batches ride the
        # same shardings as training (VERDICT r1 weak #8)
        step = module.predict_step
        if getattr(module, "jit_predict", False):
            import functools
            step = jax.jit(functools.partial(module.predict_step, **kwargs))
            kwargs = {}
        outputs = []
        warned_fallback = False
        for batch in dataloader:
            if getattr(self, "_batch_sh", None) is not None:
                try:
                    batch = jax.device_put(batch, self._batch_sh)
                except (ValueError, TypeError) as e:
                    # batch structure differs from training — running
                    # un-sharded is correct but quietly gathers onto one
                    # device on a pod, so say so ONCE (same contract as
                    # _run_validation's val_shard_fallback)
                    if not warned_fallback:
                        warned_fallback = True
                        self._log({"event": "predict_shard_fallback",
                                   "step": self.global_step,
                                   "error": str(e)[:200]})
            outputs.append(step(params, batch, **kwargs))
        return outputs

    # -- validation ------------------------------------------------------
    def _run_validation(self, module, datamodule, state, rng):
        loader = self._wrap_loader(
            getattr(datamodule, "val_dataloader", lambda: None)(),
            stage="val")
        if loader is None:
            return
        val_params = state.params
        policy = getattr(self, "_offload_policy", None)
        if policy is not None and policy.offloads_params and \
                getattr(self, "_state_sh", None) is not None:
            # opt_master parks params in HOST memory between steps
            # (docs/offload.md), but the cached val jit's in_shardings
            # are device-resident — bring one device copy up for the
            # sweep (dropped when the sweep ends). Without this, any
            # backend whose host kind differs from the device default
            # would mismatch and silently demote every batch to the
            # inferred-sharding fallback jit.
            device_kind = policy.caps.device_memory_kind
            val_params = jax.device_put(
                state.params,
                jax.tree_util.tree_map(
                    lambda s: s.with_memory_kind(device_kind),
                    self._state_sh.params))
        losses, limit = [], getattr(self.args, "limit_val_batches", 0)
        # cache the compiled val step across invocations; params ride the
        # training shardings so validation never gathers the model onto
        # one device (VERDICT r1 weak #8)
        if getattr(self, "_val_fn_module", None) is not module:
            param_sh = getattr(self, "_state_sh", None)
            if param_sh is not None:
                self._val_fn = jax.jit(
                    module.validation_loss,
                    in_shardings=(param_sh.params,
                                  getattr(self, "_batch_sh", None), None))
            else:
                self._val_fn = jax.jit(module.validation_loss)
            self._val_fn_module = module
        val_fn = self._val_fn
        # per-metric (weighted sum, weight) so a metric emitted by only
        # some batches is averaged over ITS batches, and per-batch means
        # (accuracies) are weighted by batch rows rather than skewed by a
        # short tail batch (ADVICE r4).  Count-like metrics (n_*, *_sum,
        # *_count) are summed, not averaged.
        metric_sums: dict = {}

        def _is_countlike(k: str) -> bool:
            base = k[4:] if k.startswith("val_") else k
            return (base.startswith("n_") or base.endswith("_sum")
                    or base.endswith("_count"))

        def _batch_rows(batch) -> float:
            for v in jax.tree_util.tree_leaves(batch):
                if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1:
                    return float(v.shape[0])
            return 1.0

        def _accumulate(metrics, weight):
            for k, v in (metrics or {}).items():
                try:
                    v = float(v)
                except (TypeError, ValueError):
                    continue  # non-scalar diagnostic; skip
                s, w = metric_sums.get(k, (0.0, 0.0))
                if _is_countlike(k):
                    metric_sums[k] = (s + v, -1.0)
                else:
                    metric_sums[k] = (s + v * weight, w + weight)

        for i, batch in enumerate(loader):
            if limit and i >= limit:
                break
            rows = _batch_rows(batch)
            try:
                loss, metrics = val_fn(val_params, batch, rng)
            except (TypeError, ValueError) as e:
                # this batch doesn't fit the train batch spec — run IT on a
                # separately cached inferred-sharding jit, but keep the
                # sharded val_fn for subsequent conforming batches
                if not hasattr(self, "_val_fn_plain"):
                    self._val_fn_plain = jax.jit(module.validation_loss)
                    self._log({"event": "val_shard_fallback",
                               "step": self.global_step,
                               "error": str(e)[:200]})
                loss, metrics = self._val_fn_plain(val_params, batch,
                                                   rng)
            _accumulate(metrics, rows)
            losses.append((float(loss), rows))
        if losses:
            total_rows = sum(w for _, w in losses)
            entry = {"step": self.global_step,
                     "val_loss": sum(l * w for l, w in losses)
                     / max(total_rows, 1.0)}
            for k, (total, w) in metric_sums.items():
                key = k if k.startswith("val_") else f"val_{k}"
                entry[key] = total if w < 0 else total / max(w, 1e-9)
            self._log(entry)

    # -- logging ---------------------------------------------------------
    def _log(self, entry: dict) -> None:
        """One structured event. Delegates to the unified JsonlSink
        (process-0 gating, jsonl write, console echo, logger bridge) —
        kept as a method because resilience loaders and callbacks hold
        `log=self._log` references. Every entry also enters the flight
        recorder's ring so a rewind dump carries the recent step
        trajectory."""
        self._flightrec.record(entry)
        self._sink(entry)

    def _flight_state(self) -> dict:
        """The flight recorder's trainer provider: cursor state + the
        scalar run config (the post-mortem bundle's `trainer.json`)."""
        return {
            "step": int(self.global_step),
            "consumed_samples": int(self.consumed_samples),
            "rewinds_left": int(getattr(self, "_rewinds_left", 0) or 0),
            "args": {k: v for k, v in
                     sorted(getattr(self.args, "__dict__", {}).items())
                     if isinstance(v, (bool, int, float, str,
                                       type(None)))},
        }

    def _maybe_start_metrics_server(self) -> None:
        """`--metrics_port N`: a stdlib exporter thread serving
        GET /metrics for the duration of the job; process-0-gated (the
        gate lives in start_metrics_server)."""
        port = int(getattr(self.args, "metrics_port", 0) or 0)
        if not port or self._metrics_server is not None:
            return
        from fengshen_tpu.observability import start_metrics_server
        self._metrics_server = start_metrics_server(port)
        if self._metrics_server is not None:
            self._log({"event": "metrics_server_started",
                       "port": self._metrics_server.port})
