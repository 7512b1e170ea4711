"""UniversalCheckpoint — orbax-backed checkpoint callback.

Port of the reference's Lightning ModelCheckpoint subclass
(reference: fengshen/utils/universal_checkpoint.py:5-41): argparse-configured
monitor/mode/save_top_k/every_n_train_steps/save_ckpt_path/load_ckpt_path,
and the same silently-skip-missing-load behaviour (:38-41).

TPU-native: one LOGICAL checkpoint of sharded arrays (orbax) instead of
per-rank DeepSpeed engine shards — restoring onto a different mesh reshards
automatically, which obsoletes the reference's offline TP reshard tooling
(reference: fengshen/utils/llama_convert/convert_fs_llama_tp.py).
"""

from __future__ import annotations

import argparse
import os
from typing import Any, Optional

import jax
import orbax.checkpoint as ocp


#: Target size of one checkpoint data file. Orbax's own default is 2 GiB
#: and a file may overshoot its target by one chunk, which a per-file
#: size limit (`ulimit -f`, an object store's part size) refuses with
#: EFBIG in the middle of a save. Arrays larger than this are written in
#: chunks of at most this size, so no file exceeds twice it.
DATA_FILE_BYTES = 128 * 2 ** 20


class CheckpointStructureMismatch(ValueError):
    """The checkpoint's tree/shapes don't match the run's state — a
    config error (wrong model size, wrong directory), not data
    corruption. Surfaced immediately; falling back to older steps would
    fail identically N more times at multi-GB deserialization cost."""


class UniversalCheckpoint:
    @staticmethod
    def add_argparse_args(parent_parser: argparse.ArgumentParser):
        """Reference: universal_checkpoint.py:6-23 (same flag names)."""
        parser = parent_parser.add_argument_group("universal checkpoint")
        parser.add_argument("--monitor", default="step", type=str)
        parser.add_argument("--mode", default="max", type=str)
        parser.add_argument("--save_ckpt_path", default="./ckpt/", type=str)
        parser.add_argument("--load_ckpt_path", default="./ckpt/", type=str)
        parser.add_argument("--filename", default="model-{step:02d}",
                            type=str)
        parser.add_argument("--save_last", action="store_true", default=False)
        parser.add_argument("--save_top_k", default=3, type=int)
        parser.add_argument("--every_n_train_steps", default=None, type=int)
        parser.add_argument("--save_weights_only", action="store_true",
                            default=False)
        parser.add_argument("--every_n_epochs", default=None, type=int)
        parser.add_argument("--save_on_train_epoch_end", action="store_true",
                            default=None)
        parser.add_argument(
            "--async_save", action="store_true", default=False,
            help="orbax async checkpointing: serialization overlaps the "
                 "following train steps instead of blocking (flushed at "
                 "fit end and on preemption). No reference equivalent — "
                 "the reference's Lightning saves block training.")
        return parent_parser

    def __init__(self, args):
        self.args = args
        self.save_path = os.path.abspath(
            getattr(args, "save_ckpt_path", "./ckpt/"))
        self.load_path = getattr(args, "load_ckpt_path", None)
        every_n = getattr(args, "every_n_train_steps", None)
        self.every_n_train_steps = int(every_n) if every_n else 0
        self._manager: Optional[ocp.CheckpointManager] = None

    # -- manager -----------------------------------------------------------
    def _get_manager(self) -> ocp.CheckpointManager:
        if self._manager is None:
            top_k = getattr(self.args, "save_top_k", 3)
            options = ocp.CheckpointManagerOptions(
                max_to_keep=None if top_k in (-1, None) else max(top_k, 1),
                enable_async_checkpointing=bool(
                    getattr(self.args, "async_save", False)))
            self._manager = ocp.CheckpointManager(self.save_path,
                                                  options=options)
        return self._manager

    # -- save ---------------------------------------------------------------
    def save(self, state: Any, trainer: Any, sync: bool = False) -> None:
        """`sync=True` forces a flush (preemption / fit end must not
        lose the in-flight save); with --async_save, periodic saves
        return immediately and serialization overlaps training.

        Idempotent per step: a boundary save and the preemption
        autosave can both fire for the same global step in one loop
        iteration (and a rewind can replay a boundary) — orbax raises
        StepAlreadyExistsError on a re-save, so an already-committed
        step is skipped instead."""
        step = int(trainer.global_step)
        mgr = self._get_manager()
        if sync:
            mgr.wait_until_finished()  # land any in-flight async save
        if step in mgr.all_steps():
            return
        payload = {"params": state.params}
        if not getattr(self.args, "save_weights_only", False):
            payload["opt_state"] = state.opt_state
        meta = {"global_step": step,
                "consumed_samples": int(trainer.consumed_samples),
                "global_samples": int(trainer.consumed_samples)}
        mgr.save(
            step, args=ocp.args.Composite(
                state=ocp.args.PyTreeSave(
                    payload, ocdbt_target_data_file_size=DATA_FILE_BYTES),
                meta=ocp.args.JsonSave(meta)))
        if sync or not getattr(self.args, "async_save", False):
            mgr.wait_until_finished()
            # verify the commit actually landed (orbax finalizes a step
            # by atomic rename): a save that silently failed must not
            # masquerade as a restore point while older steps get
            # pruned out from under it
            mgr.reload()  # re-read the step list from disk
            if step not in mgr.all_steps():
                raise RuntimeError(
                    f"checkpoint step {step} did not commit under "
                    f"{self.save_path}")

    def wait(self) -> None:
        """Flush any in-flight async save."""
        if self._manager is not None:
            self._manager.wait_until_finished()

    # -- restore -------------------------------------------------------------
    def _restore_step(self, mgr: ocp.CheckpointManager, step: int,
                      state: Any, weights_only: bool) -> dict:
        """Restore ONE candidate step (raises on corrupt/partial data).

        What the checkpoint CONTAINS (not what this run's flags say)
        decides whether opt_state is restored: a weights-only
        checkpoint loaded into a full run must silently fall back to
        the freshly initialized optimizer state, and vice versa —
        matching the reference's silent-skip semantics (reference:
        universal_checkpoint.py:38-41)."""
        def _restore(with_opt: bool):
            payload = {"params": state.params}
            if with_opt:
                payload["opt_state"] = state.opt_state
            abstract = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=(
                    x.sharding if hasattr(x, "sharding") else None)),
                payload)
            return mgr.restore(
                step, args=ocp.args.Composite(
                    state=ocp.args.PyTreeRestore(
                        item=abstract,
                        restore_args=ocp.checkpoint_utils
                        .construct_restore_args(abstract)),
                    meta=ocp.args.JsonRestore()))

        if weights_only:
            # The eval path carries a zero-size optimizer, so the
            # payload cannot describe the on-disk opt_state; restore the
            # params SUBTREE only (no adam-moment deserialisation)
            abstract = {"params": jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=getattr(x, "sharding", None)),
                state.params)}
            try:
                return mgr.restore(
                    step, args=ocp.args.Composite(
                        state=ocp.args.PyTreeRestore(
                            item=abstract,
                            restore_args=ocp.checkpoint_utils
                            .construct_restore_args(abstract),
                            partial_restore=True),
                        meta=ocp.args.JsonRestore()))
            except ValueError as e:
                # same classification as the full path: a wrong-model
                # eval restore must fast-fail, corrupt data falls back
                if self._params_mismatch(mgr, step, state):
                    raise CheckpointStructureMismatch(str(e)) from e
                raise
        try:
            return _restore(with_opt=True)
        except ValueError as e:
            if "opt_state" in str(e):
                try:
                    return _restore(with_opt=False)
                except ValueError as e2:
                    e = e2
            # a genuine mismatch (param shapes/tree — wrong model
            # config or wrong directory) must surface, not silently
            # reset the optimizer and not trigger the corrupt-step
            # fallback; confirmed against the checkpoint METADATA,
            # because corrupt payloads also raise ValueError and those
            # must keep falling back to older steps
            if self._params_mismatch(mgr, step, state):
                raise CheckpointStructureMismatch(str(e)) from e
            raise e

    @staticmethod
    def _params_mismatch(mgr: ocp.CheckpointManager, step: int,
                         state: Any) -> bool:
        """Does the saved params tree structurally differ from the
        run's? Decided from the (cheap) checkpoint metadata; any
        failure reading it means the step is corrupt, which is NOT a
        structure mismatch."""
        def key_meta(tree):
            return {jax.tree_util.keystr(path):
                    (tuple(getattr(leaf, "shape", ())),
                     getattr(leaf, "dtype", None))
                    for path, leaf in
                    jax.tree_util.tree_flatten_with_path(tree)[0]}

        try:
            meta = mgr.item_metadata(step)
            saved = meta.get("state") if hasattr(meta, "get") else \
                getattr(meta, "state", None)
            want = key_meta(state.params)
            got = key_meta(saved["params"])
            if want.keys() != got.keys():
                return True
            for k, (shape_w, dtype_w) in want.items():
                shape_g, dtype_g = got[k]
                if shape_w != shape_g:
                    return True
                # dtype None on either side = metadata didn't record
                # it; only a confirmed disagreement is structural
                if dtype_w is not None and dtype_g is not None and \
                        jax.numpy.dtype(dtype_w) != jax.numpy.dtype(
                            dtype_g):
                    return True
            return False
        except Exception:  # noqa: BLE001 — unreadable metadata =
            # corrupt step, handled by the caller's fallback walk
            return False

    def maybe_restore(self, state: Any, trainer: Any,
                      weights_only: bool = False) -> Any:
        """Silently skip a missing load path, exactly like the reference
        (reference: universal_checkpoint.py:38-41). `weights_only` skips
        the optimizer moments entirely — the eval-only entry restores
        into a zero-size optimizer state.

        Integrity fallback (docs/fault_tolerance.md): candidate steps
        are tried newest→oldest, and a step whose restore raises
        (truncated/corrupt payload on a preempted or bit-rotted write)
        is rejected with a logged `checkpoint_restore_rejected` event
        instead of killing the run. Only when EVERY step is
        unrestorable does the error surface — silently training a 10B
        run from scratch would be worse than crashing."""
        path = self.load_path
        if not path or not os.path.isdir(path):
            return state
        path = os.path.abspath(path)
        # reuse the save-side manager when load and save point at the
        # same directory: a second CheckpointManager on one path races
        # an in-flight --async_save write
        mgr = self._get_manager() if path == self.save_path \
            else ocp.CheckpointManager(path)
        steps = sorted(mgr.all_steps(), reverse=True)
        if not steps:
            return state
        log = getattr(trainer, "_log", None) or (lambda entry: None)
        restored, errors = None, []
        for step in steps:
            try:
                restored = self._restore_step(mgr, step, state,
                                              weights_only)
                break
            except CheckpointStructureMismatch:
                raise  # config error, identical on every step
            except Exception as e:  # noqa: BLE001 — corrupt/partial
                # step: log, fall back to the previous one
                errors.append((step, e))
                log({"event": "checkpoint_restore_rejected",
                     "ckpt_step": int(step),
                     "error": f"{type(e).__name__}: {str(e)[:200]}"})
        if restored is None:
            detail = "; ".join(
                f"step {s}: {type(e).__name__}: {str(e)[:120]}"
                for s, e in errors)
            raise RuntimeError(
                f"no restorable checkpoint under {path} ({detail})")
        if errors and path == self.save_path:
            # we OWN this directory: drop the unrestorable steps so the
            # run can re-save past them — left in place, a corrupt
            # newest step would shadow every later boundary save (the
            # idempotent-save guard skips committed steps) and re-lose
            # the same window on every future restore
            for bad_step, _ in errors:
                try:
                    mgr.delete(bad_step)
                    log({"event": "checkpoint_rejected_deleted",
                         "ckpt_step": int(bad_step)})
                except Exception as e:  # noqa: BLE001 — best-effort
                    # cleanup; the restore itself already succeeded
                    log({"event": "checkpoint_delete_failed",
                         "ckpt_step": int(bad_step),
                         "error": str(e)[:200]})
        meta = restored["meta"]
        # restore loop counters the way the reference's on_load_checkpoint
        # does (reference: examples/pretrain_erlangshen_bert/
        # pretrain_erlangshen.py:192-197)
        trainer.global_step = int(meta["global_step"])
        trainer.consumed_samples = int(meta["consumed_samples"])
        new = state.replace(params=restored["state"]["params"],
                            step=jax.numpy.asarray(meta["global_step"],
                                                   jax.numpy.int32))
        if "opt_state" in restored["state"]:
            new = new.replace(opt_state=restored["state"]["opt_state"])
        return new

    # -- trainer hooks --------------------------------------------------------
    def save_due(self, trainer: Any) -> bool:
        """Whether this execution crossed an every-n boundary (the
        Trainer also asks, to name the span `train/checkpoint` only
        where a save happens)."""
        if not self.every_n_train_steps:
            return False
        # boundary-CROSSING, not equality: under --steps_per_execution K
        # global_step advances K at a time and can jump over the exact
        # multiple (trainer sets prev_global_step per execution)
        prev = int(getattr(trainer, "prev_global_step",
                           trainer.global_step - 1))
        return (trainer.global_step // self.every_n_train_steps) > \
            (prev // self.every_n_train_steps)

    def on_train_step_end(self, trainer: Any, state: Any) -> None:
        if self.save_due(trainer):
            self.save(state, trainer)

    def on_fit_end(self, trainer: Any, state: Any) -> None:
        if getattr(self.args, "save_last", False) or \
                not self.every_n_train_steps:
            self.save(state, trainer, sync=True)
        else:
            self.wait()
