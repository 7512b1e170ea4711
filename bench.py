"""Benchmark: LLaMA causal-LM training throughput + MFU on the local chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", ...}. It runs on the TPU or
not at all: without an accelerator it exits non-zero (a caller may ask
for the CPU by name, `JAX_PLATFORMS=cpu`, to smoke the harness at tiny
shapes), and it never re-runs itself on another backend.
The reference publishes no throughput numbers (BASELINE.md), so
`vs_baseline` is measured-MFU / 0.40. ROADMAP.md S1 replaces this file
with the cell-based harness.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


def main() -> None:
    import os

    mode = os.environ.get("BENCH_CONFIG", "default")
    if mode == "large":
        return _run_large()
    if mode == "sharded":
        return _run_sharded()
    if mode == "decode":
        return _run_decode()

    batches = os.environ.get("BENCH_BATCH")
    if batches:  # pinned: run in-process, let failures propagate
        _require_accelerator()
        return _run(int(batches))
    # OOM ladder, one fresh process per rung: the tuned batch first,
    # then safer sizes — an OOM on a differently-provisioned chip must
    # degrade the number, not zero it. On chips too small for the
    # materialized-logits path, the chunked fused-CE config is the
    # honest best config.
    fce_env = os.environ.get("BENCH_FUSED_CE")
    if fce_env or os.environ.get("BENCH_INT8_LMHEAD", "0") != "0" \
            or os.environ.get("BENCH_LORA", "0") != "0":
        # a lever row (explicit fused-CE chunking, int8 head, or LoRA)
        # must not silently mix IN the other lever on a lower rung — the
        # row would be incomparable to its baseline. Pure batch ladder.
        rungs = [{"BENCH_BATCH": b, "BENCH_FUSED_CE": fce_env or 0}
                 for b in (28, 24, 16, 8)]
    else:
        rungs = [{"BENCH_BATCH": 28, "BENCH_FUSED_CE": 0},
                 {"BENCH_BATCH": 24, "BENCH_FUSED_CE": 0},
                 {"BENCH_BATCH": 28, "BENCH_FUSED_CE": 8},
                 {"BENCH_BATCH": 16, "BENCH_FUSED_CE": 0},
                 {"BENCH_BATCH": 16, "BENCH_FUSED_CE": 8},
                 {"BENCH_BATCH": 8, "BENCH_FUSED_CE": 0}]
    _ladder_of_rungs(rungs, "default")


def _require_accelerator() -> None:
    """Top of every LEAF bench path (one that computes): a row is a
    statement about the TPU, so without one the run fails — jax falls
    to the CPU on its own when it finds no accelerator, and nothing
    here re-runs on another backend. The one exception is a caller who
    asked for the CPU by name (`JAX_PLATFORMS=cpu`, the harness smoke
    tests at tiny shapes). Ladder parents never call this: a parent
    that touched the backend would hold the chip its child rungs
    need."""
    import os
    import sys

    backend = jax.default_backend()
    if backend == "tpu":
        return
    if backend == "cpu" and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    sys.exit(f"bench: the default backend is {backend!r}, not 'tpu' — "
             "no accelerator, no row (set JAX_PLATFORMS=cpu to smoke "
             "the harness at tiny shapes)")


# A compile-time OOM carries the allocator's "Ran out of memory" text;
# a RUNTIME OOM surfaces as a bare "RESOURCE_EXHAUSTED: TPU backend
# error (ResourceExhausted)."
_OOM_SIGNATURES = ("Ran out of memory", "RESOURCE_EXHAUSTED",
                   "ResourceExhausted")


def _is_oom_text(text: str) -> bool:
    return any(sig in text for sig in _OOM_SIGNATURES)


def _spawn_rung(env_overrides: dict) -> tuple[int, str]:
    """One pinned bench attempt in a FRESH interpreter.

    Ladder rungs must not share a process: a rung that OOMs can leave
    device buffers behind that OOM the next rung's state init at a size
    that fits a clean chip, and a fresh process is the only reliable
    release. stdout (the one JSON metric line) is inherited; stderr is
    captured so the caller can tell OOM (ladder down) from a real
    failure (propagate), then echoed.
    """
    import os
    import subprocess
    import sys

    env = {**os.environ,
           **{k: str(v) for k, v in env_overrides.items()}}
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stderr=subprocess.PIPE, text=True)
    sys.stderr.write(proc.stderr or "")
    sys.stderr.flush()
    return proc.returncode, proc.stderr or ""


def _ladder_of_rungs(rungs: list, label: str,
                     spawn=_spawn_rung) -> None:
    """Run pinned-rung subprocesses until one succeeds.

    OOM → step down; anything else → propagate the child's rc."""
    import sys

    for env_overrides in rungs:
        rc, err = spawn(env_overrides)
        if rc == 0:
            print(f"bench[{label}]: rung {env_overrides} succeeded",
                  file=sys.stderr, flush=True)
            return
        if not _is_oom_text(err):
            print(f"bench[{label}]: non-OOM failure (rc={rc}), not "
                  "laddering", file=sys.stderr, flush=True)
            sys.exit(rc)
        print(f"bench[{label}]: OOM at {env_overrides}, stepping down",
              file=sys.stderr, flush=True)
    raise RuntimeError(f"bench[{label}]: every ladder rung OOM")


def _emit(row: dict) -> None:
    """The one JSON metric line, written through the unified jsonl
    sink (docs/observability.md). Every row names the device that
    produced it."""
    import sys

    from fengshen_tpu.observability import JsonlSink

    device = jax.devices()[0]
    row.update(platform=device.platform, device_kind=device.device_kind,
               device_count=len(jax.devices()))
    JsonlSink(stream=sys.stdout, only_process_zero=False)(row)


def _offload_request(default: str = "none") -> str:
    """BENCH_OFFLOAD → an `--offload` ladder request (docs/offload.md).
    Legacy truthy ints (the pre-probe boolean contract) map to "opt",
    "0"/"" keep the mode's default, and anything unrecognized warns and
    falls back to the default — the Trainer's argparse choices would
    otherwise SystemExit the whole bench run."""
    import os
    import sys

    raw = (os.environ.get("BENCH_OFFLOAD", "") or "").strip()
    if raw in ("", "0"):
        return default
    if raw in ("auto", "none", "opt", "opt_master", "stream"):
        return raw
    try:
        return "opt" if int(raw) else default
    except ValueError:
        print(f"bench: unrecognized BENCH_OFFLOAD={raw!r} (expected "
              "0|1|auto|none|opt|opt_master|stream); using "
              f"{default!r}", file=sys.stderr, flush=True)
        return default


def _trainer_bench(config, metric_name: str, per_chip: int,
                   seq: int, flops_attn_term: float,
                   extra_args: list, steps: int = 15) -> bool:
    """One Trainer-driven bench attempt in a FRESH run dir (Trainer
    appends to metrics.jsonl, so reusing a dir would mix runs/rungs).
    Returns True on success; raises on non-OOM errors; returns False on
    compile/runtime OOM so the caller's ladder can step down.

    Logging is windowed (every 3 steps), not per-step: materializing
    metrics each step blocks dispatch on the host pulling device
    values. With a 3-step window, steady-state steps pipeline
    back-to-back and only the window edge syncs."""
    import argparse
    import os
    import sys
    import tempfile

    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaForCausalLM
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.parallel import set_mesh
    from fengshen_tpu.trainer import Trainer, add_trainer_args
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.observability import peak_flops_per_chip

    n_dev = len(jax.devices())
    # BENCH_STEPS_PER_EXEC=K: scan K optimizer steps inside one jitted
    # dispatch (Trainer --steps_per_execution) — A/B row for the
    # per-dispatch host latency
    spe = os.environ.get("BENCH_STEPS_PER_EXEC")
    if spe:
        extra_args = extra_args + ["--steps_per_execution", spe]
    root = tempfile.mkdtemp(prefix="fstpu_bench_")
    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    args = parser.parse_args([
        "--max_steps", str(steps),
        "--train_batchsize", str(per_chip * n_dev),
        "--log_every_n_steps", "3", "--warmup_steps", "1",
        "--default_root_dir", root] + extra_args)
    rng = np.random.RandomState(0)
    rows = [{"input_ids":
             rng.randint(0, config.vocab_size - 1, seq).tolist()}
            for _ in range(per_chip * n_dev * (steps + 1))]

    class DS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    trainer = None
    try:
        trainer = Trainer(args)
        module = CausalLMModule(args, LlamaForCausalLM(config), config)
        dm = UniversalDataModule(args=args, datasets={"train": DS()})
        state = trainer.fit(module, dm)
        jax.block_until_ready(state.params)
    except Exception as e:  # noqa: BLE001 — ladder on OOM only
        set_mesh(None)
        if not _is_oom_text(str(e)):
            raise
        # the fixed "(ResourceExhausted)" marker guarantees a parent
        # _ladder_of_rungs classifies this rung as OOM (step down) no
        # matter how the backend phrased the message; the excerpt is
        # for the human log
        print(f"bench[{metric_name}]: OOM (ResourceExhausted) at "
              f"per_chip={per_chip}, stepping down ({str(e)[:160]})",
              file=sys.stderr, flush=True)
        return False
    set_mesh(None)
    metrics = [json.loads(line)
               for line in open(f"{root}/metrics.jsonl")]
    # steady-state: drop the first two 3-step windows (compile +
    # settling); average the remaining windowed readings
    tps_list = [m["tokens_per_sec"] for m in metrics
                if "tokens_per_sec" in m][2:]
    tps = float(np.mean(tps_list)) if tps_list else 0.0
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(state.params))
    flops_per_token = 6.0 * n_params + flops_attn_term
    # same denominator as the decode and serving rows
    # (docs/observability.md)
    peak = peak_flops_per_chip(jax.devices()[0].device_kind)
    mfu = tps * flops_per_token / (peak * n_dev)
    row = {
        "metric": metric_name,
        "value": round(tps / n_dev, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "mfu": float(f"{mfu:.4g}"),
    }
    # rows driven at an offload level carry the RESOLVED placement
    # (docs/offload.md) so benchdiff never compares across placements
    # — "auto" resolving to none keeps the row placement-free and
    # directly comparable to --offload=none rows
    policy = getattr(trainer, "_offload_policy", None)
    if policy is not None and policy.level != "none":
        row["offload"] = policy.level
        row["memory_kind"] = policy.opt_state_kind
    _emit(row)
    return True


def _run_large() -> None:
    """13B-SHAPED config: the real LLaMA-13B layer
    shape — hidden 5120, intermediate 13824, 40 query heads at head_dim
    128 with GQA (8 kv heads), 32k vocab, seq 2048 — at the deepest
    layer count that fits one chip, driven through the ACTUAL Trainer so
    the production levers (bf16 params, --offload_optimizer host-resident
    adam, remat) are the ones measured. BENCH_LAYERS + BENCH_BATCH
    (both) pin one ladder rung."""
    import os
    import sys

    from fengshen_tpu.models.llama import LlamaConfig

    seq = int(os.environ.get("BENCH_SEQ", "2048"))
    layers_env = os.environ.get("BENCH_LAYERS")
    batch_env = os.environ.get("BENCH_BATCH")
    if bool(layers_env) != bool(batch_env):
        print("bench-large: set BOTH BENCH_LAYERS and BENCH_BATCH to pin "
              "a rung; ignoring the lone override and running the ladder",
              file=sys.stderr, flush=True)
    if not (layers_env and batch_env):
        # each rung in a fresh process (see _spawn_rung). Lower rungs
        # mix in chunked fused CE (~1-2 GB of fp32 logits freed at seq
        # 2048) — on a small chip that rescues a deeper rung, which is
        # worth more than a materialized shallow one.
        rungs = [(8, 4, 0), (8, 4, 8), (8, 2, 8), (6, 2, 8),
                 (4, 1, 8), (2, 1, 8)]
        if os.environ.get("BENCH_FUSED_CE"):  # explicit: honor it
            fce = os.environ["BENCH_FUSED_CE"]
            rungs = list(dict.fromkeys(
                (l, b, fce) for l, b, _ in rungs))
        return _ladder_of_rungs(
            [{"BENCH_CONFIG": "large", "BENCH_LAYERS": l,
              "BENCH_BATCH": b, "BENCH_FUSED_CE": f}
             for l, b, f in rungs],
            "large")
    layers, per_chip = int(layers_env), int(batch_env)
    _require_accelerator()
    # env dim overrides exist ONLY for CPU smoking (a 5120-dim
    # compile takes minutes on the CPU backend); hardware runs use
    # the 13B defaults
    config = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_VOCAB", "32000")),
        hidden_size=int(os.environ.get("BENCH_HIDDEN", "5120")),
        intermediate_size=int(os.environ.get("BENCH_INTER", "13824")),
        num_hidden_layers=layers,
        num_attention_heads=int(os.environ.get("BENCH_HEADS", "40")),
        num_key_value_heads=int(os.environ.get("BENCH_KV", "8")),
        max_position_embeddings=seq, dtype="bfloat16",
        param_dtype="bfloat16", attention_impl="flash",
        scan_layers=True, gradient_checkpointing=True,
        remat_policy=os.environ.get("BENCH_REMAT", "dots_no_batch"),
        fused_ce_chunks=int(os.environ.get("BENCH_FUSED_CE", "0")))
    if not _trainer_bench(
            config, f"llama13bshape_l{layers}_train_tokens_per_sec"
            "_per_chip", per_chip, seq,
            flops_attn_term=12.0 * config.num_hidden_layers *
            config.hidden_size * seq,
            # capability-probed placement (docs/offload.md): auto picks
            # the shallowest level whose footprint fits the reported
            # device budget — the pre-probe hard-coded
            # --offload_optimizer aborted this whole mode on backends
            # without pinned_host (the seed-failing bench smoke tests)
            extra_args=["--offload", _offload_request("auto")]):
        raise RuntimeError(
            f"bench-large: rung l{layers} b{per_chip} OOM")


def _run_sharded() -> None:
    """BENCH_CONFIG=sharded: the default 300M shape driven through the
    Trainer's fsdp+tensor-sharded step (partition rules + sharding
    constraints + donation — the code path a pod runs). Axis sizes are
    env-overridable (BENCH_FSDP / BENCH_TP) and default to fsdp=n_dev on
    multi-chip hosts so the mode actually shards when it can."""
    import os

    from fengshen_tpu.models.llama import LlamaConfig

    _require_accelerator()
    n_dev = len(jax.devices())
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    per_chip = int(os.environ.get("BENCH_BATCH", "16"))
    fsdp = int(os.environ.get("BENCH_FSDP", str(n_dev)))
    tp = int(os.environ.get("BENCH_TP", "1"))
    config = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_VOCAB", "32000")),
        hidden_size=int(os.environ.get("BENCH_HIDDEN", "1024")),
        intermediate_size=int(os.environ.get("BENCH_INTER", "2816")),
        num_hidden_layers=int(os.environ.get("BENCH_LAYERS", "16")),
        num_attention_heads=int(os.environ.get("BENCH_HEADS", "8")),
        max_position_embeddings=seq, dtype="bfloat16",
        attention_impl=os.environ.get("BENCH_ATTN", "flash"),
        scan_layers=True, gradient_checkpointing=True,
        remat_policy=os.environ.get("BENCH_REMAT", "dots_no_batch"))
    extra = ["--fsdp_parallel_size", str(fsdp),
             "--tensor_model_parallel_size", str(tp)]
    name = "llama300m_sharded_step_tokens_per_sec_per_chip"
    offload = _offload_request()
    if offload not in ("none", "auto"):
        # headroom lever row (docs/performance.md): host-resident adam
        # moments (and master params at opt_master) between steps —
        # measures the offloaded-update cost on the 300M shape. The
        # memory kind is probe-resolved (docs/offload.md), so this row
        # runs on pinned_host-less backends too.
        extra += ["--offload", offload]
        name = "llama300m_offload_update_tokens_per_sec_per_chip"
    elif offload == "auto":
        # auto at the 300M shape must resolve to "none" whenever the
        # state fits (the <5% tokens/s acceptance bar vs --offload=none
        # holds by construction: same program); keep the base metric
        # name and let the emitted row carry any resolved placement
        extra += ["--offload", "auto"]
    else:
        # the baseline rung is PINNED device-resident: without this the
        # Trainer's --offload default ("auto") could quietly offload on
        # a memory-pressured chip and the base metric would stop being
        # comparable to its published baseline
        extra += ["--offload", "none"]
    if not _trainer_bench(
            config, name, per_chip, seq,
            flops_attn_term=12.0 * config.num_hidden_layers *
            config.hidden_size * seq, extra_args=extra):
        raise RuntimeError("bench-sharded: OOM")


def _run_decode() -> None:
    """BENCH_CONFIG=decode: jitted KV-cached generation throughput
    (reference serving analog: fengshen/examples/ziya_inference —
    greedy/sampled causal decode — and the qa_t5/summary beam decodes).

    Default row: greedy decode on the 300M-shape LLaMA (bf16, flash
    prefill, scan KV cache); BENCH_INT8_LMHEAD=1 measures the int8
    serving head. BENCH_DECODE=beam instead measures num_beams=4
    seq2seq beam search on a Randeng-T5-ish encoder-decoder. Metric is
    GENERATED tokens/sec/chip (prompt prefill included in the time).
    CPU-smokable with the usual BENCH_* shrinks + BENCH_NEW_TOKENS.
    """
    import os

    from jax.sharding import NamedSharding, PartitionSpec as P

    from fengshen_tpu.parallel import MeshConfig, make_mesh, set_mesh

    _require_accelerator()
    n_dev = len(jax.devices())
    batch = int(os.environ.get("BENCH_BATCH", "8")) * n_dev
    prompt = int(os.environ.get("BENCH_PROMPT", "128"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "512"))
    runs = max(1, int(os.environ.get("BENCH_DECODE_RUNS", "3")))
    rng = np.random.RandomState(0)
    # shard the batch over all chips (the serving layout); params stay
    # replicated — without this a multi-chip host would decode on one
    # device and the /n_dev per-chip number would lie
    mesh = make_mesh(MeshConfig(data=n_dev, fsdp=1, sequence=1, tensor=1))
    set_mesh(mesh)
    batch_sh = NamedSharding(mesh, P(("data",)))

    if os.environ.get("BENCH_DECODE", "greedy") == "beam":
        from fengshen_tpu.models.t5 import T5Config, T5ForConditionalGeneration
        from fengshen_tpu.utils.generate import seq2seq_generate

        config = T5Config(
            vocab_size=int(os.environ.get("BENCH_VOCAB", "32128")),
            d_model=int(os.environ.get("BENCH_HIDDEN", "768")),
            d_kv=64,
            d_ff=int(os.environ.get("BENCH_INTER", "2048")),
            num_layers=int(os.environ.get("BENCH_LAYERS", "12")),
            num_heads=int(os.environ.get("BENCH_HEADS", "12")),
            dtype="bfloat16", tie_word_embeddings=False,
            # cache must out-size max_new_tokens or seq2seq_generate
            # silently falls back to the uncached O(L^2) re-run path —
            # the row must measure the KV-cached serving loop
            decode_cache_length=new_tokens + prompt + 8)
        model = T5ForConditionalGeneration(config)
        src = jax.device_put(
            jnp.asarray(rng.randint(1, config.vocab_size - 1,
                                    (batch, prompt)), jnp.int32),
            batch_sh)
        params = jax.jit(lambda r: model.init(
            r, jnp.zeros((1, 8), jnp.int32),
            jnp.zeros((1, 4), jnp.int32))["params"])(jax.random.PRNGKey(0))

        @jax.jit
        def _gen(params, src):
            return seq2seq_generate(
                model, params, src, max_new_tokens=new_tokens,
                num_beams=4, eos_token_id=None, pad_token_id=0,
                decoder_start_token_id=0)

        def decode():
            return _gen(params, src)
        metric = "t5beam4_decode_tokens_per_sec_per_chip"
    else:
        from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from fengshen_tpu.utils.generate import (generate,
                                                 speculative_generate)

        config = LlamaConfig(
            vocab_size=int(os.environ.get("BENCH_VOCAB", "32000")),
            hidden_size=int(os.environ.get("BENCH_HIDDEN", "1024")),
            intermediate_size=int(os.environ.get("BENCH_INTER", "2816")),
            num_hidden_layers=int(os.environ.get("BENCH_LAYERS", "16")),
            num_attention_heads=int(os.environ.get("BENCH_HEADS", "8")),
            max_position_embeddings=prompt + new_tokens,
            dtype="bfloat16", scan_layers=True,
            attention_impl=os.environ.get("BENCH_ATTN", "flash"),
            int8_lm_head=bool(int(os.environ.get("BENCH_INT8_LMHEAD",
                                                 "0"))))
        model = LlamaForCausalLM(config)
        ids = jax.device_put(
            jnp.asarray(rng.randint(1, config.vocab_size - 1,
                                    (batch, prompt)), jnp.int32),
            batch_sh)
        params = jax.jit(lambda r: model.init(
            r, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(0))

        if os.environ.get("BENCH_DECODE") == "lookup":
            # draft-free prompt-lookup speculation (token-exact greedy;
            # wins scale with output repetitiveness)
            from fengshen_tpu.utils.generate import prompt_lookup_generate
            import dataclasses
            gamma = int(os.environ.get("BENCH_SPEC_GAMMA", "4"))
            config = dataclasses.replace(
                config,
                max_position_embeddings=prompt + new_tokens + gamma)
            model = LlamaForCausalLM(config)

            @jax.jit
            def _gen(params, ids):
                return prompt_lookup_generate(
                    model, params, ids, max_new_tokens=new_tokens,
                    gamma=gamma,
                    ngram=int(os.environ.get("BENCH_LOOKUP_NGRAM", "2")),
                    eos_token_id=None, pad_token_id=0)

            def decode():
                return _gen(params, ids)
            metric = ("llama300m_int8_lookup_decode_tokens_per_sec_per_chip"
                      if config.int8_lm_head else
                      "llama300m_lookup_decode_tokens_per_sec_per_chip")
        elif os.environ.get("BENCH_DECODE") == "spec":
            # speculative decoding: token-exact greedy via a shallow
            # draft of the same width (BENCH_DRAFT_LAYERS deep). The
            # row measures COMMITTED tokens/sec — acceptance rate on
            # random-init weights is pessimal, so this row is a lower
            # bound on the mechanism's overhead, not a realistic
            # speedup (that needs a trained draft/target pair)
            import dataclasses
            gamma = int(os.environ.get("BENCH_SPEC_GAMMA", "4"))
            # the speculation window needs gamma extra cache slots
            # (speculative_generate refuses loudly without them);
            # params are RoPE so the rebuilt model reuses them as-is
            config = dataclasses.replace(
                config,
                max_position_embeddings=prompt + new_tokens + gamma)
            model = LlamaForCausalLM(config)
            draft_cfg = dataclasses.replace(
                config, num_hidden_layers=int(
                    os.environ.get("BENCH_DRAFT_LAYERS", "2")))
            draft = LlamaForCausalLM(draft_cfg)
            draft_params = jax.jit(lambda r: draft.init(
                r, jnp.zeros((1, 8), jnp.int32))["params"])(
                jax.random.PRNGKey(1))

            @jax.jit
            def _gen(params, draft_params, ids):
                return speculative_generate(
                    model, params, draft, draft_params, ids,
                    max_new_tokens=new_tokens, gamma=gamma,
                    eos_token_id=None, pad_token_id=0)

            def decode():
                return _gen(params, draft_params, ids)
            # the int8 lever composes with spec decode (the verify
            # forward just uses the int8 head) — keep the rows apart
            metric = ("llama300m_int8_spec_decode_tokens_per_sec_per_chip"
                      if config.int8_lm_head else
                      "llama300m_spec_decode_tokens_per_sec_per_chip")
        else:
            @jax.jit
            def _gen(params, ids):
                return generate(model, params, ids,
                                max_new_tokens=new_tokens,
                                eos_token_id=None, pad_token_id=0)

            def decode():
                return _gen(params, ids)
            metric = ("llama300m_int8_decode_tokens_per_sec_per_chip"
                      if config.int8_lm_head else
                      "llama300m_decode_tokens_per_sec_per_chip")

    jax.block_until_ready(decode())  # compile
    t0 = time.perf_counter()
    for _ in range(runs):
        out = decode()
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    set_mesh(None)
    tps = batch * new_tokens * runs / dt
    # no MFU target for decode (bandwidth-bound); vs_baseline is
    # tokens/sec/chip relative to the training north-star scale (40%
    # MFU train ≈ 43k tok/s at 300M) — a rough single-number context
    row = {
        "metric": metric,
        "value": round(tps / n_dev, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tps / n_dev / 43000.0, 4),
    }
    # utilization column (forward-only FLOPs — decode does no backward);
    # the low absolute value IS the point: it quantifies how far
    # bandwidth-bound batch-1 decode sits from the chip's matmul peak
    from fengshen_tpu.observability import (estimate_flops_per_token,
                                            peak_flops_per_chip)
    f_tok = estimate_flops_per_token(config, include_backward=False)
    if f_tok:
        peak = peak_flops_per_chip(jax.devices()[0].device_kind)
        row["mfu"] = float(f"{tps * f_tok / (peak * n_dev):.4g}")
    _emit(row)


def _run(per_chip_batch: int) -> None:
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.parallel import MeshConfig, make_mesh, set_mesh
    from fengshen_tpu.parallel.cross_entropy import stable_cross_entropy
    from fengshen_tpu.observability import peak_flops_per_chip

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshConfig(data=n_dev, fsdp=1, sequence=1, tensor=1))
    set_mesh(mesh)

    # ~300M-param LLaMA slice; bf16 compute, fp32 params/adam.
    # Env overrides make the MFU sweep a flag flip:
    # BENCH_BATCH / BENCH_SEQ / BENCH_REMAT / BENCH_ATTN / BENCH_HEADS.
    # Defaults: heads 8 → head_dim 128 (the real LLaMA-13B head_dim,
    # and the Pallas flash kernel's tile-eligibility bound), batch 28,
    # dots_no_batch remat.
    import os
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    config = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_VOCAB", "32000")),
        hidden_size=int(os.environ.get("BENCH_HIDDEN", "1024")),
        intermediate_size=int(os.environ.get("BENCH_INTER", "2816")),
        num_hidden_layers=int(os.environ.get("BENCH_LAYERS", "16")),
        num_attention_heads=int(os.environ.get("BENCH_HEADS", "8")),
        max_position_embeddings=seq, dtype="bfloat16",
        attention_impl=os.environ.get("BENCH_ATTN", "flash"),
        scan_layers=True, gradient_checkpointing=True,
        remat_policy=os.environ.get("BENCH_REMAT", "dots_no_batch"),
        # headroom lever rows (docs/performance.md): BENCH_INT8_LMHEAD=1
        int8_lm_head=bool(int(os.environ.get("BENCH_INT8_LMHEAD", "0"))),
        # BENCH_FUSED_CE=<chunks>: chunked fused LM-head+CE frees the
        # ~3.7GB fp32 logits tensor → try larger BENCH_BATCH with it
        fused_ce_chunks=int(os.environ.get("BENCH_FUSED_CE", "0")))
    model = LlamaForCausalLM(config)
    batch = per_chip_batch * n_dev

    rng = jax.random.PRNGKey(0)
    params = jax.jit(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))["params"])(rng)
    lora_rank = int(os.environ.get("BENCH_LORA", "0"))
    if lora_rank:
        # LoRA lever row: frozen base + rank-r adapters on the
        # attention projections — measures the stop_gradient DCE win
        # (no base weight grads, adam only on adapters) vs the full-
        # finetune row at the same shape
        from functools import partial

        from fengshen_tpu.ops.lora import (apply_lora, init_lora,
                                           lora_param_labels)
        params = {"base": params,
                  "lora": init_lora(params, jax.random.PRNGKey(1),
                                    lora_rank,
                                    r"(q_proj|k_proj|v_proj|o_proj)")}
        tx = optax.multi_transform(
            {"lora": optax.adamw(1e-4, weight_decay=0.1),
             "freeze": optax.set_to_zero()},
            partial(lora_param_labels, train_regex=None))
    else:
        tx = optax.adamw(1e-4, weight_decay=0.1)
    opt_state = jax.jit(tx.init)(params)

    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, config.vocab_size - 1, (batch, seq)), jnp.int32)

    if config.fused_ce_chunks:
        from fengshen_tpu.ops.fused_ce import causal_fused_loss

        def loss_fn(p, ids):
            hidden = model.apply({"params": p}, ids, return_hidden=True)
            kernel = p["lm_head"]["kernel"].astype(hidden.dtype)
            loss, _, _ = causal_fused_loss(
                hidden, kernel, ids, num_chunks=config.fused_ce_chunks)
            return loss
    else:
        def loss_fn(p, ids):
            logits = model.apply({"params": p}, ids)
            loss, _ = stable_cross_entropy(logits[:, :-1], ids[:, 1:])
            return loss

    if lora_rank:
        inner_loss = loss_fn

        def loss_fn(p, ids):  # noqa: F811 — merged-view wrapper
            merged = apply_lora(jax.lax.stop_gradient(p["base"]),
                                p["lora"])
            return inner_loss(merged, ids)

    @jax.jit
    def step(p, o, ids):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids)
        updates, o = tx.update(grads, o, p)
        p = optax.apply_updates(p, updates)
        return p, o, loss

    # warmup / compile
    params, opt_state, loss = step(params, opt_state, ids)
    jax.block_until_ready(loss)

    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, loss = step(params, opt_state, ids)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    tokens = batch * seq * n_steps
    tps = tokens / dt
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(params))
    flops_per_token = 6.0 * n_params + 12.0 * config.num_hidden_layers * \
        config.hidden_size * seq  # attention term
    peak = peak_flops_per_chip(jax.devices()[0].device_kind)
    mfu = tps * flops_per_token / (peak * n_dev)

    _emit({
        # lever rows must be distinguishable in the BENCH file (the
        # int8 head changes numerics; LoRA changes what trains)
        "metric": ("llama300m_lora_train_tokens_per_sec_per_chip"
                   if lora_rank else
                   "llama300m_int8_train_tokens_per_sec_per_chip"
                   if config.int8_lm_head else
                   "llama300m_train_tokens_per_sec_per_chip"),
        "value": round(tps / n_dev, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "mfu": float(f"{mfu:.4g}"),
    })


if __name__ == "__main__":
    main()
