"""On-chip smoke: the two main paths, once, at Ziya-LLaMA-13B width.

    python chip_smoke.py

Runs on a TPU or not at all (exit code != 0, no result line). One
process holds the chip for all three phases:

1. kernels — every op in `ops.pallas.dispatch_table()`: the registered
   Mosaic implementation compiled for real (never interpreted) at the
   shapes the next two phases use (the folded decode entry and the
   chunked gated delta rule, which no LLaMA shape reaches, at
   Qwen3-Next's; the experts' grouped matmul at Keye's), against its
   registered xla twin;
2. train — `Trainer(args).fit(CausalLMModule, UniversalDataModule)` as
   every example builds them: a few optimizer steps at seq 2048, mesh
   over all visible devices, one `UniversalCheckpoint` save and restore;
3. serve — the route `python -m fengshen_tpu.api.main` takes:
   text-generation `Pipeline` → `create_continuous_engine` → warmup
   thread → stdlib HTTP server, answering concurrent requests on the
   default engine and on the paged int8 pool.

The model is `workspace/ziya-llama-13b/config.json` with no width cut.
Depth is the only cut; it is sized to the device memory jax reports, by
arithmetic this script prints. Weights are random from a seed. Nothing
here is a benchmark: rates are printed as evidence the path ran, and no
number from this script is a result.

Any assertion or exception ends the run with a traceback; no phase's
failure is caught. The last line of stdout on success is one JSON
object, `{"ok": true, "device": {...}}`.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import threading
import time
import types
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_PATH = os.path.join(HERE, "workspace", "ziya-llama-13b",
                           "config.json")
#: run directory (metrics.jsonl, checkpoint); git-ignored, removed on
#: success
RUN_DIR = os.path.join(HERE, "chip_smoke_run")

SEED = 20260926
SEQ = 2048
TRAIN_STEPS = 8
#: tokens per block of the paged pool the serve phase asks for — the
#: smallest size the Mosaic decode kernel takes
PAGED_BLOCK = 128

#: bytes a trained parameter holds in this Trainer: fp32 master copy
#: (4) + fp32 Adam m and v (8) + the fp32 gradient of the step (4)
TRAIN_BYTES_PER_PARAM = 16
#: bytes a served parameter holds: bf16
SERVE_BYTES_PER_PARAM = 2
#: share of device memory the sized state may fill; the rest is for
#: activations, logits, FSDP gather buffers and (serving) the KV pool
TRAIN_STATE_SHARE = 0.70
SERVE_WEIGHT_SHARE = 0.40

#: normalized error allowed between a Mosaic kernel and its xla twin on
#: bf16 operands: max|a - b| / max|b| <= 8 bf16 roundoffs (2**-8 each),
#: the slack two chained bf16 matmuls around an f32 softmax need
BF16_TOL = 8 * 2.0 ** -8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- compile accounting ---------------------------------------------------

class CompileMeter:
    """Seconds spent in backend compilation (or in loading a compiled
    program from the persistent cache) and the cache's hit/miss counts,
    from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return self.seconds, self.hits, self.misses

    def since(self, mark: tuple) -> str:
        return (f"compile {self.seconds - mark[0]:.1f}s, cache hits "
                f"{self.hits - mark[1]}, misses {self.misses - mark[2]}")


# -- model sizing ---------------------------------------------------------

def load_config(**overrides):
    from fengshen_tpu.models.llama import LlamaConfig
    return dataclasses.replace(LlamaConfig.from_pretrained(CONFIG_PATH),
                               **overrides)


def param_counts(cfg) -> tuple:
    """(parameters per decoder layer, parameters outside the layers)."""
    h, inter = cfg.hidden_size, cfg.intermediate_size
    kv_dim = cfg.num_key_value_heads * cfg.head_dim
    per_layer = 2 * h * h + 2 * h * kv_dim + 3 * h * inter + 2 * h
    outside = 2 * cfg.vocab_size * h + h
    return per_layer, outside


def depth_for(cfg, total_bytes: int, share: float,
              bytes_per_param: int, what: str) -> int:
    per_layer, outside = param_counts(cfg)
    room = share * total_bytes / bytes_per_param
    depth = int(max(1, min(cfg.num_hidden_layers,
                           (room - outside) // per_layer)))
    log(f"{what} depth: {share:.2f} x {total_bytes / 1e9:.2f} GB / "
        f"{bytes_per_param} B/param = room for {room / 1e6:.0f} M "
        f"params; {outside / 1e6:.0f} M outside the layers + "
        f"{per_layer / 1e6:.0f} M per layer -> {depth} of "
        f"{cfg.num_hidden_layers} layers "
        f"({(outside + depth * per_layer) / 1e6:.0f} M params)")
    return depth


# -- phase 1: kernels -----------------------------------------------------

def _norm_err(got, want) -> float:
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert bool(jnp.isfinite(got).all()), "non-finite kernel output"
    return float(jnp.abs(got - want).max() /
                 jnp.maximum(jnp.abs(want).max(), 1e-30))


def _check(rows: list, op: str, case: str, got, want, tol=BF16_TOL):
    import jax
    errs = [_norm_err(g, w) for g, w in
            zip(jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want))]
    worst = max(errs)
    rows.append((op, case, worst))
    log(f"  {op:<24} {case:<34} max err {worst:.2e} (tol {tol:.2e})")
    assert worst <= tol, f"{op} [{case}]: {worst} > {tol}"


def _grads(fwd, cotangent):
    """jitted (dq, dk, dv) of an attention `fwd` under one cotangent."""
    import jax
    import jax.numpy as jnp
    return jax.jit(jax.grad(
        lambda q, k, v: (fwd(q, k, v).astype(jnp.float32) *
                         cotangent.astype(jnp.float32)).sum(),
        argnums=(0, 1, 2)))


def _flash_cases(cfg, rows):
    import jax
    import jax.numpy as jnp

    from fengshen_tpu.ops.pallas import get_kernel
    pallas = get_kernel("flash_attention", "pallas")
    xla = get_kernel("flash_attention", "xla")
    heads, hd = cfg.num_attention_heads, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, ct = (jax.random.normal(kk, (1, SEQ, heads, hd),
                                     jnp.bfloat16) for kk in keys)
    # packed-row segment ids: three examples and a padded tail
    seg = jnp.asarray(
        [[1] * 700 + [2] * 900 + [3] * 300 + [0] * (SEQ - 1900)],
        jnp.int32)
    for name, segs in (("causal", None), ("causal+segments", seg)):
        def fwd_p(q, k, v):
            return pallas(q, k, v, segs, segs, True)

        def fwd_x(q, k, v):
            return xla(q, k, v, causal=True, q_segment_ids=segs,
                       kv_segment_ids=segs)
        shape = f"[1,{SEQ},{heads},{hd}] {name}"
        _check(rows, "flash_attention", f"fwd {shape}",
               jax.jit(fwd_p)(q, k, v), jax.jit(fwd_x)(q, k, v))
        _check(rows, "flash_attention", f"bwd {shape}",
               _grads(fwd_p, ct)(q, k, v), _grads(fwd_x, ct)(q, k, v))


def _decode_cases(cfg, rows):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fengshen_tpu.ops.int8_matmul import quantize_kv
    from fengshen_tpu.ops.pallas import get_kernel
    from fengshen_tpu.ops.pallas.decode_attention import (
        pallas_decode_eligible)
    from fengshen_tpu.serving import EngineConfig
    pallas = get_kernel("decode_attention", "pallas")
    xla = get_kernel("decode_attention", "xla")
    heads, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
    lanes, gamma = EngineConfig().num_slots, EngineConfig().spec_gamma
    max_len = cfg.max_position_embeddings
    block, per_lane = PAGED_BLOCK, max_len // PAGED_BLOCK
    n_blocks = lanes * per_lane + 1
    rng = np.random.RandomState(SEED)
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    # per-lane ragged validity: a left-padded prompt, then a cursor
    lo = rng.randint(0, 60, lanes)
    hi = rng.randint(200, max_len - 8, lanes)
    table = jnp.asarray(1 + rng.permutation(n_blocks - 1).reshape(
        lanes, per_lane), jnp.int32)
    for s in (1, gamma + 1):      # decode tick, speculative window
        q = jax.random.normal(keys[0], (lanes, s, heads, hd),
                              jnp.bfloat16)
        pos = np.arange(max_len)[None, None, :]
        valid = jnp.asarray((pos >= lo[:, None, None]) &
                            (pos <= (hi[:, None] +
                                     np.arange(s)[None])[:, :, None]))
        for layout in ("slot", "paged"):
            shape = ((lanes, max_len, kvh, hd) if layout == "slot"
                     else (n_blocks, block, kvh, hd))
            k = jax.random.normal(keys[1], shape, jnp.bfloat16)
            v = jax.random.normal(keys[2], shape, jnp.bfloat16)
            kw = {"dequant_dtype": jnp.bfloat16}
            if layout == "paged":
                kw["block_table"] = table
            for dtype in ("bf16", "int8"):
                if dtype == "int8":
                    (k8, ks), (v8, vs) = quantize_kv(k), quantize_kv(v)
                    args = (q, k8, v8, valid)
                    kw.update(k_scale=ks, v_scale=vs)
                else:
                    args = (q, k, v, valid)
                assert pallas_decode_eligible(
                    q, args[1], block_table=kw.get("block_table")), \
                    f"{layout}/{dtype} S={s} is not kernel-eligible"
                got = jax.jit(lambda *a, kw=kw: pallas(*a, **kw))(*args)
                want = jax.jit(lambda *a, kw=kw: xla(*a, **kw))(*args)
                _check(rows, "decode_attention",
                       f"{layout} {dtype} S={s} "
                       f"kv={list(shape)}", got, want)


def _folded_decode_cases(cfg, rows):
    """The seam's folded entry at Qwen3-Next's published geometry (16
    query heads over 2 KV heads of 256 in one 512-value row, blocks of
    128 tokens), whatever the smoke's own model is: the kernel walks
    each lane to its own cursor, the xla twin to the longest lane's."""
    del cfg
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fengshen_tpu.ops.pallas import get_kernel
    from fengshen_tpu.ops.pallas.decode_attention import (
        _folded_ineligible_reason)
    pallas = get_kernel("folded_decode_attention", "pallas")
    xla = get_kernel("folded_decode_attention", "xla")
    lanes, heads, hd, groups, block, per_lane = 8, 16, 256, 2, 128, 24
    n_blocks = lanes * per_lane + 1
    rng = np.random.RandomState(SEED + 4)
    keys = jax.random.split(jax.random.PRNGKey(SEED + 4), 3)
    q = jax.random.normal(keys[0], (lanes, 1, heads, hd), jnp.bfloat16)
    shape = (n_blocks, block, 1, groups * hd)
    k = jax.random.normal(keys[1], shape, jnp.bfloat16)
    v = jax.random.normal(keys[2], shape, jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(n_blocks - 1).reshape(
        lanes, per_lane), jnp.int32).at[0].set(0)      # a released lane
    t = jnp.asarray([0, 127, 128, 1023, 1024, per_lane * block - 1] +
                    list(rng.randint(200, per_lane * block, lanes - 6)),
                    jnp.int32)
    assert _folded_ineligible_reason(q, k) is None
    got = jax.jit(lambda *a: pallas(*a, scale=hd ** -0.5))(q, k, v, table, t)
    want = jax.jit(lambda *a: xla(*a, scale=hd ** -0.5))(q, k, v, table, t)
    _check(rows, "folded_decode_attention",
           f"paged bf16 kv={list(shape)} t={t.tolist()}", got, want)


def _mla_decode_cases(cfg, rows):
    """The seam's latent entry at JoyAI-LLM-Flash's published geometry
    (32 heads over one shared row of rank 512 + rope 64, padded to 640;
    blocks of 128 tokens in a `[2, ...]` stack read at layer 1),
    whatever the smoke's own model is: ragged cursors, left-padded
    lanes, a released lane; the kernel walks each lane's live blocks,
    the xla twin gathers every table entry."""
    del cfg
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fengshen_tpu.ops.pallas import get_kernel
    from fengshen_tpu.ops.pallas.decode_attention import (
        _mla_ineligible_reason)
    pallas = get_kernel("mla_decode_attention", "pallas")
    xla = get_kernel("mla_decode_attention", "xla")
    lanes, heads, rank, rope, width, block, per_lane = 8, 32, 512, 64, 640, \
        128, 24
    n_blocks = lanes * per_lane + 1
    rng = np.random.RandomState(SEED + 5)
    keys = jax.random.split(jax.random.PRNGKey(SEED + 5), 3)
    shape = (2, n_blocks, block, 1, width)
    kv = jax.random.normal(keys[2], shape, jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(n_blocks - 1).reshape(
        lanes, per_lane), jnp.int32).at[0].set(0)      # a released lane
    hi = np.array([0, 127, 128, 1023, 1024, per_lane * block - 2] +
                  list(rng.randint(200, per_lane * block - 2, lanes - 6)))
    lo = np.minimum(rng.randint(0, 200, lanes), hi)
    lo[0] = 1                                          # no valid key
    pos = np.arange(per_lane * block)[None, None, :]
    for s in (1, 2):              # decode tick, a verify window
        q = jax.random.normal(keys[0], (lanes, s, heads, rank),
                              jnp.bfloat16)
        q_rope = jax.random.normal(keys[1], (lanes, s, heads, rope),
                                   jnp.bfloat16)
        valid = jnp.asarray((pos >= lo[:, None, None]) &
                            (pos <= (hi[:, None] +
                                     np.arange(s)[None])[:, :, None]))
        assert _mla_ineligible_reason(q, kv, table) is None
        kw = dict(scale=192 ** -0.5, block_table=table, layer=jnp.int32(1))
        got = jax.jit(lambda *a: pallas(*a, **kw))(q, q_rope, kv, valid)
        want = jax.jit(lambda *a: xla(*a, **kw))(q, q_rope, kv, valid)
        # the released lane attends nothing: each lowering averages
        # what it walked
        _check(rows, "mla_decode_attention",
               f"paged bf16 S={s} kv={list(shape)}", got[1:], want[1:])


def _mla_prefill_cases(cfg, rows):
    """Latent attention's full form at the published geometry of both
    models that have it (32 heads of 128 + 64 and 128 over rows of rank
    512 in 640): a window of 1,024 queries at position 3,072 of a lane
    of 8,192 rows, Kimi-Linear's call, and a prompt of 1,024 left-padded
    by 300 onto a lane of 4,096, JoyAI's; the kernel walks the rows to
    the window's last query, its xla twin is the `jax.numpy` walk."""
    del cfg
    import jax
    import jax.numpy as jnp

    from fengshen_tpu.ops.pallas import get_kernel
    from fengshen_tpu.ops.pallas.latent_attention import _ineligible_reason
    pallas = get_kernel("mla_prefill_attention", "pallas")
    xla = get_kernel("mla_prefill_attention", "xla")
    seq, heads, rank, width = 1024, 32, 512, 640
    ks = jax.random.split(jax.random.PRNGKey(SEED + 6), 4)
    q_nope = jax.random.normal(ks[0], (1, seq, heads, 128), jnp.bfloat16)
    q_shared = jax.random.normal(ks[1], (1, seq, heads, 64), jnp.bfloat16)
    w_kvb = (jax.random.normal(ks[2], (rank, heads, 256)) *
             rank ** -0.5).astype(jnp.bfloat16)
    for total, start, pad in ((8192, 3072, None), (4096, 0, 300)):
        lane = jax.random.normal(ks[3], (1, total, width), jnp.bfloat16)
        lane = lane.at[..., rank + 64:].set(0)
        valid = None if pad is None else (jnp.arange(total) >= pad)[None]
        assert _ineligible_reason(q_nope, q_shared, lane, w_kvb) is None
        kw = dict(key_valid=valid, scale=192 ** -0.5)
        args = (q_nope, q_shared, lane, w_kvb, jnp.int32(start))
        got = jax.jit(lambda *a: pallas(*a, **kw))(*args)
        want = jax.jit(lambda *a: xla(*a, **kw))(*args)
        # a pad query has no valid key: each lowering averages what it
        # walked, and no one reads the row
        _check(rows, "mla_prefill_attention",
               f"bf16 q=[1,{seq},{heads},128+64] rows=[1,{total},{width}] "
               f"start={start} pad={pad}", got[:, pad or 0:],
               want[:, pad or 0:])


def _gated_delta_cases(cfg, rows):
    """The chunked gated delta rule, a case a caller: at Qwen3-Next's
    published head geometry (value heads of 128 two to a key head, one
    scalar gate a token a head) and at Kimi-Linear's (one value head a
    key head, a gate per key channel); l2-normalised float32 q and k,
    bfloat16 v, a window padded on the right, onto a state that is not
    zero: the chunk kernel's two bodies against the `jax.numpy` form.
    The float32 state is held to 1e-4, not to a bf16 tolerance."""
    del cfg
    import jax
    import jax.numpy as jnp

    from fengshen_tpu.ops.gated_delta import l2norm
    from fengshen_tpu.ops.pallas import get_kernel
    from fengshen_tpu.ops.pallas.gated_delta import _ineligible_reason
    pallas = get_kernel("gated_delta_prefill", "pallas")
    xla = get_kernel("gated_delta_prefill", "xla")
    seq, heads, dim, real = 1024, 8, 128, 900
    for key_heads, per_channel in ((4, False), (8, True)):
        ks = jax.random.split(jax.random.PRNGKey(SEED + 5), 6)
        q = l2norm(jax.random.normal(ks[0], (1, seq, key_heads, dim))) * \
            dim ** -0.5
        k = l2norm(jax.random.normal(ks[1], (1, seq, key_heads, dim)))
        v = jax.random.normal(ks[2], (1, seq, heads, dim), jnp.bfloat16)
        g = -jax.nn.softplus(jax.random.normal(
            ks[3], (1, seq, heads) + (dim,) * per_channel))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, heads)))
        state = jax.random.normal(ks[5], (1, heads, dim, dim))
        mask = jnp.arange(seq)[None] < real
        assert _ineligible_reason(q, v, g) is None
        got, got_state = jax.jit(pallas)(q, k, v, g, beta, state, mask)
        want, want_state = jax.jit(xla)(q, k, v, g, beta, state, mask)
        case = f"q={list(q.shape)} g={list(g.shape)} bf16, {real} real"
        _check(rows, "gated_delta_prefill", case + ", out", got[:, :real],
               want[:, :real])
        _check(rows, "gated_delta_prefill", case + ", state", got_state,
               want_state, tol=1e-4)


def _grouped_matmul_cases(cfg, rows):
    """The routed experts' products at Keye's and JoyAI's published
    widths (tables of 2048 x 768) over rows sorted by expert, the
    Mosaic grouped matmul against three `ragged_dot`. A window: groups
    of uneven size, some empty, a tile that straddles several, and a
    last quarter of the rows past the last group (a share's experts not
    held). A decode tick: 2 rows an expert, a third of the groups
    empty, one tile visited by dozens of groups. The rows past the last
    group are the kernel's zeros; `ragged_dot` leaves them undefined, so
    they are not compared."""
    del cfg
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fengshen_tpu.ops.pallas import get_kernel
    from fengshen_tpu.ops.pallas.grouped_matmul import _ineligible_reason
    pallas = get_kernel("grouped_matmul", "pallas")
    xla = get_kernel("grouped_matmul", "xla")
    hidden, width = 2048, 768
    rng = np.random.RandomState(SEED + 6)
    share = rng.dirichlet(np.full(32, 0.5)) * (rng.rand(32) > 0.2)
    window = np.floor(share / share.sum() * 4096 * 0.75).astype(np.int32)
    tick = rng.multinomial(256, rng.dirichlet(np.full(128, 1.0))
                           ).astype(np.int32)
    assert (tick == 0).sum() >= 16
    for what, total, sizes in (("window", 4096, window),
                               ("tick", 256, tick)):
        count, held = len(sizes), int(sizes.sum())
        ks = jax.random.split(jax.random.PRNGKey(SEED + 6), 4)
        r = jax.random.normal(ks[0], (total, hidden), jnp.bfloat16)
        g, u = (0.02 * jax.random.normal(k, (count, hidden, width),
                                         jnp.bfloat16) for k in ks[1:3])
        d = 0.02 * jax.random.normal(ks[3], (count, width, hidden),
                                     jnp.bfloat16)
        assert _ineligible_reason(r, g) is None
        got = jax.jit(pallas)(r, g, u, d, jnp.asarray(sizes))
        want = jax.jit(xla)(r, g, u, d, jnp.asarray(sizes))
        assert not np.asarray(got[held:], np.float32).any()
        _check(rows, "grouped_matmul",
               f"{what}: rows={list(r.shape)} tables={list(g.shape)} bf16, "
               f"{held} held in groups of {sizes.min()}..{sizes.max()}",
               got[:held], want[:held])


def _fused_ce_cases(cfg, rows):
    import jax
    import jax.numpy as jnp

    from fengshen_tpu.ops.pallas import get_kernel
    pallas = get_kernel("fused_ce", "pallas")
    xla = get_kernel("fused_ce", "xla")
    h, vocab = cfg.hidden_size, cfg.vocab_size
    keys = jax.random.split(jax.random.PRNGKey(SEED + 2), 3)
    hidden = jax.random.normal(keys[0], (1, SEQ, h), jnp.bfloat16)
    kernel = (jax.random.normal(keys[1], (h, vocab), jnp.float32) *
              cfg.initializer_range).astype(jnp.bfloat16)
    labels = jax.random.randint(keys[2], (1, SEQ), 0, vocab)
    labels = labels.at[:, :100].set(-100)       # ignored prompt tokens
    shape = f"[{SEQ},{h}]x[{h},{vocab}]"
    got = jax.jit(lambda x, w: pallas(x, w, labels))(hidden, kernel)
    want = jax.jit(lambda x, w: xla(x, w, labels))(hidden, kernel)
    _check(rows, "fused_ce", f"fwd loss {shape}", got[0], want[0],
           tol=1e-3)
    assert int(got[1]) == int(want[1]) == SEQ - 100, (got[1], want[1])
    # near-tied bf16 logits may move an argmax or two
    assert abs(int(got[2]) - int(want[2])) <= 2, (got[2], want[2])

    def grads(fn):
        return jax.jit(jax.grad(lambda x, w: fn(x, w, labels)[0],
                                argnums=(0, 1)))
    _check(rows, "fused_ce", f"bwd dx,dK {shape}",
           grads(pallas)(hidden, kernel), grads(xla)(hidden, kernel))


def _block_sparse_cases(cfg, rows):
    """Registers no xla twin: its reference is the dense expanded-mask
    path `ops.attention.dot_product_attention` falls to."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fengshen_tpu.ops.attention import dot_product_attention
    from fengshen_tpu.ops.pallas import get_kernel
    pallas = get_kernel("block_sparse_attention", "pallas")
    heads, hd = cfg.num_attention_heads, cfg.head_dim
    blk = 128
    n = SEQ // blk
    rows_i, cols_i = np.indices((n, n))
    layout = (cols_i <= rows_i) & (rows_i - cols_i < 4)   # banded causal
    mask = jnp.asarray(np.kron(layout, np.ones((blk, blk), bool)))
    keys = jax.random.split(jax.random.PRNGKey(SEED + 3), 4)
    q, k, v, ct = (jax.random.normal(kk, (1, SEQ, heads, hd),
                                     jnp.bfloat16) for kk in keys)

    def fwd_p(q, k, v):
        return pallas(q, k, v, layout, blk)

    def fwd_x(q, k, v):
        return dot_product_attention(q, k, v, mask=mask[None, None])
    shape = f"[1,{SEQ},{heads},{hd}] band 4x{blk}"
    _check(rows, "block_sparse_attention", f"fwd {shape}",
           jax.jit(fwd_p)(q, k, v), jax.jit(fwd_x)(q, k, v))
    _check(rows, "block_sparse_attention", f"bwd {shape}",
           _grads(fwd_p, ct)(q, k, v), _grads(fwd_x, ct)(q, k, v))


KERNEL_CASES = {
    "flash_attention": _flash_cases,
    "decode_attention": _decode_cases,
    "folded_decode_attention": _folded_decode_cases,
    "mla_decode_attention": _mla_decode_cases,
    "mla_prefill_attention": _mla_prefill_cases,
    "gated_delta_prefill": _gated_delta_cases,
    "grouped_matmul": _grouped_matmul_cases,
    "fused_ce": _fused_ce_cases,
    "block_sparse_attention": _block_sparse_cases,
}


def kernel_phase(cfg, meter) -> dict:
    import jax

    from fengshen_tpu.ops.pallas import dispatch_table, probe
    mark, t0 = meter.mark(), time.perf_counter()
    table = dispatch_table()
    log(f"kernel phase: probe {probe().describe()}")
    assert set(table) == set(KERNEL_CASES), \
        f"ops without a smoke case: {set(table) ^ set(KERNEL_CASES)}"
    rows: list = []
    for op, impl in table.items():
        assert impl == "pallas", f"{op} dispatches to {impl} on a TPU"
        KERNEL_CASES[op](cfg, rows)
        jax.clear_caches()
        gc.collect()
    log("dispatch table (op -> implementation that will run):")
    for op, impl in table.items():
        n = sum(1 for r in rows if r[0] == op)
        log(f"  {op:<24} -> {impl}  ({n} cases compiled and matched)")
    log(f"kernel phase ok in {time.perf_counter() - t0:.1f}s "
        f"({meter.since(mark)})")
    return table


# -- phase 2: train -------------------------------------------------------

def _train_args(root: str, n_dev: int, tensor: int, batch: int):
    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.trainer import add_trainer_args
    from fengshen_tpu.utils import UniversalCheckpoint
    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    UniversalCheckpoint.add_argparse_args(parser)
    ckpt_dir = os.path.join(root, "ckpt")
    return parser.parse_args([
        "--offload", "none",
        "--fsdp_parallel_size", str(n_dev // tensor),
        "--tensor_model_parallel_size", str(tensor),
        "--max_steps", str(TRAIN_STEPS), "--max_epochs", "1",
        "--train_batchsize", str(batch), "--sampler_type", "single",
        "--log_every_n_steps", "1", "--seed", str(SEED),
        "--learning_rate", "1e-4", "--scheduler_type", "constant",
        "--warmup_ratio", "0", "--weight_decay", "0",
        "--default_root_dir", root,
        "--save_ckpt_path", ckpt_dir, "--load_ckpt_path", ckpt_dir,
    ])


class _RepeatedBatch:
    """`steps` copies of one seeded batch of token rows: with the
    sequential sampler every optimizer step sees the same batch, so the
    loss has to fall."""

    def __init__(self, vocab: int, batch: int, steps: int):
        import numpy as np
        rows = np.random.RandomState(SEED).randint(
            1, vocab, (batch, SEQ)).astype(np.int32)
        self._rows, self._n = rows, batch * steps

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        return {"input_ids": self._rows[i % len(self._rows)]}


def train_phase(meter, tensor: int = 1, depth: int = 0,
                batch: int = 0) -> dict:
    """`depth`/`batch` 0 = sized from the devices (what `main` runs);
    explicit values let a builder compare mesh layouts at equal work."""
    import jax
    import jax.numpy as jnp

    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.llama import LlamaForCausalLM
    from fengshen_tpu.observability import peak_flops_per_chip
    from fengshen_tpu.parallel import set_mesh
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint
    from fengshen_tpu.utils.universal_checkpoint import DATA_FILE_BYTES

    mark, t0 = meter.mark(), time.perf_counter()
    devices = jax.devices()
    n_dev = len(devices)
    total = sum(d.memory_stats()["bytes_limit"] for d in devices)
    cfg = load_config()
    depth = depth or depth_for(cfg, total, TRAIN_STATE_SHARE,
                               TRAIN_BYTES_PER_PARAM, "train")
    cfg = dataclasses.replace(cfg, num_hidden_layers=depth)
    batch = batch or n_dev
    root = os.path.join(RUN_DIR, f"train_fsdp{n_dev // tensor}"
                                 f"_tp{tensor}")
    shutil.rmtree(root, ignore_errors=True)
    args = _train_args(root, n_dev, tensor, batch)

    trainer = Trainer(args)
    module = CausalLMModule(args, LlamaForCausalLM(cfg), cfg)
    data = UniversalDataModule(args=args, datasets={
        "train": _RepeatedBatch(cfg.vocab_size, batch, TRAIN_STEPS)})
    ckpt = UniversalCheckpoint(args)
    trainer.callbacks.append(ckpt)
    log(f"train phase: mesh {dict(trainer.mesh.shape)}, depth {depth}, "
        f"batch {batch} x {SEQ} tokens, {TRAIN_STEPS} steps")
    state = trainer.fit(module, data)
    jax.block_until_ready(state.params)

    policy = trainer._offload_policy
    log(f"  offload level resolved: {policy.level}")
    assert policy.level == "none", policy.level
    assert trainer.global_step == TRAIN_STEPS, trainer.global_step

    with open(os.path.join(root, "metrics.jsonl")) as f:
        entries = [json.loads(line) for line in f]
    steps = [e for e in entries if "loss" in e and "step" in e]
    assert len(steps) >= 6, f"only {len(steps)} logged steps"
    losses = [e["loss"] for e in steps]
    log("  loss by step: " + " ".join(f"{x:.4f}" for x in losses))
    assert all(x == x and abs(x) < 1e9 for x in losses), losses
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    assert all(e["bad_step_count"] == 0 for e in steps), \
        [e["bad_step_count"] for e in steps]

    # tokens/s and MFU are in the log, against this device's peak
    peak = peak_flops_per_chip(devices[0].device_kind) * n_dev
    for e in steps:
        assert e["tokens_per_sec"] > 0, e
        want = e["tokens_per_sec"] * module.flops_per_token() / peak
        assert abs(e["mfu"] - want) <= 1e-3 * want + 1e-12, (e, want)
    log(f"  last step entry: tokens_per_sec "
        f"{steps[-1]['tokens_per_sec']:.0f}, mfu {steps[-1]['mfu']:.4f} "
        f"of {peak:.3g} FLOP/s (evidence the path ran, not a result)")

    # the attention that ran was the Mosaic kernel: from the log line
    dispatch = [e for e in entries if e.get("event") == "kernel_dispatch"]
    assert dispatch, "the fit logged no kernel_dispatch line"
    sites = [s for s in dispatch[-1]["call_sites"]
             if s["op"] == "flash_attention" and f", {SEQ}, " in
             s["detail"].split(" kv=")[0]]
    log(f"  flash call sites at seq {SEQ}: {sites}")
    assert sites and all(s["impl"] == "pallas" for s in sites), sites

    # the state is spread over the mesh, not piled on one device
    leaves = jax.tree_util.tree_leaves(state.params)
    assert all(len(leaf.sharding.device_set) == n_dev for leaf in leaves)
    split = sum(leaf.nbytes for leaf in leaves
                if not leaf.sharding.is_fully_replicated)
    whole = sum(leaf.nbytes for leaf in leaves)
    log(f"  parameter bytes sharded over the mesh: {split / whole:.4f} "
        "(norm scales replicate)")
    assert n_dev == 1 or split >= 0.99 * whole, (split, whole)
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices]
    log("  bytes in use per device (GB): " +
        " ".join(f"{b / 1e9:.2f}" for b in in_use) + "; peak: " +
        " ".join(f"{b / 1e9:.2f}" for b in peaks))
    assert max(in_use) <= 1.5 * min(in_use), in_use

    # one checkpoint was saved at fit end, in files a per-file size limit
    # on the machine lets through; restore its weights
    sizes = [os.path.getsize(os.path.join(d, name))
             for d, _, names in os.walk(args.save_ckpt_path)
             for name in names]
    log(f"  checkpoint on disk: {sum(sizes) / 1e9:.2f} GB in {len(sizes)} "
        f"files, largest {max(sizes) / 2 ** 20:.0f} MiB")
    assert max(sizes) <= 2 * DATA_FILE_BYTES, max(sizes)
    cursor = types.SimpleNamespace(global_step=-1, consumed_samples=-1)
    t_restore = time.perf_counter()
    restored = ckpt.maybe_restore(state, cursor, weights_only=True)
    assert cursor.global_step == TRAIN_STEPS, cursor.global_step
    assert restored.params is not state.params
    for a, b in zip(leaves, jax.tree_util.tree_leaves(restored.params)):
        assert a.sharding == b.sharding, (a.sharding, b.sharding)
        assert bool(jnp.array_equal(a, b)), a.shape
    log(f"  checkpoint restored and equal "
        f"({time.perf_counter() - t_restore:.1f}s)")

    # free the trainer's state before the engine is built
    del state, restored, leaves, trainer, module, data, ckpt
    set_mesh(None)
    jax.clear_caches()
    gc.collect()
    shutil.rmtree(root, ignore_errors=True)
    log(f"train phase ok in {time.perf_counter() - t0:.1f}s "
        f"({meter.since(mark)})")
    return {"depth": depth, "losses": losses}


# -- phase 3: serve -------------------------------------------------------

class _IntTokenizer:
    """Token ids as space-separated integers: the serve phase has no
    vocabulary file, and needs to read the generated ids back."""

    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


def _http(url: str, payload: dict = None) -> tuple:
    """(status, json body) of a GET, or of a POST when `payload` is
    given; an HTTP error status is an answer, not an exception."""
    req = urllib.request.Request(
        url, data=None if payload is None else json.dumps(payload)
        .encode(), headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=600) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _serve_round(pipe, cfg, engine_args: dict, expect_impl: str,
                 label: str) -> None:
    import numpy as np

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       _start_warmup_thread,
                                       build_stdlib_server,
                                       create_continuous_engine)
    t0 = time.perf_counter()
    events: list = []
    engine = create_continuous_engine(pipe, engine_args,
                                      log=events.append)
    server_cfg = ServerConfig(host="127.0.0.1", port=0,
                              engine="continuous")
    pipeline_cfg = PipelineConfig(task="text_generation")
    ready = _start_warmup_thread(server_cfg, pipeline_cfg, pipe, engine)
    server = build_stdlib_server(server_cfg, pipeline_cfg, pipeline=pipe,
                                 engine=engine, ready=ready)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        code, body = _http(f"{base}/healthz")
        log(f"  [{label}] /healthz while warming: {code} {body}")
        ready.settled.wait()
        code, body = _http(f"{base}/healthz")
        assert (code, ready.error) == (200, None), (code, body)
        log(f"  [{label}] warm in {time.perf_counter() - t0:.1f}s")

        # the first engine log entry names the kernels; the one after
        # warmup names what each traced call site took
        assert events[0]["event"] == "kernel_dispatch", events[0]
        assert events[0]["table"]["decode_attention"] == expect_impl
        restated = [e for e in events
                    if e["event"] == "kernel_dispatch"][-1]
        lanes = engine.config.num_slots
        pool = (f"kv=({engine.num_blocks}, {engine.block_size}, "
                if engine.paged else f"kv=({lanes}, {engine.max_len}, ")
        ticks = [s for s in restated["call_sites"]
                 if s["op"] == "decode_attention" and pool in s["detail"]
                 and s["detail"].startswith(f"q=({lanes}, 1, ")]
        log(f"  [{label}] decode tick call sites: {ticks}")
        assert ticks and all(s["impl"] == expect_impl for s in ticks)

        rng = np.random.RandomState(SEED)
        requests = []
        for i in range(8):
            # two prefill buckets: prompts under 64 and under 128 tokens
            n_prompt = int(rng.randint(20, 60) if i % 2 else
                           rng.randint(70, 120))
            ids = rng.randint(1, cfg.vocab_size, n_prompt)
            requests.append({"input_text": " ".join(map(str, ids)),
                             "max_new_tokens": 32 + 8 * (i % 3)})
        with ThreadPoolExecutor(len(requests)) as pool:
            answers = list(pool.map(
                lambda r: _http(f"{base}/api/text_generation", r),
                requests))
        for req, (code, body) in zip(requests, answers):
            assert code == 200, (code, body)
            assert body["finish_reason"] == "length", body
            tokens = [int(t) for t in body["result"].split()]
            assert len(tokens) == req["max_new_tokens"], \
                (len(tokens), req["max_new_tokens"])
            assert all(0 <= t < cfg.vocab_size for t in tokens), tokens
        deadline = time.monotonic() + 30
        while True:
            code, stats = _http(f"{base}/stats")
            assert code == 200, (code, stats)
            if stats["slots_active"] == 0 or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        log(f"  [{label}] /stats: completed {stats['completed']}, "
            f"last_error {stats['last_error']}, kv "
            f"{stats['kv_layout']}/{stats['kv_dtype']} blocks used "
            f"{stats['kv_blocks_used']} of {stats['kv_blocks_total']}")
        assert stats["last_error"] is None, stats["last_error"]
        assert stats["completed"] == len(requests), stats["completed"]
        assert stats["kv_blocks_used"] == 0, stats["kv_blocks_used"]
        assert not [e for e in events
                    if e["event"] == "serving_tick_error"], events
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()
        thread.join(timeout=10)
    log(f"  [{label}] ok in {time.perf_counter() - t0:.1f}s")


def serve_phase(meter, table: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from fengshen_tpu.models.llama import LlamaForCausalLM
    from fengshen_tpu.pipelines.text_generation import Pipeline

    mark, t0 = meter.mark(), time.perf_counter()
    # the engine has no mesh: one replica serves from one chip
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    cfg = load_config(param_dtype="bfloat16")
    depth = depth_for(cfg, limit, SERVE_WEIGHT_SHARE,
                      SERVE_BYTES_PER_PARAM, "serve")
    cfg = dataclasses.replace(cfg, num_hidden_layers=depth)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    pipe = Pipeline(module=model, params=params,
                    tokenizer=_IntTokenizer())
    log(f"serve phase: depth {depth}, bf16 weights "
        f"{sum(p.nbytes for p in jax.tree_util.tree_leaves(params)) / 1e9:.2f} GB")
    expect = table["decode_attention"]
    _serve_round(pipe, cfg, {}, expect, "default engine")
    gc.collect()
    _serve_round(pipe, cfg, {"kv_layout": "paged", "kv_dtype": "int8",
                             "kv_block_size": PAGED_BLOCK}, expect,
                 "paged int8 engine")
    log(f"serve phase ok in {time.perf_counter() - t0:.1f}s "
        f"({meter.since(mark)})")
    return {"depth": depth}


# -- entry ----------------------------------------------------------------

def device_gate() -> dict:
    """Exit before anything else unless the default backend is a TPU
    whose peak is on record; print what the run is standing on."""
    from importlib.metadata import version

    import jax

    from fengshen_tpu.compile_cache import ensure_compile_cache
    from fengshen_tpu.observability import peak_flops_per_chip
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: the default backend is {backend!r}, not "
                 "'tpu'; this check runs on the chip or not at all")
    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}, peak "
        f"{peak_flops_per_chip(first.device_kind):.3g} FLOP/s per chip, "
        f"memory {first.memory_stats()['bytes_limit'] / 1e9:.2f} GB "
        "per chip")
    log(f"versions: jax {version('jax')}, jaxlib {version('jaxlib')}, "
        f"libtpu {version('libtpu')}, flax {version('flax')}, optax "
        f"{version('optax')}, orbax-checkpoint "
        f"{version('orbax-checkpoint')}")
    log(f"compile cache directory: {ensure_compile_cache()}")
    return device


def run(phases=("kernels", "train", "serve"), **train_kw) -> dict:
    t0 = time.perf_counter()
    device = device_gate()
    meter = CompileMeter()
    from fengshen_tpu.ops.pallas import dispatch_table
    table = kernel_phase(load_config(), meter) if "kernels" in phases \
        else dispatch_table()
    if "train" in phases:
        train_phase(meter, **train_kw)
    if "serve" in phases:
        serve_phase(meter, table)
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    log(f"all phases ok in {time.perf_counter() - t0:.1f}s; total "
        f"{meter.since((0.0, 0, 0))}")
    return device


if __name__ == "__main__":
    print(json.dumps({"ok": True, "device": run()}), flush=True)
