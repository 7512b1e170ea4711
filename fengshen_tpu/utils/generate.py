"""Generation / sampling utilities.

Covers two reference surfaces:
- `top_k_logits` / `sample_sequence(_batch)` sampling helpers
  (reference: fengshen/utils/transfo_xl_utils.py, exported at
  fengshen/utils/__init__.py:1-4) — here with top-p added;
- the HF-`generate`-style decode path used for LLaMA SFT inference
  (reference: fengshen/examples/ziya_llama/llama_generate.py:17-58 —
  left-padded batch, kv-cache trim, position_ids from mask cumsum,
  reference: fengshen/models/llama/modeling_llama.py:353-375).

TPU-native: the whole decode loop is one `lax.scan` inside jit (static
shapes, preallocated cache), instead of a per-token Python loop.
"""

from __future__ import annotations

import inspect
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


def top_k_logits(logits: jax.Array, k: int = 0, p: float = 0.0,
                 filter_value: float = -1e9) -> jax.Array:
    """Reference: fengshen/utils/transfo_xl_utils.py top_k_logits — combined
    top-k then nucleus filtering."""
    if k > 0:
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, filter_value, logits)
    if p > 0.0:
        logits = top_p_logits(logits, p, filter_value)
    return logits


def top_p_logits(logits: jax.Array, p: float,
                 filter_value: float = -1e9) -> jax.Array:
    """Nucleus filtering: keep the smallest set of tokens with cumulative
    probability ≥ p (always keeps the argmax)."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # mask tokens whose prefix (excluding themselves) already reaches p
    cutoff_mask = (cum - probs) >= p
    threshold = jnp.where(cutoff_mask, jnp.inf, sorted_logits).min(
        axis=-1, keepdims=True)
    return jnp.where(logits < threshold, filter_value, logits)


def apply_logits_controls(logits, history, cur_index, *,
                          repetition_penalty: float = 1.0,
                          no_repeat_ngram_size: int = 0,
                          min_length: int = 0,
                          eos_token_id: Optional[int] = None,
                          history_mask=None):
    """HF-`generate`-compatible logits processors, fully jittable
    (reference: fengshen/utils/transfo_xl_utils.py penalized sampling;
    the examples pass the HF kwargs — mt5_summary, qa_t5, ziya).

    logits [N, V]; history [N, L] tokens generated so far (prompt
    included for decoder-only); cur_index: traced count of valid history
    tokens (== the position the next token will take); history_mask
    [N, L] marks real tokens (left-padded prompts).
    """
    n_rows, vocab = logits.shape
    length = history.shape[1]
    logits = logits.astype(jnp.float32)
    valid = jnp.arange(length)[None, :] < cur_index
    if history_mask is not None:
        valid = valid & history_mask.astype(bool)

    if repetition_penalty != 1.0:
        seen = jnp.zeros((n_rows, vocab), jnp.int32).at[
            jnp.arange(n_rows)[:, None], history].max(
            valid.astype(jnp.int32)).astype(bool)
        penalized = jnp.where(logits > 0, logits / repetition_penalty,
                              logits * repetition_penalty)
        logits = jnp.where(seen, penalized, logits)

    if no_repeat_ngram_size == 1:
        # HF semantics at size 1: ban every previously generated token
        banned = jnp.zeros((n_rows, vocab), jnp.int32).at[
            jnp.arange(n_rows)[:, None], history].max(
            valid.astype(jnp.int32)).astype(bool)
        logits = jnp.where(banned, jnp.float32(-1e9), logits)
    elif no_repeat_ngram_size > 1:
        n = no_repeat_ngram_size
        # previous complete n-grams: windows [s, s+n) inside the valid
        # prefix; the candidate v is banned when the last (n-1)-gram plus
        # v matches one of them (HF NoRepeatNGramLogitsProcessor)
        n_win = length - n + 1
        if n_win > 0:
            idx = jnp.arange(n_win)[:, None] + jnp.arange(n - 1)[None, :]
            wins = history[:, idx]                     # [N, W, n-1]
            nxt = history[:, jnp.arange(n - 1, length)]  # [N, W]
            win_ok = valid[:, idx].all(-1) & \
                valid[:, jnp.arange(n - 1, length)]
            last = jax.lax.dynamic_slice_in_dim(
                history, cur_index - (n - 1), n - 1, axis=1)
            match = (wins == last[:, None, :]).all(-1) & win_ok
            match = match & (cur_index >= n - 1)
            banned = jnp.zeros((n_rows, vocab), jnp.int32).at[
                jnp.arange(n_rows)[:, None], nxt].max(
                match.astype(jnp.int32)).astype(bool)
            logits = jnp.where(banned, jnp.float32(-1e9), logits)

    if min_length > 0 and eos_token_id is not None:
        eos_col = jnp.arange(vocab) == eos_token_id
        logits = jnp.where(eos_col[None] & (cur_index < min_length),
                           jnp.float32(-1e9), logits)
    return logits


def _controls_active(repetition_penalty, no_repeat_ngram_size,
                     min_length) -> bool:
    return (repetition_penalty != 1.0 or no_repeat_ngram_size > 0 or
            min_length > 0)


def _make_control(control_kw: dict, history_mask=None):
    """`control(logits, history, cur_index)` — identity when no control
    is active, else apply_logits_controls bound to these settings. The
    ONE place every decode path gets its processor from."""
    if not _controls_active(control_kw["repetition_penalty"],
                            control_kw["no_repeat_ngram_size"],
                            control_kw["min_length"]):
        return lambda logits, history, cur: logits
    return partial(apply_logits_controls, history_mask=history_mask,
                   **control_kw)


def _filtered_logits(logits, temperature, top_k, top_p):
    """THE sampling filter pipeline (fp32, temperature, combined
    top-k/top-p). Shared by `_select_token` and `_spec_dist`: the
    speculative rejection scheme is distribution-exact only if the p/q
    it compares are exactly the distribution draft proposals are
    sampled from — one implementation keeps them from drifting."""
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    return top_k_logits(logits, k=top_k, p=top_p)


def _select_token(logits, rng, do_sample, temperature, top_k, top_p):
    if not do_sample:
        return logits.astype(jnp.float32).argmax(-1)
    return jax.random.categorical(
        rng, _filtered_logits(logits, temperature, top_k, top_p),
        axis=-1)


def generate(model: Any, params: Any, input_ids: jax.Array,
             attention_mask: Optional[jax.Array] = None,
             max_new_tokens: int = 32,
             do_sample: bool = False, temperature: float = 1.0,
             top_k: int = 0, top_p: float = 0.0,
             eos_token_id: Optional[int] = None,
             pad_token_id: int = 0,
             repetition_penalty: float = 1.0,
             no_repeat_ngram_size: int = 0,
             min_length: int = 0,
             rng: Optional[jax.Array] = None) -> jax.Array:
    """Batched decode with a preallocated KV cache.

    `input_ids` is LEFT-padded [B, S] (the reference pads left for batched
    generation, reference: llama_generate.py:17-40); `attention_mask` marks
    real tokens. Returns [B, S + max_new_tokens] with pad after eos.
    `min_length` counts the FULL sequence (prompt + generated), matching
    HF `generate(min_length=...)` for decoder-only models.
    """
    batch, prompt_len = input_ids.shape
    if max_new_tokens <= 0:
        return input_ids
    if attention_mask is None:
        attention_mask = jnp.ones((batch, prompt_len), jnp.int32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    total_len = prompt_len + max_new_tokens
    hist_mask = jnp.concatenate(
        [attention_mask.astype(jnp.int32),
         jnp.ones((batch, max_new_tokens), jnp.int32)], axis=1)
    control = _make_control(
        dict(repetition_penalty=repetition_penalty,
             no_repeat_ngram_size=no_repeat_ngram_size,
             min_length=min_length, eos_token_id=eos_token_id),
        history_mask=hist_mask)

    # position_ids from mask cumsum (left-pad aware,
    # reference: modeling_llama.py:353-375)
    position_ids = jnp.clip(attention_mask.cumsum(-1) - 1, 0, None)

    logits, cache = _prefill_cache(model, params, input_ids,
                                   attention_mask, position_ids)

    buf = jnp.concatenate(
        [input_ids.astype(jnp.int32),
         jnp.full((batch, max_new_tokens), pad_token_id, jnp.int32)],
        axis=1)
    rng, step_rng = jax.random.split(rng)
    step_logits = control(logits[:, -1], buf, jnp.int32(prompt_len))
    next_token = _select_token(step_logits, step_rng, do_sample,
                               temperature, top_k, top_p)
    buf = buf.at[:, prompt_len].set(next_token.astype(jnp.int32))
    finished = jnp.zeros((batch,), bool)
    if eos_token_id is not None:
        finished = finished | (next_token == eos_token_id)

    def step(carry, inp):
        cache, buf, token, pos, finished = carry
        t, step_rng = inp
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, token[:, None],
            attention_mask=attention_mask,
            position_ids=pos[:, None], init_cache=True, mutable=["cache"])
        step_logits = control(logits[:, -1], buf, t)
        nxt = _select_token(step_logits, step_rng, do_sample,
                            temperature, top_k, top_p)
        nxt = jnp.where(finished, pad_token_id, nxt).astype(jnp.int32)
        if eos_token_id is not None:
            finished = finished | (nxt == eos_token_id)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, nxt[:, None], t, axis=1)
        return (mutated["cache"], buf, nxt, pos + 1, finished), None

    pos0 = position_ids[:, -1] + 1
    step_rngs = jax.random.split(rng, max(max_new_tokens - 1, 0))
    ts = jnp.arange(prompt_len + 1, total_len)
    (_, buf, _, _, _), _ = jax.lax.scan(
        step, (cache, buf, next_token, pos0, finished), (ts, step_rngs))
    return buf


def is_cache_index_path(path) -> bool:
    """True when a tree_map_with_path key path addresses a `cache_index`
    leaf (the decode write-position state in every cache family here).
    Shared by `_rollback_cache` and the serving slot pool's per-slot
    index surgery (fengshen_tpu/serving/cache.py)."""
    return any(getattr(k, "key", None) == "cache_index" for k in path)


def _rollback_cache(cache, delta):
    """Lower every `cache_index` leaf by `delta` (traced scalar).

    Sound for this repo's cache design (modeling_llama.py _update_cache
    and its siblings): entries are written with dynamic_update_slice AT
    the index, and attention validity is `key_pos <= idx + t` per
    query — so after lowering the index, stale tail entries are masked
    out and later overwritten in place."""
    def fix(path, leaf):
        if is_cache_index_path(path):
            return leaf - jnp.asarray(delta, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def model_takes(model, name: str) -> bool:
    """Whether `model`'s `__call__` has the optional argument `name`:
    how a caller finds out what a model family can be told
    (`cache_empty`, `live`, `logits_row`; docs/serving.md)."""
    return name in inspect.signature(type(model).__call__).parameters


def _prefill_cache(model, params, input_ids, attention_mask,
                   position_ids, logits_row=None):
    """Abstract-init a decode cache and run the prompt through it.
    Returns (prompt logits, primed cache). With `logits_row` (a traced
    scalar) and a model that takes it, the logits are that one row's,
    `[B, 1, V]`; a model that does not take it returns every row's.

    The cache is built from abstract shapes only — a real init would
    materialize a full-precision param tree (fatal for the int8 serving
    path on models sized to barely fit)."""
    batch = input_ids.shape[0]
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((batch, 1), jnp.int32),
                           init_cache=True))
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), abstract["cache"])
    # the one place that KNOWS the cache holds nothing (it was made a
    # line ago; its index is traced): a model that can use the fact
    # attends over the prompt's own keys, not the cache's extent
    told = {"cache_empty": True} if model_takes(model, "cache_empty") \
        else {}
    if logits_row is not None and model_takes(model, "logits_row"):
        told["logits_row"] = logits_row
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, input_ids,
        attention_mask=attention_mask, position_ids=position_ids,
        init_cache=True, mutable=["cache"], **told)
    return logits, mutated["cache"]


def _spec_dist(logits, temperature, top_k, top_p):
    """The filtered sampling distribution `_select_token` draws from,
    as fp32 probabilities (same `_filtered_logits` pipeline)."""
    return jax.nn.softmax(
        _filtered_logits(logits, temperature, top_k, top_p), axis=-1)


def _spec_round_tokens(t_logits, d_logits, d, rng, *, do_sample,
                       temperature=1.0, top_k=0, top_p=0.0):
    """One speculative round's accept/commit math (pure — the
    distributional correctness of the sampling scheme is unit-tested
    directly against analytic probabilities).

    `t_logits` [B, g+1, V]: target logits over `[last, d_1..d_g]`;
    `d_logits` [B, g, V] or None (greedy): draft logits for the
    proposals `d` [B, g]. Returns `(n_r, w)`: per-row accepted-prefix
    length and the [B, g+1] window tokens — accepted proposals, then
    the correction/resample at the first rejection, then (meaningful
    only on full acceptance) the bonus token.

    Greedy: accept while the draft equals the target argmax; the
    correction IS the target argmax, so w is argmax(t_logits).
    Sampling (the standard speculative rejection scheme): accept d_i
    with prob min(1, p_i(d_i)/q_i(d_i)); at the first rejection
    resample from norm(max(0, p_i - q_i)); on full acceptance sample
    the bonus from p_{g+1}. Every committed token is then distributed
    EXACTLY as a plain sample from the target's filtered distribution
    conditioned on the committed prefix — the draft changes only how
    many target dispatches it takes.
    """
    gamma = d.shape[1]
    if not do_sample:
        y = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
        m = (d == y[:, :gamma])
        n_r = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1), axis=1)
        return n_r, y
    p = _spec_dist(t_logits, temperature, top_k, top_p)  # [B, g+1, V]
    q = _spec_dist(d_logits, temperature, top_k, top_p)  # [B, g, V]
    p_d = jnp.take_along_axis(p[:, :gamma], d[..., None], -1)[..., 0]
    q_d = jnp.take_along_axis(q, d[..., None], -1)[..., 0]
    r_accept, r_resid, r_bonus = jax.random.split(rng, 3)
    # u < p/q without the division (q_d > 0: d was sampled from q)
    u = jax.random.uniform(r_accept, d.shape)
    accept = u * q_d < p_d
    n_r = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
    resid = jnp.maximum(p[:, :gamma] - q, 0.0)
    norm = resid.sum(-1, keepdims=True)
    # p == q makes the residual empty; any sample from p is then
    # already correct (rejection can't occur with prob > 0, but guard
    # the categorical against log(0) rows anyway)
    resid = jnp.where(norm > 0, resid / jnp.maximum(norm, 1e-20),
                      p[:, :gamma])
    resample = jax.random.categorical(
        r_resid, jnp.log(resid + 1e-20), axis=-1).astype(jnp.int32)
    bonus = jax.random.categorical(
        r_bonus, jnp.log(p[:, gamma] + 1e-20), axis=-1).astype(jnp.int32)
    w = jnp.concatenate(
        [jnp.where(jnp.arange(gamma)[None] < n_r[:, None], d, resample),
         bonus[:, None]], axis=1)
    return n_r, w


def _spec_round_tokens_lanes(t_logits, d_logits, d, keys, *, do_sample,
                             temperature=1.0, top_k=0, top_p=0.0):
    """Per-lane keyed variant of `_spec_round_tokens` for the serving
    engine's slot pool: each lane carries its OWN PRNG key (the
    engine's per-lane key ring), so a lane's accept/resample draws are
    a pure function of its request seed — independent of which other
    requests co-tenant the pool. `keys` is [B, 2] uint32 (one key per
    lane). Greedy delegates straight to the shared single-key path
    (the rng is unused there); sampling vmaps the SAME accept rule
    over lanes so there is exactly one implementation of the
    rejection-sampling math."""
    if not do_sample:
        return _spec_round_tokens(t_logits, None, d, None,
                                  do_sample=False)

    def per_lane(tl, dl, dd, key):
        n_r, w = _spec_round_tokens(
            tl[None], dl[None], dd[None], key, do_sample=True,
            temperature=temperature, top_k=top_k, top_p=top_p)
        return n_r[0], w[0]

    return jax.vmap(per_lane)(t_logits, d_logits, d, keys)


def _spec_early_return(input_ids, max_new_tokens, return_stats):
    """Shared no-op path for max_new_tokens <= 0 (None = proceed)."""
    if max_new_tokens > 0:
        return None
    return (input_ids, {"rounds": 0, "drafted": 0, "accepted": 0,
                        "acceptance_rate": 0.0}) \
        if return_stats else input_ids


def _check_spec_cache_headroom(models, total_len, gamma, fn_name):
    """The verify forward near the end writes cache entries up to index
    total_len + gamma - 1; a too-small preallocated cache would CLAMP
    the dynamic_update_slice start and silently corrupt committed
    entries (breaking exactness), so refuse loudly. `models` is
    (name, module) pairs."""
    for name, m in models:
        max_len = getattr(getattr(m, "config", None),
                          "max_position_embeddings", None)
        if max_len is not None and max_len < total_len + gamma:
            raise ValueError(
                f"{fn_name}: {name}.config.max_position_embeddings="
                f"{max_len} < prompt+max_new_tokens+gamma="
                f"{total_len + gamma}; the speculation window needs "
                "gamma extra cache slots")


def _speculative_loop(model, params, input_ids, attention_mask,
                      max_new_tokens, gamma, *, do_sample, temperature,
                      top_k, top_p, eos_token_id, pad_token_id, rng,
                      return_stats, propose, post_commit, extra_init):
    """The ONE copy of the propose→verify→commit speculative machinery
    (shared by `speculative_generate` and `prompt_lookup_generate` —
    the eos-masking, min-advance commit, and cache-rollback bookkeeping
    are subtle enough that two copies would silently diverge).

    `propose(extra, buf, t, pos, last, r_draft) -> (extra, d, d_logits)`
    supplies each round's [B, gamma] proposals (d_logits None in greedy
    modes); `post_commit(extra, n) -> extra` runs after the commit
    (e.g. draft-cache rollback); `extra` is any pytree carried through
    the while_loop (a draft KV cache, or () for draft-free lookup).
    `attention_mask` may be None (defaults to all-ones); the shared
    cache-headroom guard lives in `_check_spec_cache_headroom`.
    """
    batch, prompt_len = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((batch, prompt_len), jnp.int32)
    total_len = prompt_len + max_new_tokens
    position_ids = jnp.clip(attention_mask.cumsum(-1) - 1, 0, None)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    t_logits, t_cache = _prefill_cache(model, params, input_ids,
                                       attention_mask, position_ids)

    # slack columns keep the fixed-width window write in-bounds near
    # the end (dynamic_update_slice CLAMPS the start index, which would
    # silently mis-place the window)
    buf = jnp.concatenate(
        [input_ids.astype(jnp.int32),
         jnp.full((batch, max_new_tokens + gamma + 1), pad_token_id,
                  jnp.int32)], axis=1)
    rng, r_first = jax.random.split(rng)
    first = _select_token(t_logits[:, -1], r_first, do_sample,
                          temperature, top_k, top_p).astype(jnp.int32)
    buf = buf.at[:, prompt_len].set(first)
    finished = (first == eos_token_id) if eos_token_id is not None \
        else jnp.zeros((batch,), bool)
    last = jnp.where(finished, pad_token_id, first).astype(jnp.int32)
    pos0 = position_ids[:, -1] + 1

    def body(carry):
        (extra, t_cache, buf, t, pos, last, finished,
         rng, rounds, accepted) = carry
        prev_finished = finished
        rng, r_draft, r_round = jax.random.split(rng, 3)
        extra, d, d_logits = propose(extra, buf, t, pos, last, r_draft)

        verify = jnp.concatenate([last[:, None], d], axis=1)
        v_pos = pos[:, None] + jnp.arange(gamma + 1)[None]
        logits, mut = model.apply(
            {"params": params, "cache": t_cache}, verify,
            attention_mask=attention_mask, position_ids=v_pos,
            init_cache=True, mutable=["cache"])
        t_cache = mut["cache"]

        n_r, w = _spec_round_tokens(
            logits, d_logits, d, r_round, do_sample=do_sample,
            temperature=temperature, top_k=top_k, top_p=top_p)
        n_r = jnp.where(finished, gamma, n_r)
        n = jnp.min(n_r)
        c = n + 1  # committed this round (1..gamma+1)

        if eos_token_id is not None:
            is_eos = w == eos_token_id
            after = jnp.pad(jnp.cumsum(is_eos, axis=1)[:, :-1],
                            ((0, 0), (1, 0))) > 0
            w = jnp.where(after, pad_token_id, w)
            in_window = jnp.arange(gamma + 1)[None] < c
            finished = finished | jnp.any(is_eos & in_window, axis=1)
        w = jnp.where(prev_finished[:, None], pad_token_id, w)
        w = jnp.where(jnp.arange(gamma + 1)[None] < c, w, pad_token_id)

        buf = jax.lax.dynamic_update_slice_in_dim(buf, w, t, axis=1)
        new_last = jax.lax.dynamic_slice_in_dim(w, c - 1, 1, axis=1)[:, 0]
        # the committed count is c; the target cache advanced gamma+1
        # -> valid through the second-newest committed token, t'-1
        t_cache = _rollback_cache(t_cache, gamma - n)
        extra = post_commit(extra, n)
        return (extra, t_cache, buf, t + c, pos + c, new_last,
                finished, rng, rounds + 1, accepted + n)

    def cond(carry):
        t, finished = carry[3], carry[6]
        return (t < total_len) & ~jnp.all(finished)

    init = (extra_init, t_cache, buf, jnp.int32(prompt_len + 1), pos0,
            last, finished, rng, jnp.int32(0), jnp.int32(0))
    (_, _, buf, _, _, _, _, _, rounds, accepted) = \
        jax.lax.while_loop(cond, body, init)
    out = buf[:, :total_len]
    if return_stats:
        drafted = rounds * gamma
        return out, {"rounds": rounds, "drafted": drafted,
                     "accepted": accepted,
                     "acceptance_rate":
                         accepted.astype(jnp.float32) /
                         jnp.maximum(drafted, 1).astype(jnp.float32)}
    return out


def speculative_generate(model: Any, params: Any,
                         draft_model: Any, draft_params: Any,
                         input_ids: jax.Array,
                         attention_mask: Optional[jax.Array] = None,
                         max_new_tokens: int = 32,
                         gamma: int = 4,
                         do_sample: bool = False,
                         temperature: float = 1.0,
                         top_k: int = 0, top_p: float = 0.0,
                         eos_token_id: Optional[int] = None,
                         pad_token_id: int = 0,
                         rng: Optional[jax.Array] = None,
                         return_stats: bool = False):
    """Speculative decoding: the output law of plain `generate` at a
    fraction of the target-model dispatches (beyond-reference serving
    capability; the reference's serving path is plain per-token decode,
    fengshen/examples/ziya_llama/llama_generate.py:17-58).

    Each round the small draft model proposes `gamma` tokens
    autoregressively; the target model scores `[last, d_1..d_gamma]` in
    ONE forward; the longest acceptable prefix is committed plus one
    correction token. Greedy (`do_sample=False`): acceptance is
    draft==target-argmax and the output is TOKEN-EXACT vs plain greedy
    decode. Sampling (`do_sample=True`): the draft samples from its
    filtered distribution q, acceptance is the standard rejection rule
    min(1, p/q) with residual resampling (see `_spec_round_tokens`), so
    every committed token is distributed exactly as a plain sample from
    the target's filtered distribution — same law as `generate(...,
    do_sample=True)`, not token-identical (randomness is consumed
    differently). Per round the target runs once for 1..gamma+1
    committed tokens instead of once per token.

    Batched: rows advance together by the MINIMUM accepted length
    across unfinished rows (a shared cache index keeps positions
    aligned). An over-accepted row's discarded tail is re-derived next
    round: greedily that reproduces the identical tokens (exactness by
    determinism); under sampling the redo draws fresh randomness, and
    exactness holds in DISTRIBUTION — the fresh round conditions only
    on the committed prefix, so each committed token is still
    ~ p(.|prefix). Both KV caches roll back via `_rollback_cache` —
    sound because stale entries past the index are masked and
    overwritten (see that helper's docstring).

    The whole loop is one `lax.while_loop` under jit: static shapes,
    `gamma` static, dynamic trip count with >=1 committed token per
    round. `return_stats` also returns
    {"rounds", "drafted", "accepted"} for acceptance-rate tuning.
    """
    assert gamma >= 1, "speculative decoding needs gamma >= 1"
    batch, prompt_len = input_ids.shape
    early = _spec_early_return(input_ids, max_new_tokens, return_stats)
    if early is not None:
        return early
    if attention_mask is None:
        attention_mask = jnp.ones((batch, prompt_len), jnp.int32)
    _check_spec_cache_headroom(
        (("model", model), ("draft_model", draft_model)),
        prompt_len + max_new_tokens, gamma, "speculative_generate")
    position_ids = jnp.clip(attention_mask.cumsum(-1) - 1, 0, None)
    _, d_cache = _prefill_cache(draft_model, draft_params, input_ids,
                                attention_mask, position_ids)

    def draft_step(carry, step_rng):
        cache, tok, pos = carry
        logits, mut = draft_model.apply(
            {"params": draft_params, "cache": cache}, tok[:, None],
            attention_mask=attention_mask, position_ids=pos[:, None],
            init_cache=True, mutable=["cache"])
        nxt = _select_token(logits[:, -1], step_rng, do_sample,
                            temperature, top_k, top_p).astype(jnp.int32)
        ys = (nxt, logits[:, -1]) if do_sample else nxt
        return (mut["cache"], nxt, pos + 1), ys

    def propose(d_cache, buf, t, pos, last, r_draft):
        # draft gamma proposals (one extra feed keeps the draft cache
        # aligned with the target on full acceptance)
        (d_cache, _, _), drafts = jax.lax.scan(
            draft_step, (d_cache, last, pos),
            jax.random.split(r_draft, gamma + 1))
        if do_sample:
            d = jnp.moveaxis(drafts[0], 0, 1)[:, :gamma]  # [B, gamma]
            d_logits = jnp.moveaxis(drafts[1], 0, 1)[:, :gamma]
        else:
            d = jnp.moveaxis(drafts, 0, 1)[:, :gamma]
            d_logits = None
        return d_cache, d, d_logits

    return _speculative_loop(
        model, params, input_ids, attention_mask, max_new_tokens,
        gamma, do_sample=do_sample, temperature=temperature,
        top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
        pad_token_id=pad_token_id, rng=rng, return_stats=return_stats,
        propose=propose,
        post_commit=lambda d_cache, n: _rollback_cache(d_cache,
                                                       gamma - n),
        extra_init=d_cache)


def _ngram_propose(buf, t, ngram, gamma, pad_token_id):
    """Prompt-lookup proposals: find an earlier occurrence of the
    `ngram`-token suffix ending at position t (exclusive) in each row
    of `buf`, and propose the `gamma` tokens that followed it. Prefers
    the LATEST match whose whole gamma-token continuation lies inside
    the committed region — the very latest match's continuation can run
    into uncommitted pads, capping acceptance on exactly the periodic
    outputs lookup targets — falling back to the latest partial match.
    Rows with no match propose pads (they'll be rejected and the round
    degrades to plain one-token decode). Pure + static shapes; `t` may
    be traced."""
    batch, width = buf.shape
    suffix = jax.lax.dynamic_slice_in_dim(buf, t - ngram, ngram, axis=1)
    # windows[b, j] == buf[b, j:j+ngram]
    windows = jnp.stack(
        [buf[:, k:width - ngram + 1 + k] for k in range(ngram)], axis=-1)
    match = jnp.all(windows == suffix[:, None, :], axis=-1)
    pos = jnp.arange(width - ngram + 1)[None]
    # continuation must start strictly inside the committed region
    match = match & (pos + ngram < t)
    fits = match & (pos + ngram + gamma <= t)
    j_fit = jnp.max(jnp.where(fits, pos, -1), axis=1)
    j_any = jnp.max(jnp.where(match, pos, -1), axis=1)
    j = jnp.where(j_fit >= 0, j_fit, j_any)  # [B], -1 = none
    idx = jnp.clip(j[:, None] + ngram + jnp.arange(gamma)[None], 0,
                   width - 1)
    d = jnp.take_along_axis(buf, idx, axis=1)
    return jnp.where((j >= 0)[:, None], d, pad_token_id).astype(jnp.int32)


def _ngram_propose_lanes(buf, t, ngram, gamma, fallback):
    """Per-lane-cursor flavor of `_ngram_propose` for the serving slot
    pool (fengshen_tpu/serving/engine.py): `t` is a [B] vector — every
    lane's committed history ends at its own position — and a lane with
    no n-gram hit proposes its `fallback` token (its last committed
    token) repeated, so degenerate lanes degrade to >=1 committed token
    per verify instead of drafting pads that can never be accepted.
    Pure + static shapes; vmap turns the dynamic suffix slice into a
    gather, so the ONE matcher implementation serves both the lockstep
    `prompt_lookup_generate` loop and the pool's per-lane tick."""
    def one(row, ti, fb):
        return _ngram_propose(row[None], ti, ngram, gamma, fb)[0]
    return jax.vmap(one)(buf, t, fallback)


def prompt_lookup_generate(model: Any, params: Any,
                           input_ids: jax.Array,
                           attention_mask: Optional[jax.Array] = None,
                           max_new_tokens: int = 32,
                           gamma: int = 4, ngram: int = 2,
                           eos_token_id: Optional[int] = None,
                           pad_token_id: int = 0,
                           return_stats: bool = False):
    """DRAFT-FREE speculative decoding (prompt lookup): propose the
    continuation of the latest earlier occurrence of the current
    `ngram`-token suffix, verify all `gamma` proposals with one target
    forward, commit the accepted prefix + 1 correction. TOKEN-EXACT vs
    plain greedy `generate` — the lookup only changes how many target
    dispatches it takes. Big wins on extractive/repetitive workloads
    (summarisation, QA over a context, code) where the continuation
    often already appears verbatim in the prompt or the generation.

    Same loop/cache machinery as `speculative_generate` minus the
    draft model: one `lax.while_loop`, KV rollback via `_rollback_cache`,
    batched min-advance (see that function's docstring).
    """
    assert gamma >= 1 and ngram >= 1
    prompt_len = input_ids.shape[1]
    early = _spec_early_return(input_ids, max_new_tokens, return_stats)
    if early is not None:
        return early
    _check_spec_cache_headroom(
        (("model", model),), prompt_len + max_new_tokens, gamma,
        "prompt_lookup_generate")

    def propose(extra, buf, t, pos, last, r_draft):
        return extra, _ngram_propose(buf, t, ngram, gamma,
                                     pad_token_id), None

    return _speculative_loop(
        model, params, input_ids, attention_mask, max_new_tokens,
        gamma, do_sample=False, temperature=1.0, top_k=0, top_p=0.0,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id, rng=None,
        return_stats=return_stats, propose=propose,
        post_commit=lambda extra, n: extra, extra_init=())


def _make_seq2seq_logits_fn(model, params, input_ids, attention_mask,
                            expand: int):
    """Build `logits_fn(dec_buf [N, L]) -> [N, L, V]` for an encoder-decoder
    model, with the batch expanded ×`expand` (beam width).

    Two protocols:
    - `encode` + `decode_logits` (every seq2seq family in the zoo — T5,
      BART, Pegasus, DeltaLM): the encoder runs ONCE outside the decode
      loop; only the decoder stack re-runs per step.
    - plain `__call__(input_ids, decoder_input_ids, ...)`: fallback for
      external/custom modules that only expose a full forward — the whole
      model re-runs per step.
    """
    if hasattr(model, "encode") and hasattr(model, "decode_logits"):
        enc = model.apply({"params": params}, input_ids, attention_mask,
                          method=model.encode)
        enc = jnp.repeat(enc, expand, axis=0)
        mask = (None if attention_mask is None
                else jnp.repeat(attention_mask, expand, axis=0))

        def logits_fn(dec_buf):
            return model.apply({"params": params}, dec_buf, enc, mask,
                               method=model.decode_logits)
    else:
        ids = jnp.repeat(input_ids, expand, axis=0)
        mask = (None if attention_mask is None
                else jnp.repeat(attention_mask, expand, axis=0))

        def logits_fn(dec_buf):
            return model.apply({"params": params}, ids, dec_buf,
                               attention_mask=mask)
    return logits_fn


def _seq2seq_supports_cache(model) -> bool:
    """True when `decode_logits` takes `init_cache` (T5-style KV cache)."""
    return (hasattr(model, "encode") and hasattr(model, "decode_logits")
            and "init_cache" in
            inspect.signature(model.decode_logits).parameters)


def _init_seq2seq_cache(model, src, dec1):
    """Zeros KV-cache pytree from abstract init shapes (no param
    materialisation — same trick as decoder-only `generate`)."""
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros_like(src),
                           jnp.zeros_like(dec1), init_cache=True))
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), abstract["cache"])


def _cache_capacity(model) -> int:
    cfg = getattr(model, "config", None)
    cap = getattr(cfg, "decode_cache_length", 512)
    if _takes_position_offset(model):
        # absolute-position decoders cannot place tokens past their
        # position table; keep overflow on the buffer path, which fails
        # loudly instead of silently clamping the position lookup
        cap = min(cap, getattr(cfg, "max_position_embeddings", cap))
    return cap


def seq2seq_generate(model, params, input_ids: jax.Array,
                     attention_mask: Optional[jax.Array] = None, *,
                     max_new_tokens: int = 32,
                     decoder_start_token_id: int = 0,
                     eos_token_id: Optional[int] = None,
                     pad_token_id: int = 0,
                     do_sample: bool = False, temperature: float = 1.0,
                     top_k: int = 0, top_p: float = 0.0,
                     num_beams: int = 1, length_penalty: float = 1.0,
                     repetition_penalty: float = 1.0,
                     no_repeat_ngram_size: int = 0,
                     min_length: int = 0,
                     rng: Optional[jax.Array] = None) -> jax.Array:
    """Encoder-decoder decode (HF `generate` surface for the seq2seq
    examples — reference: fengshen/examples/mt5_summary, qa_t5,
    finetune_bart_qg all call HF `model.generate(num_beams=...)`).

    Greedy / sampling when `num_beams == 1`, otherwise beam search.
    Returns [B, 1 + max_new_tokens] decoder ids starting with
    `decoder_start_token_id`, padded after eos. `min_length` counts
    decoder tokens (start token included), matching HF seq2seq
    `generate(min_length=...)`; `repetition_penalty` and
    `no_repeat_ngram_size` act over the decoder sequence.
    """
    if num_beams > 1:
        if do_sample:
            raise ValueError(
                "beam-multinomial sampling is not supported; use either "
                "num_beams>1 (deterministic beam search) or do_sample=True")
        return seq2seq_beam_search(
            model, params, input_ids, attention_mask,
            max_new_tokens=max_new_tokens,
            decoder_start_token_id=decoder_start_token_id,
            eos_token_id=eos_token_id, pad_token_id=pad_token_id,
            num_beams=num_beams, length_penalty=length_penalty,
            repetition_penalty=repetition_penalty,
            no_repeat_ngram_size=no_repeat_ngram_size,
            min_length=min_length)

    batch = input_ids.shape[0]
    if max_new_tokens == 0:
        return jnp.full((batch, 1), decoder_start_token_id, jnp.int32)
    length = max_new_tokens + 1
    if rng is None:
        rng = jax.random.PRNGKey(0)
    control_kw = dict(repetition_penalty=repetition_penalty,
                      no_repeat_ngram_size=no_repeat_ngram_size,
                      min_length=min_length, eos_token_id=eos_token_id)
    if _seq2seq_supports_cache(model) and \
            max_new_tokens < _cache_capacity(model):
        return _cached_seq2seq_sample(
            model, params, input_ids, attention_mask,
            max_new_tokens=max_new_tokens,
            decoder_start_token_id=decoder_start_token_id,
            eos_token_id=eos_token_id, pad_token_id=pad_token_id,
            do_sample=do_sample, temperature=temperature, top_k=top_k,
            top_p=top_p, control_kw=control_kw, rng=rng)
    logits_fn = _make_seq2seq_logits_fn(model, params, input_ids,
                                        attention_mask, expand=1)
    buf = jnp.full((batch, length), pad_token_id, jnp.int32)
    buf = buf.at[:, 0].set(decoder_start_token_id)
    finished = jnp.zeros((batch,), bool)
    control = _make_control(control_kw)

    def step(carry, inp):
        buf, finished = carry
        t, step_rng = inp
        logits = jax.lax.dynamic_index_in_dim(
            logits_fn(buf), t - 1, axis=1, keepdims=False)
        logits = control(logits, buf, t)
        nxt = _select_token(logits, step_rng, do_sample, temperature,
                            top_k, top_p)
        nxt = jnp.where(finished, pad_token_id, nxt).astype(jnp.int32)
        if eos_token_id is not None:
            finished = finished | (nxt == eos_token_id)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, nxt[:, None], t, axis=1)
        return (buf, finished), None

    ts = jnp.arange(1, length)
    (buf, _), _ = jax.lax.scan(
        step, (buf, finished), (ts, jax.random.split(rng, length - 1)))
    return buf


def _cross_cache_kwargs(model) -> dict:
    """{'cross_from_cache': True} when decode_logits supports reading the
    cross-attention K/V from the cache — the priming call projects the
    encoder K/V once and scan steps skip those matmuls entirely."""
    if "cross_from_cache" in \
            inspect.signature(model.decode_logits).parameters:
        return {"cross_from_cache": True}
    return {}


def _takes_position_offset(model) -> bool:
    """Absolute-position decoders (BART family) need the decode step's
    position explicitly; T5's relative bias derives it from the cache."""
    return "position_offset" in \
        inspect.signature(model.decode_logits).parameters


def _cached_seq2seq_sample(model, params, input_ids, attention_mask, *,
                           max_new_tokens, decoder_start_token_id,
                           eos_token_id, pad_token_id, do_sample,
                           temperature, top_k, top_p, control_kw, rng):
    """Greedy/sampling decode through the model's KV cache: the encoder
    runs once, cross-attention K/V are projected once on the priming
    call, and each scan step runs the decoder on ONE token (O(L)
    attention per step instead of the O(L²) full-prefix re-run)."""
    batch = input_ids.shape[0]
    control = _make_control(control_kw)
    enc = model.apply({"params": params}, input_ids, attention_mask,
                      method=model.encode)
    cache = _init_seq2seq_cache(model, input_ids,
                                jnp.zeros((batch, 1), jnp.int32))
    cross_kw = _cross_cache_kwargs(model)
    has_pos = _takes_position_offset(model)

    def decode(cache, tok, kw, offset):
        if has_pos:
            kw = dict(kw, position_offset=offset)
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, tok[:, None], enc,
            attention_mask, init_cache=True, mutable=["cache"],
            method=model.decode_logits, **kw)
        return mutated["cache"], logits[:, -1]

    length = max_new_tokens + 1
    buf = jnp.full((batch, length), pad_token_id, jnp.int32)
    buf = buf.at[:, 0].set(decoder_start_token_id)
    start = jnp.full((batch,), decoder_start_token_id, jnp.int32)
    # same key stream as the buffer path (split(rng, max_new)): the two
    # implementations must sample identically for a given seed
    keys = jax.random.split(rng, max_new_tokens)
    # prime: projects cross K/V, decodes the start token at position 0
    cache, logits = decode(cache, start, {}, jnp.int32(0))
    tok = _select_token(control(logits, buf, jnp.int32(1)), keys[0],
                        do_sample, temperature, top_k, top_p
                        ).astype(jnp.int32)
    buf = buf.at[:, 1].set(tok)
    finished = jnp.zeros((batch,), bool)
    if eos_token_id is not None:
        finished = finished | (tok == eos_token_id)

    def step(carry, inp):
        cache, buf, tok, finished = carry
        t, step_rng = inp
        cache, logits = decode(cache, tok, cross_kw, t)
        nxt = _select_token(control(logits, buf, t + 1), step_rng,
                            do_sample, temperature, top_k, top_p)
        nxt = jnp.where(finished, pad_token_id, nxt).astype(jnp.int32)
        if eos_token_id is not None:
            finished = finished | (nxt == eos_token_id)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, nxt[:, None], t + 1, axis=1)
        return (cache, buf, nxt, finished), None

    ts = jnp.arange(1, max_new_tokens)  # token t sits at position t
    (_, buf, _, _), _ = jax.lax.scan(
        step, (cache, buf, tok, finished), (ts, keys[1:]))
    return buf


_BEAM_NEG = jnp.float32(-1e9)


def _beam_init(batch, K, length, pad_token_id, decoder_start_token_id):
    """(alive_buf, alive_scores, fin_buf, fin_scores) — only beam 0 live."""
    alive_buf = jnp.full((batch, K, length), pad_token_id, jnp.int32)
    alive_buf = alive_buf.at[:, :, 0].set(decoder_start_token_id)
    alive_scores = jnp.tile(
        jnp.where(jnp.arange(K) == 0, 0.0, _BEAM_NEG)[None], (batch, 1))
    fin_buf = jnp.full((batch, K, length), pad_token_id, jnp.int32)
    fin_scores = jnp.full((batch, K), _BEAM_NEG)
    return alive_buf, alive_scores, fin_buf, fin_scores


def _beam_select(alive_buf, alive_scores, fin_buf, fin_scores, log_probs,
                 t, K, eos_token_id, length_penalty):
    """One beam bookkeeping step, shared by the cached and buffer paths:
    expand alive beams by the vocab, keep the top 2K candidates (2K
    guarantees K non-eos survivors), move eos hypotheses into the
    finished pool (length-penalized, merged top-K), re-select K alive
    beams. Returns the updated pools plus (src_beam, tok): which previous
    beam each new alive beam extends, and with what token."""
    batch = alive_buf.shape[0]
    vocab = log_probs.shape[-1]
    cand = (alive_scores[:, :, None] + log_probs).reshape(batch, -1)
    scores2k, idx = jax.lax.top_k(cand, 2 * K)
    beam_idx, tok = idx // vocab, (idx % vocab).astype(jnp.int32)
    buf2k = jnp.take_along_axis(alive_buf, beam_idx[:, :, None], axis=1)
    buf2k = jax.lax.dynamic_update_slice_in_dim(
        buf2k, tok[:, :, None], t, axis=2)
    if eos_token_id is not None:
        is_eos = tok == eos_token_id
        pen = scores2k / (t.astype(jnp.float32) ** length_penalty)
        pen = jnp.where(is_eos, pen, _BEAM_NEG)
        all_scores = jnp.concatenate([fin_scores, pen], axis=1)
        all_buf = jnp.concatenate([fin_buf, buf2k], axis=1)
        fin_scores, fin_idx = jax.lax.top_k(all_scores, K)
        fin_buf = jnp.take_along_axis(all_buf, fin_idx[:, :, None], axis=1)
        scores2k = jnp.where(is_eos, _BEAM_NEG, scores2k)
    alive_scores, alive_idx = jax.lax.top_k(scores2k, K)
    alive_buf = jnp.take_along_axis(buf2k, alive_idx[:, :, None], axis=1)
    src_beam = jnp.take_along_axis(beam_idx, alive_idx, axis=1)
    new_tok = jnp.take_along_axis(tok, alive_idx, axis=1)
    return alive_buf, alive_scores, fin_buf, fin_scores, src_beam, new_tok


def _beam_finish(alive_buf, alive_scores, fin_buf, fin_scores,
                 max_new_tokens, length_penalty):
    """Alive beams compete with the finished pool at the horizon length;
    return the best sequence per batch row."""
    horizon = jnp.float32(max_new_tokens) ** length_penalty
    all_scores = jnp.concatenate([fin_scores, alive_scores / horizon],
                                 axis=1)
    all_buf = jnp.concatenate([fin_buf, alive_buf], axis=1)
    best = jnp.argmax(all_scores, axis=1)
    return jnp.take_along_axis(all_buf, best[:, None, None], axis=1)[:, 0]


def _cached_seq2seq_beam(model, params, input_ids, attention_mask, *,
                         max_new_tokens, decoder_start_token_id,
                         eos_token_id, pad_token_id, num_beams,
                         length_penalty, control_kw):
    """Beam search through the KV cache: one-token decoder steps with the
    cache rows gathered along the beam dimension on every reorder."""
    batch = input_ids.shape[0]
    K = num_beams
    N = batch * K
    length = max_new_tokens + 1

    enc = model.apply({"params": params}, input_ids, attention_mask,
                      method=model.encode)
    enc = jnp.repeat(enc, K, axis=0)
    mask = (None if attention_mask is None
            else jnp.repeat(attention_mask, K, axis=0))
    src_rep = jnp.repeat(input_ids, K, axis=0)
    cache = _init_seq2seq_cache(model, src_rep,
                                jnp.zeros((N, 1), jnp.int32))

    alive_buf, alive_scores, fin_buf, fin_scores = _beam_init(
        batch, K, length, pad_token_id, decoder_start_token_id)
    last_tok = jnp.full((batch, K), decoder_start_token_id, jnp.int32)
    cross_kw = _cross_cache_kwargs(model)
    has_pos = _takes_position_offset(model)
    row_control = _make_control(control_kw)

    def control(log_probs, alive_buf, cur):
        # HF beam search runs the processors on the log-softmaxed scores
        vocab = log_probs.shape[-1]
        out = row_control(log_probs.reshape(batch * K, vocab),
                          alive_buf.reshape(batch * K, -1), cur)
        return out.reshape(batch, K, vocab)

    def decode(cache, last_tok, kw, offset):
        if has_pos:
            kw = dict(kw, position_offset=offset)
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, last_tok.reshape(N, 1),
            enc, mask, init_cache=True, mutable=["cache"],
            method=model.decode_logits, **kw)
        log_probs = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32), -1).reshape(batch, K, -1)
        return mutated["cache"], log_probs

    def reorder(cache, src_beam):
        # gather the self-attention cache rows onto the surviving beams'
        # source beams; cross K/V are identical across a row's beams
        # (encoder output is repeated), so gathering them would be pure
        # wasted HBM traffic — skip by key name
        flat = (jnp.arange(batch)[:, None] * K + src_beam).reshape(-1)

        def gather(path, c):
            if c.ndim != 4 or any("cross" in str(p) for p in path):
                return c
            return c[flat]
        return jax.tree_util.tree_map_with_path(gather, cache)

    # priming step (t=1): projects the cross-attention K/V into the cache
    cache, log_probs = decode(cache, last_tok, {}, jnp.int32(0))
    log_probs = control(log_probs, alive_buf, jnp.int32(1))
    (alive_buf, alive_scores, fin_buf, fin_scores, src_beam,
     last_tok) = _beam_select(alive_buf, alive_scores, fin_buf,
                              fin_scores, log_probs, jnp.int32(1), K,
                              eos_token_id, length_penalty)
    cache = reorder(cache, src_beam)

    def step(carry, t):
        (alive_buf, alive_scores, fin_buf, fin_scores, cache,
         last_tok) = carry
        # last_tok was selected at step t-1 and sits at position t-1
        cache, log_probs = decode(cache, last_tok, cross_kw, t - 1)
        log_probs = control(log_probs, alive_buf, t)
        (alive_buf, alive_scores, fin_buf, fin_scores, src_beam,
         last_tok) = _beam_select(alive_buf, alive_scores, fin_buf,
                                  fin_scores, log_probs, t, K,
                                  eos_token_id, length_penalty)
        cache = reorder(cache, src_beam)
        return (alive_buf, alive_scores, fin_buf, fin_scores, cache,
                last_tok), None

    carry = (alive_buf, alive_scores, fin_buf, fin_scores, cache, last_tok)
    (alive_buf, alive_scores, fin_buf, fin_scores, _, _), _ = jax.lax.scan(
        step, carry, jnp.arange(2, length))
    return _beam_finish(alive_buf, alive_scores, fin_buf, fin_scores,
                        max_new_tokens, length_penalty)


def seq2seq_predict_step(model, config, args, params, batch, *,
                         max_new_tokens: int) -> jax.Array:
    """The canonical `predict_step` body for seq2seq example modules
    (qa_t5, summary, …): beam/greedy decode driven by the module's parsed
    flags (`--num_beams`, `--length_penalty`)."""
    return seq2seq_generate(
        model, params, batch["input_ids"], batch.get("attention_mask"),
        max_new_tokens=max_new_tokens,
        decoder_start_token_id=getattr(config, "decoder_start_token_id", 0),
        eos_token_id=getattr(config, "eos_token_id", None),
        pad_token_id=getattr(config, "pad_token_id", 0) or 0,
        num_beams=getattr(args, "num_beams", 1),
        length_penalty=getattr(args, "length_penalty", 1.0),
        repetition_penalty=getattr(args, "repetition_penalty", 1.0),
        no_repeat_ngram_size=getattr(args, "no_repeat_ngram_size", 0),
        min_length=getattr(args, "min_length", 0))


def seq2seq_beam_search(model, params, input_ids: jax.Array,
                        attention_mask: Optional[jax.Array] = None, *,
                        max_new_tokens: int = 32,
                        decoder_start_token_id: int = 0,
                        eos_token_id: Optional[int] = None,
                        pad_token_id: int = 0, num_beams: int = 4,
                        length_penalty: float = 1.0,
                        repetition_penalty: float = 1.0,
                        no_repeat_ngram_size: int = 0,
                        min_length: int = 0) -> jax.Array:
    """Beam search over an encoder-decoder model, fully inside `lax.scan`
    (static shapes; TPU-friendly — no per-token host sync).

    Scoring: a hypothesis ending with eos at generated-length `t`
    (excluding the start token, including eos) scores
    `sum_logprobs / t ** length_penalty`; alive beams at the horizon are
    scored the same way at `t = max_new_tokens`. Returns the best
    sequence per batch row, [B, 1 + max_new_tokens].
    """
    batch = input_ids.shape[0]
    if max_new_tokens == 0:
        return jnp.full((batch, 1), decoder_start_token_id, jnp.int32)
    control_kw = dict(repetition_penalty=repetition_penalty,
                      no_repeat_ngram_size=no_repeat_ngram_size,
                      min_length=min_length, eos_token_id=eos_token_id)
    row_control = _make_control(control_kw)
    if _seq2seq_supports_cache(model) and \
            max_new_tokens < _cache_capacity(model):
        return _cached_seq2seq_beam(
            model, params, input_ids, attention_mask,
            max_new_tokens=max_new_tokens,
            decoder_start_token_id=decoder_start_token_id,
            eos_token_id=eos_token_id, pad_token_id=pad_token_id,
            num_beams=num_beams, length_penalty=length_penalty,
            control_kw=control_kw)
    K = num_beams
    length = max_new_tokens + 1

    logits_fn = _make_seq2seq_logits_fn(model, params, input_ids,
                                        attention_mask, expand=K)
    alive_buf, alive_scores, fin_buf, fin_scores = _beam_init(
        batch, K, length, pad_token_id, decoder_start_token_id)

    def step(carry, t):
        alive_buf, alive_scores, fin_buf, fin_scores = carry
        logits = jax.lax.dynamic_index_in_dim(
            logits_fn(alive_buf.reshape(batch * K, length)),
            t - 1, axis=1, keepdims=False)
        log_probs = jax.nn.log_softmax(
            logits.astype(jnp.float32), axis=-1)
        log_probs = row_control(
            log_probs, alive_buf.reshape(batch * K, length),
            t).reshape(batch, K, -1)
        (alive_buf, alive_scores, fin_buf, fin_scores, _, _) = \
            _beam_select(alive_buf, alive_scores, fin_buf, fin_scores,
                         log_probs, t, K, eos_token_id, length_penalty)
        return (alive_buf, alive_scores, fin_buf, fin_scores), None

    carry = (alive_buf, alive_scores, fin_buf, fin_scores)
    (alive_buf, alive_scores, fin_buf, fin_scores), _ = jax.lax.scan(
        step, carry, jnp.arange(1, length))
    return _beam_finish(alive_buf, alive_scores, fin_buf, fin_scores,
                        max_new_tokens, length_penalty)


def sample_sequence_batch(model, params, context: jax.Array,
                          max_out_seq: int, *,
                          attention_mask: Optional[jax.Array] = None,
                          temperature: float = 1.0,
                          top_k: int = 0, top_p: float = 0.0,
                          eos_token_id: Optional[int] = None,
                          rng: Optional[jax.Array] = None) -> jax.Array:
    """Name/shape parity with the reference's sampling helper
    (reference: fengshen/utils/transfo_xl_utils.py sample_sequence_batch).
    `attention_mask` marks real tokens of a LEFT-padded context — required
    whenever prompts in the batch have different lengths."""
    # a context already at/over max_out_seq generates nothing (the
    # reference loop simply doesn't iterate)
    max_new = max(max_out_seq - context.shape[1], 0)
    return generate(model, params, context,
                    attention_mask=attention_mask, max_new_tokens=max_new,
                    do_sample=True, temperature=temperature, top_k=top_k,
                    top_p=top_p, eos_token_id=eos_token_id, rng=rng)


def generate_with_prompts(model, params, tokenizer, prompts: list,
                          max_out_seq: int = 128, *,
                          temperature: float = 1.0, top_k: int = 0,
                          top_p: float = 0.0, seed: int = 0) -> list:
    """Encode → strip trailing eos → LEFT-pad with mask → sample → decode
    continuations (the shared driver behind the transfo_xl paraphrase /
    reasoning surfaces, reference: fengshen/utils/transfo_xl_utils.py).
    Returns the decoded text AFTER each prompt."""
    import numpy as np

    enc = [tokenizer.encode(p) for p in prompts]
    enc = [ids[:-1] if ids and ids[-1] == tokenizer.eos_token_id else ids
           for ids in enc]
    max_len = max(len(x) for x in enc)
    pad = tokenizer.pad_token_id or 0
    batch = np.full((len(enc), max_len), pad, np.int32)
    mask = np.zeros((len(enc), max_len), np.int32)
    for i, ids in enumerate(enc):
        batch[i, max_len - len(ids):] = ids
        mask[i, max_len - len(ids):] = 1
    out = sample_sequence_batch(
        model, params, jnp.asarray(batch),
        attention_mask=jnp.asarray(mask), max_out_seq=max_out_seq,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_token_id=tokenizer.eos_token_id,
        rng=jax.random.PRNGKey(seed))
    return [tokenizer.decode([int(t) for t in row[max_len:]])
            for row in np.asarray(out)]
