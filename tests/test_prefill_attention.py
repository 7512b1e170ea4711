"""A whole-prompt prefill onto an empty cache attends over the prompt's
own keys (`ops.flash_attention.prefill_attention`), not over the scratch
cache's extent through the decode seam. The full-extent path it
replaced is kept HERE as the plain reference: the same `apply` without
the static `cache_empty` fact, which takes the seam's dense lowering
over all `max_position_embeddings` rows, as every prefill did before.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fengshen_tpu.models.llama.modeling_llama as modeling_llama
from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.utils.generate import _prefill_cache


def _tiny(head_dim, kv_heads, scan, max_len, dtype="bfloat16"):
    heads = 4
    cfg = LlamaConfig(
        dtype=dtype,
        vocab_size=96, hidden_size=heads * head_dim, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=heads,
        num_key_value_heads=kv_heads, max_position_embeddings=max_len,
        scan_layers=scan)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _left_padded(seq, pads=(5, 0), seed=0):
    """A lockstep batch as `generate` and the engine build it: row b
    has `pads[b]` pad tokens on the left."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(1, 96, (len(pads), seq))
    mask = np.ones((len(pads), seq), np.int32)
    for b, n in enumerate(pads):
        mask[b, :n] = 0
        ids[b, :n] = 0
    mask = jnp.asarray(mask)
    return jnp.asarray(ids), mask, jnp.clip(mask.cumsum(-1) - 1, 0, None)


def _empty_cache(model, batch):
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((batch, 1), jnp.int32),
                           init_cache=True))
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), abstract["cache"])


def _full_extent_prefill(model, params, ids, mask, pos, cache=None):
    """The reference: the prompt through the decode seam over the whole
    cache (what `_prefill_cache` ran before it knew the cache empty)."""
    cache = _empty_cache(model, ids.shape[0]) if cache is None else cache
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, ids, attention_mask=mask,
        position_ids=pos, init_cache=True, mutable=["cache"])
    return logits, mutated["cache"]


@pytest.fixture
def calls(monkeypatch):
    """Which attention each traced layer call took, by query window."""
    seen = {"seam": [], "prefill": []}
    seam, prefill = (modeling_llama.decode_attention,
                     modeling_llama.prefill_attention)

    def spy_seam(q, *a, **kw):
        seen["seam"].append(q.shape[1])
        return seam(q, *a, **kw)

    def spy_prefill(q, *a, **kw):
        seen["prefill"].append(q.shape[1])
        return prefill(q, *a, **kw)

    monkeypatch.setattr(modeling_llama, "decode_attention", spy_seam)
    monkeypatch.setattr(modeling_llama, "prefill_attention", spy_prefill)
    return seen


@pytest.fixture
def mosaic_interpreted(monkeypatch):
    """The dispatch seam answers as on a TPU and the flash kernel runs
    in interpret mode: the arithmetic of the kernel on the CPU."""
    import fengshen_tpu.ops.pallas.flash_attention as kernel_module
    from fengshen_tpu.ops.pallas import FORCE_ENV, probe
    real = kernel_module.pallas_flash_attention
    ran = []

    def interpreted(q, k, v, q_seg, kv_seg, causal):
        ran.append((q.shape, k.shape, q_seg is not None, causal))
        return real(q, k, v, q_seg, kv_seg, causal, 256, 256, True)

    monkeypatch.setattr(kernel_module, "pallas_flash_attention",
                        interpreted)
    monkeypatch.setenv(FORCE_ENV, "pallas")
    probe(refresh=True)
    yield ran
    monkeypatch.delenv(FORCE_ENV)
    probe(refresh=True)


def _real_rows(cache, mask, seq):
    """The K/V rows of real tokens, in a fixed order (the scanned
    cache has a leading layer axis; the cursors come along whole)."""
    real = np.asarray(mask).astype(bool)
    rows = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        leaf = np.asarray(leaf)
        if leaf.ndim < 4:
            rows.append((jax.tree_util.keystr(path), leaf))
            continue
        lead = leaf.reshape((-1,) + leaf.shape[-4:])[:, :, :seq]
        rows.append((jax.tree_util.keystr(path), lead[:, real]))
    return rows


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_prefill_over_own_keys_matches_full_extent_dense(calls, kv_heads,
                                                         scan):
    """head_dim 64 cannot take the kernel: the dense chain over the
    prompt's `seq` keys. The same sums without the zeros: last-position
    logits equal, and every real token's cache row bitwise."""
    from fengshen_tpu.ops.pallas import traced_dispatch
    model, params = _tiny(64, kv_heads, scan, max_len=64)
    ids, mask, pos = _left_padded(24)
    want_logits, want_cache = _full_extent_prefill(model, params, ids,
                                                   mask, pos)
    # (the cache's init pass is one token, and the seam's)
    assert calls["prefill"] == [] and set(calls["seam"]) == {1, 24}
    calls["seam"].clear()
    logits, cache = _prefill_cache(model, params, ids, mask, pos)
    assert set(calls["seam"]) == {1} and set(calls["prefill"]) == {24}
    assert any(d["op"] == "flash_attention" and d["impl"] == "xla"
               and d["detail"].startswith("prefill q=(2, 24, 4, 64)")
               for d in traced_dispatch())
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(want_logits[:, -1]),
                               rtol=1e-5, atol=1e-6)
    for (name, got), (_, want) in zip(_real_rows(cache, mask, 24),
                                      _real_rows(want_cache, mask, 24)):
        np.testing.assert_array_equal(got, want, err_msg=name)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_prefill_over_own_keys_matches_full_extent_mosaic(
        calls, mosaic_interpreted, kv_heads, scan):
    """head_dim 128 and a 128-token bucket take the flash kernel
    (interpret mode): causal, the pad mask as segment ids, GQA inside
    the kernel. In float32, where another order of the same sums shows
    only in the last digits."""
    from fengshen_tpu.ops.pallas import traced_dispatch
    model, params = _tiny(128, kv_heads, scan, max_len=256,
                          dtype="float32")
    ids, mask, pos = _left_padded(128, pads=(37, 0))
    want_logits, want_cache = _full_extent_prefill(model, params, ids,
                                                   mask, pos)
    assert mosaic_interpreted == []          # the seam's dense lowering
    logits, cache = _prefill_cache(model, params, ids, mask, pos)
    assert set(calls["prefill"]) == {128}
    assert mosaic_interpreted and all(
        ran == ((2, 128, 4, 128), (2, 128, kv_heads, 128), True, True)
        for ran in mosaic_interpreted)
    assert any(d["op"] == "flash_attention" and d["impl"] == "pallas"
               and d["detail"].startswith("prefill q=(2, 128, 4, 128)")
               for d in traced_dispatch())
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(want_logits[:, -1]),
                               rtol=2e-4, atol=2e-5)
    for (name, got), (_, want) in zip(_real_rows(cache, mask, 128),
                                      _real_rows(want_cache, mask, 128)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("lowering", ["dense", "mosaic"])
def test_no_real_query_reads_a_pad_key(request, lowering):
    """Under segment ids a pad query attends the pad keys, under the
    dense mask it attends nothing and spreads over all: either way what
    the pads hold reaches no real row's logits or cache rows."""
    if lowering == "mosaic":
        request.getfixturevalue("mosaic_interpreted")
        model, params = _tiny(128, 2, False, max_len=256)
        seq, pads = 128, (37, 0)
    else:
        model, params = _tiny(64, 2, False, max_len=64)
        seq, pads = 24, (5, 0)
    ids, mask, pos = _left_padded(seq, pads)
    logits, cache = _prefill_cache(model, params, ids, mask, pos)
    other = ids.at[0, :pads[0]].set(
        jnp.arange(1, pads[0] + 1, dtype=ids.dtype))
    logits2, cache2 = _prefill_cache(model, params, other, mask, pos)
    real = np.asarray(mask).astype(bool)
    np.testing.assert_array_equal(np.asarray(logits)[real],
                                  np.asarray(logits2)[real])
    assert not np.array_equal(np.asarray(logits)[~real],
                              np.asarray(logits2)[~real])
    for (name, got), (_, want) in zip(_real_rows(cache, mask, seq),
                                      _real_rows(cache2, mask, seq)):
        np.testing.assert_array_equal(got, want, err_msg=name)


@pytest.mark.parametrize("window", [
    "decode_step", "verify_window", "short_prompt_onto_empty_cache",
    "long_window_onto_a_prefix", "slot_pool"])
def test_everything_but_a_whole_prompt_stays_on_the_seam(calls, window):
    """The decode tick, a speculative verify window (<= 8 queries), a
    prompt that short, any window onto a cache that holds a prefix, and
    the serving pools keep the decode seam: `prefill_attention` is
    never entered. A long window at a non-zero index (no caller today)
    is exact there: it reads the cached prefix."""
    model, params = _tiny(64, 2, False, max_len=64)
    ids, mask, pos = _left_padded(32, pads=(0, 0))
    if window == "short_prompt_onto_empty_cache":
        _prefill_cache(model, params, ids[:, :8], mask[:, :8], pos[:, :8])
        assert calls["prefill"] == [] and set(calls["seam"]) == {1, 8}
        return
    if window == "slot_pool":
        # a [B] cursor is the serving slot pool's: never the prefill's
        # layout, whatever the caller says of its contents
        cache = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros((2,), jnp.int32)
            if path[-1].key == "cache_index" else leaf,
            _empty_cache(model, 2))
        model.apply({"params": params, "cache": cache}, ids[:, :16],
                    position_ids=pos[:, :16], init_cache=True,
                    cache_empty=True, mutable=["cache"])
        assert calls["prefill"] == [] and set(calls["seam"]) == {1, 16}
        return
    want, _ = _full_extent_prefill(model, params, ids, mask, pos)
    n = {"decode_step": 1, "verify_window": 5,
         "long_window_onto_a_prefix": 16}[window]
    _, primed = _prefill_cache(model, params, ids[:, :32 - n],
                               mask[:, :32 - n], pos[:, :32 - n])
    assert set(calls["prefill"]) == {32 - n}
    calls["prefill"].clear(), calls["seam"].clear()
    got, _ = _full_extent_prefill(model, params, ids[:, 32 - n:], mask,
                                  pos[:, 32 - n:], cache=primed)
    assert calls["prefill"] == [] and set(calls["seam"]) == {n}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 32 - n:]),
                               rtol=1e-5, atol=1e-5)
