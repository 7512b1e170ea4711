"""Programs of the serving path compiled for a DESCRIBED TPU v5e (no
chip attached; the TPU's compiler is installed here): what the CPU
backend cannot show. Nothing runs, so nothing here is a result or a
time. All such compiles live in this one file, behind one fixture, so
that one pytest worker loads the TPU's library (on-chip-measurement
guide, section 2).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _rows_by_vocab(compiled, rows, vocab):
    """Instructions of the compiled program's ENTRY computation, its
    parameters aside, whose result is `[..., rows, vocab]`: the head's
    product over every row of a prefill where one row's logits are
    kept (PERF.md, PR 46). A fusion's inner lines are never
    materialised and do not count; a re-laid copy of the head's table
    (hidden = rows = 2,048 in three families) does."""
    entry = compiled.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    return [line.strip()[:120] for line in entry.splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(?:\d+,)*" +
                        f"{rows},{vocab}\\]", line)
            and " parameter(" not in line]


def test_latent_decode_tick_updates_its_pool_in_place(one_chip,
                                                      no_compile_cache):
    """The continuous-batching engine's decode program over a paged
    latent pool (rows of 128 values: whole lanes, as the published
    model's 640): no copy, dynamic-slice or dynamic-update-slice of the
    stack's or of one layer's pool shape, and the donated pool aliased
    to the returned one. A row that is not whole lanes wide gets a
    transposed default layout and the program then copies the whole
    pool twice a tick (PERF.md, PR 26)."""
    from fengshen_tpu.models.joyai import JoyAIConfig, JoyAIForCausalLM
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    cfg = JoyAIConfig.small_test_config(dtype="bfloat16",
                                        param_dtype="bfloat16")
    model = JoyAIForCausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=8, buckets=(16, 32),
                                    max_new_tokens=16, kv_layout="paged",
                                    kv_block_size=16, kv_num_blocks=33))
    pool = eng._cache["model"]["cached_latent"]
    assert pool.shape == (3, 33, 16, 1, 128)
    args = _abstract((params, eng._cache, eng._history, eng._mask,
                      jnp.asarray(eng._last_tok), jnp.asarray(eng._pos),
                      jnp.asarray(eng._phys), jnp.asarray(eng._active),
                      eng._keys), one_chip)
    compiled = eng._decode_jit.lower(*args).compile()
    shapes = {pool.shape, (1,) + pool.shape[1:], pool.shape[1:]}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(", line)
        if m and tuple(int(d) for d in m.group(1).split(",") if d) \
                in shapes:
            found.append(line.strip()[:120])
    assert not found, found
    assert compiled.memory_analysis().alias_size_in_bytes >= pool.nbytes


def _tiny_model(kind):
    if kind == "latent":
        from fengshen_tpu.models.joyai import JoyAIConfig, JoyAIForCausalLM
        return JoyAIForCausalLM(JoyAIConfig.small_test_config(
            dtype="bfloat16", param_dtype="bfloat16"))
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=1024, intermediate_size=512,
        num_hidden_layers=3, num_attention_heads=8, num_key_value_heads=8,
        max_position_embeddings=128, dtype="bfloat16",
        param_dtype="bfloat16", scan_layers=True))


@pytest.mark.parametrize("kind", ["latent", "key_value"])
def test_assign_scatters_a_prompt_into_the_pool_in_place(one_chip,
                                                         no_compile_cache,
                                                         kind):
    """The engine's assign program (a prefilled prompt's rows into the
    lane's blocks, every layer in one scatter of whole blocks) for a
    one-leaf latent pool and for a scanned K/V pool: no copy or
    transpose of a pool-shaped array, the donated pool aliased to the
    returned one. A scatter mapped over the layers copied the whole
    one-head pool into another layout and back (PERF.md, PR 26). Rows
    are whole tiles here as at the published widths (8 KV heads of
    128; a latent row of 128): fewer heads get a layout of their own."""
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    model = _tiny_model(kind)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=8, buckets=(16, 32),
                                    max_new_tokens=16, kv_layout="paged",
                                    kv_block_size=16, kv_num_blocks=33))
    pools = [leaf for leaf in jax.tree_util.tree_leaves(eng._cache)
             if leaf.ndim == 5]
    assert len(pools) == (1 if kind == "latent" else 2)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    primed, _ = jax.eval_shape(
        eng._prefill_jit, params, i32(1, 32), i32(1, 32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    args = _abstract((eng._cache, eng._history, eng._mask, eng._last_tok,
                      primed), one_chip) + (
        i32(eng.seq_capacity), i32(eng.seq_capacity),
        i32(eng.max_blocks_per_slot), i32(), i32())
    compiled = eng._assign_jit.lower(*args).compile()
    shapes = {p.shape for p in pools}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"(copy|transpose)\(", line)
        if m and tuple(int(d) for d in m.group(1).split(",") if d) \
                in shapes:
            found.append(line.strip()[:120])
    assert not found, found
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        sum(p.nbytes for p in pools)


def test_routed_experts_compile_to_the_grouped_matmul_at_published_widths(
        one_chip, no_compile_cache):
    """64 tokens x 8 picks over 256 experts of 2048 x 768: the three
    products are the TPU's native ragged dot (custom calls), the
    program holds no `[tokens, experts, ...]` dispatch tensor, and its
    operations are those of 512 rows through ONE expert each, not of
    every row through all 256."""
    from fengshen_tpu.ops.moe import grouped_swiglu
    T, K, E, H, F = 64, 8, 256, 2048, 768
    bf16, sd = jnp.bfloat16, jax.ShapeDtypeStruct
    args = (sd((T, H), bf16, sharding=one_chip),
            sd((T, K), jnp.int32, sharding=one_chip),
            sd((T, K), jnp.float32, sharding=one_chip),
            sd((E, H, F), bf16, sharding=one_chip),
            sd((E, H, F), bf16, sharding=one_chip),
            sd((E, F, H), bf16, sharding=one_chip))
    compiled = jax.jit(grouped_swiglu).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"ragged-dot[\w.\-]* = [^\n]*custom-call\(",
                          text)) >= 3
    once = 2 * T * K * H * F * 3
    assert compiled.cost_analysis()["flops"] < 2 * once
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2 ** 20      # no [T, E, C]


@pytest.mark.parametrize("tokens,top_k,count,width", [
    (2048, 8, 128, 768), (2048, 10, 256, 512), (2048, 8, 256, 768),
    (16, 8, 128, 768), (64, 10, 256, 512), (64, 8, 256, 768)],
    ids=["keye_window", "qwen3next_window", "joyai_2048",
         "keye_tick", "qwen3next_tick", "joyai_tick"])
def test_routed_experts_compile_to_the_mosaic_grouped_matmul(
        one_chip, no_compile_cache, monkeypatch, tokens, top_k, count,
        width):
    """A prefill window's 2,048 tokens and a decode tick's 16 or 64 x
    top-k picks over the experts held, at the published widths (Keye
    128 tables of 2048 x 768, Qwen3-Next 256 of 512 held at 2048 x 512,
    JoyAI's 2,048 bucket and its tick over 256 of 2048 x 768): through
    the seam the three products are two Mosaic calls named
    `fstpu_moe_experts...` and no `ragged-dot`; the slots of gate and
    up fit the kernel's VMEM and every tile is aligned, or the compiler
    refuses here; the program's operations are those of each row
    through ONE expert, and it holds no `[tokens, experts, ...]`
    tensor."""
    import fengshen_tpu.ops.pallas as kernels
    from fengshen_tpu.ops.moe import EXPERTS_SCOPE, grouped_swiglu
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None,
                                            "described v5e"))
    hidden, bf16, sd = 2048, jnp.bfloat16, jax.ShapeDtypeStruct
    args = (sd((tokens, hidden), bf16, sharding=one_chip),
            sd((tokens, top_k), jnp.int32, sharding=one_chip),
            sd((tokens, top_k), jnp.float32, sharding=one_chip),
            sd((count, hidden, width), bf16, sharding=one_chip),
            sd((count, hidden, width), bf16, sharding=one_chip),
            sd((count, width, hidden), bf16, sharding=one_chip))
    compiled = jax.jit(lambda *a: grouped_swiglu(*a)).lower(*args).compile()
    took = kernels.traced_dispatch()[-1]
    assert took["impl"] == "pallas" and \
        f"rows=({tokens * top_k}, 2048)" in took["detail"], took
    text = compiled.as_text()
    calls = re.findall(r"%?(" + EXPERTS_SCOPE + r"[\w.\-]*) = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 2, calls
    assert "ragged-dot" not in text
    once = 2 * tokens * top_k * hidden * width * 3
    assert once <= compiled.cost_analysis()["flops"] < 3 * once
    dispatch = [line.strip()[:120] for line in text.splitlines()
                if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[" +
                            f"{tokens},{count}[,\\]]", line)]
    assert not dispatch, dispatch
    # the sorted rows, their products and the picks gathered back, all
    # bf16 and rows-shaped; none of them experts wide
    rows = tokens * top_k * hidden * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * rows


@pytest.mark.parametrize("cell,lanes", [
    ("joyai-llm-flash", 64), ("keye-vl-2.0-30b-a3b", 16)],
    ids=["joyai", "keye"])
def test_a_routed_models_decode_tick_reads_its_experts_through_the_kernel(
        one_chip, no_compile_cache, monkeypatch, cell, lanes):
    """The engine's decode program at the benchmark's widths and LANES
    (JoyAI 64 x top-8 over 256 tables, Keye 16 x top-8 over 128; a pool
    of 4 blocks a lane): each of the four expert layers' three products
    are the two Mosaic calls `fstpu_moe_experts_gate_up` / `_down`, and
    the program holds no `ragged-dot` (PERF.md, PR 42)."""
    import json
    import os

    import fengshen_tpu.ops.pallas as kernels
    from benchmarks.lib import manifest
    from fengshen_tpu.ops.moe import EXPERTS_SCOPE
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None,
                                            "described v5e"))
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           cell + ".json")) as f:
        config = json.load(f)
    model, _ = manifest.family(config).build(config)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=lanes, buckets=(256,), max_new_tokens=256,
        kv_layout="paged", kv_block_size=128,
        kv_num_blocks=lanes * 4 + 1, kv_max_blocks_per_slot=4))
    # the decisions of the tick alone, not of `model.init`'s 8 tokens
    monkeypatch.setattr(kernels, "_TRACED", {})
    tick = eng._decode_jit.lower(*_abstract(
        (params, eng._cache, eng._history, eng._mask,
         jnp.asarray(eng._last_tok), jnp.asarray(eng._pos),
         jnp.asarray(eng._phys), jnp.asarray(eng._active), eng._keys),
        one_chip)).compile()
    took = [d for d in kernels.traced_dispatch()
            if d["op"] == "grouped_matmul"]
    assert took and all(d["impl"] == "pallas" for d in took), took
    text = tick.as_text()
    calls = re.findall(r"%?(" + EXPERTS_SCOPE + r"_(?:gate_up|down)[\w.\-]*)"
                       r" = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert len(calls) == 8, calls
    assert "ragged-dot" not in text


def test_whole_prompt_prefill_holds_the_flash_call_and_no_scores_tensor(
        one_chip, no_compile_cache, monkeypatch):
    """The engine's 2048-bucket `prefill_fn` at Mistral-7B's attention
    geometry (32 heads of 128 over 8 KV heads, a 4,096-row scratch
    cache): attention is the Mosaic flash kernel over the prompt's own
    2,048 keys, and nothing in the program has the `[32, 2048, 4096]`
    shape of the scores over the cache's extent. At the benchmark's
    full configuration (16 layers, MLP 14336) the compiler's
    temporaries were 1.125 GB with that tensor and are 0.135 GB
    without (PERF.md, PR 29)."""
    import fengshen_tpu.ops.pallas as kernels
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    # the seam asks the backend, which is the CPU here: answer as the
    # described chip would
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None,
                                            "described v5e"))
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=4096, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=4096, dtype="bfloat16",
        param_dtype="bfloat16", scan_layers=True))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(2048,),
                                    max_new_tokens=16, kv_layout="paged",
                                    kv_block_size=128, kv_num_blocks=36))
    ids = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)
    compiled = eng._prefill_jit.lower(
        _abstract(params, one_chip), ids, ids,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    took = [d for d in kernels.traced_dispatch()
            if d["detail"].startswith("prefill q=(1, 2048, 32, 128)")]
    assert took and all(d["impl"] == "pallas" for d in took), took
    scores = [line.strip()[:120] for line in text.splitlines()
              if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[32,2048,4096\]",
                          line)]
    assert not scores, scores
    # the head projects the last row alone, not the bucket's 2,048
    assert not _rows_by_vocab(compiled, 2048, 256)
    # one f32[32, 2048, 4096] alone is 1,024 MiB
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


def test_sala_decode_tick_keeps_pool_and_state_in_place(one_chip,
                                                        no_compile_cache):
    """The engine's decode program over a pool of rows at two rates and
    a float32 state (`models/sala`, heads of 128 as published, a short
    stack): the K/V pool and the state stack are aliased to the
    returned ones and neither is copied whole; and the dense seam's
    GQA repeat of a whole lane (6.25 GB at the published sizes, PR 30)
    is nowhere: lanes within `dense_len` go through the sparse entry."""
    from fengshen_tpu.models.sala import SalaConfig, SalaForCausalLM
    from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                             EngineConfig)
    cfg = SalaConfig.small_test_config(
        hidden_size=256, intermediate_size=512, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, lightning_nh=4, lightning_nkv=4,
        lightning_head_dim=128, max_position_embeddings=4096,
        kernel_size=32, kernel_stride=16, block_size=64, topk=8,
        window_size=256, dense_len=1024, dtype="bfloat16",
        param_dtype="bfloat16")
    model = SalaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, shapes, EngineConfig(
        num_slots=8, buckets=(256,), max_new_tokens=16, kv_layout="paged",
        kv_block_size=128, max_queue=8))
    args = [_abstract(a, one_chip) for a in eng._decode_args(eng._active)]
    compiled = eng._decode_jit.lower(*args).compile()
    text = compiled.as_text()
    pool = eng._cache["model"]["cached_key"]
    state = eng._cache["model"]["state_lightning"]
    # nothing as large as the pool or the state is copied, in whatever
    # shape: a gather that took one KV head of a `[block, KVH, D]` block
    # had XLA:TPU re-lay the pool out as `[blocks * 2, 64, 2, 128]`,
    # eight times a tick (PERF.md, PR 30)
    for dims in re.findall(r"= \w+\[([\d,]+)\][^ ]* copy\(", text):
        size = 1
        for d in dims.split(","):
            size *= int(d)
        assert size < min(pool.size, state.size), dims
    stats = compiled.memory_analysis()
    held = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(eng._cache))
    assert stats.alias_size_in_bytes >= held
    # no [lanes, lane length, KVH, 16, D] repeat of a gathered lane
    assert f"[8,{cfg.max_position_embeddings},2,16,128]" not in text


def test_sala_window_program_compiles_without_cache_sized_scores(
        one_chip, no_compile_cache):
    """The window program (`jit_window_fn`) at heads of 128 over a
    4,096-row cache: it compiles for the chip, its batch-1 cache is
    donated and aliased, and no `[heads, window, cache]` score array
    exists (the sparse read walks the cache in tiles)."""
    from fengshen_tpu.models.sala import SalaConfig, SalaForCausalLM
    from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                             EngineConfig)
    cfg = SalaConfig.small_test_config(
        hidden_size=256, intermediate_size=512, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, lightning_nh=4, lightning_nkv=4,
        lightning_head_dim=128, max_position_embeddings=4096,
        kernel_size=32, kernel_stride=16, block_size=64, topk=8,
        window_size=256, dense_len=1024, lightning_chunk=256,
        dtype="bfloat16", param_dtype="bfloat16")
    model = SalaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, shapes, EngineConfig(
        num_slots=2, buckets=(1024,), max_new_tokens=16, kv_layout="paged",
        kv_block_size=128, max_queue=8))
    scratch = jax.eval_shape(eng._fresh_jit)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    compiled = eng._window_jit.lower(
        _abstract(shapes, one_chip), _abstract(scratch, one_chip),
        i32(1, 1024), i32(1, eng.seq_capacity), i32(), i32(),
        _abstract(eng._zero_key, one_chip)).compile()
    stats = compiled.memory_analysis()
    held = sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(scratch))
    assert stats.alias_size_in_bytes >= held
    assert not re.search(r"\[(1,)?32,1024,4096\]|\[2,16,1024,4096\]",
                         compiled.as_text())


@pytest.mark.parametrize("table_width, window, int8", [
    (10, 1, False), (17, 1, False), (10, 1, True), (10, 5, False)],
    ids=["chat", "doc", "chat_int8", "verify_window"])
def test_paged_decode_kernel_compiles_at_the_mistral_cells_shapes(
        one_chip, no_compile_cache, table_width, window, int8):
    """The paged decode kernel for the chip at the benchmark's Mistral
    cells' geometry: 32 lanes, 32 heads of 128 over 8 KV heads, the
    16-layer stack of 449 blocks of 128 tokens read in place through
    `layer`. Interpret mode cannot refuse what Mosaic refuses — the
    K/V operands left in HBM, the fetches through the table, the loop
    whose trip count is a lane's live blocks. The program is the one
    custom call and nothing the size of a layer's pool is copied."""
    from fengshen_tpu.ops.pallas.decode_attention import (
        TRACE_NAME, pallas_decode_attention)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    pool = shape((16, 449, 128, 8, 128), jnp.int8 if int8 else jnp.bfloat16)
    args = [shape((32, window, 32, 128), jnp.bfloat16), pool, pool,
            shape((32, window, table_width * 128), jnp.bool_),
            shape((32, table_width), jnp.int32), shape((), jnp.int32)]
    if int8:
        args += [shape((16, 449, 128, 8), jnp.float32)] * 2

    def call(q, k, v, valid, table, layer, k_scale=None, v_scale=None):
        return pallas_decode_attention(
            q, k, v, valid, block_table=table, layer=layer,
            k_scale=k_scale, v_scale=v_scale, dequant_dtype=jnp.bfloat16)
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and TRACE_NAME in text
    copies = [line.strip()[:120] for line in text.splitlines()
              if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(16,)?449,128,8,128\]"
                          r"[^=]* (copy|dynamic-slice)\(", line)]
    assert not copies, copies


@pytest.mark.parametrize("stacked", [True, False], ids=["stack", "pool"])
def test_folded_decode_kernel_compiles_at_the_qwen3next_cells_shapes(
        one_chip, no_compile_cache, stacked):
    """The folded kernel for the chip at `qwen3next_longchat_saturated`'s
    geometry: 64 lanes, 16 heads of 256 over 2 KV heads in one
    512-value row, 9,217 blocks of 128 tokens behind a 144-wide table,
    read in place through `layer` (and as a lone pool). Interpret mode
    cannot refuse what Mosaic refuses: pools left in HBM, fetches
    through the table, a trip count from a lane's cursor, half-row
    slices of the block. The program is the one custom call, found in
    the trace by the scope's name; nothing pool-sized is copied."""
    from fengshen_tpu.ops.gated_attention import DECODE_SCOPE
    from fengshen_tpu.ops.pallas.decode_attention import (
        folded_decode_attention)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    rows = (9217, 128, 1, 512)
    pool = shape(((1,) if stacked else ()) + rows, jnp.bfloat16)

    def call(q, k, v, table, t, layer):
        return folded_decode_attention(
            q, k, v, table, t, scale=256 ** -0.5,
            layer=layer if stacked else None, impl="pallas")
    text = jax.jit(call).lower(
        shape((64, 1, 16, 256), jnp.bfloat16), pool, pool,
        shape((64, 144), jnp.int32), shape((64,), jnp.int32),
        shape((), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and DECODE_SCOPE in text
    copies = [line.strip()[:120] for line in text.splitlines()
              if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(1,)?9217,128,"
                          r"(1,)?512\][^=]* (copy|dynamic-slice|transpose)\(",
                          line)]
    assert not copies, copies


@pytest.mark.parametrize("window", [1, 4], ids=["tick", "verify_window"])
def test_latent_decode_kernel_compiles_at_the_joyai_cells_shapes(
        one_chip, no_compile_cache, window):
    """The latent kernel for the chip at `joyai_reason_saturated`'s
    geometry: 64 lanes, 32 heads over one shared row of 640 (rank 512 +
    rope 64 + zeros), a `[5, 1537, ...]` stack of 128-token blocks
    behind a 24-wide table, read in place through a traced `layer`; one
    query a lane and a verify window of four. The program is the one
    custom call, found in the trace by the scope's name; nothing
    pool-sized is copied and no lane's 24 blocks are gathered."""
    from fengshen_tpu.ops.pallas.decode_attention import (
        MLA_TRACE_NAME, mla_decode_attention)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(q_latent, q_rope, kv, valid, table, layer):
        return mla_decode_attention(
            q_latent, q_rope, kv, valid, scale=192 ** -0.5,
            block_table=table, layer=layer, impl="pallas")
    text = jax.jit(call).lower(
        shape((64, window, 32, 512), jnp.bfloat16),
        shape((64, window, 32, 64), jnp.bfloat16),
        shape((5, 1537, 128, 1, 640), jnp.bfloat16),
        shape((64, window, 3072), jnp.bool_), shape((64, 24), jnp.int32),
        shape((), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and MLA_TRACE_NAME in text
    copies = [line.strip()[:120] for line in text.splitlines()
              if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(5,)?1537,128,"
                          r"(1,)?640\][^=]* (copy|dynamic-slice|transpose)\(",
                          line)]
    assert not copies, copies
    assert "[1536,128,640]" not in text


def test_joyai_decode_tick_reads_the_latent_pool_through_the_kernel(
        one_chip, no_compile_cache, monkeypatch):
    """The engine's decode program at the benchmark's widths, lanes and
    pool (64 lanes of 24 blocks, a `[5, 1537, 128, 1, 640]` stack): the
    seam sends each of the five layers' latent read to the Mosaic
    kernel by the pool's shape, the program holds five such calls by
    the scope's name and no `[1536, 128, 640]` gather of every lane's
    table row (252 MB a layer with the xla lowering, fifteen arrays of
    that shape in the parent's program; PERF.md, PR 43), and the stack
    is neither copied nor sliced."""
    import json
    import os

    import fengshen_tpu.ops.pallas as kernels
    from benchmarks.lib import manifest
    from fengshen_tpu.ops.pallas.decode_attention import MLA_TRACE_NAME
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None,
                                            "described v5e"))
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    model, _ = manifest.family(config).build(config)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=64, buckets=(256,), max_new_tokens=1024,
        kv_layout="paged", kv_block_size=128, kv_num_blocks=64 * 24 + 1,
        kv_max_blocks_per_slot=24))
    pool = eng._cache["model"]["cached_latent"]
    assert pool.shape == (5, 1537, 128, 1, 640)
    # the decisions of the tick alone, not of `model.init`'s 8 tokens
    monkeypatch.setattr(kernels, "_TRACED", {})
    tick = eng._decode_jit.lower(*_abstract(
        (params, eng._cache, eng._history, eng._mask,
         jnp.asarray(eng._last_tok), jnp.asarray(eng._pos),
         jnp.asarray(eng._phys), jnp.asarray(eng._active), eng._keys),
        one_chip)).compile()
    took = [d for d in kernels.traced_dispatch()
            if d["op"] == "mla_decode_attention"]
    assert took and all(d["impl"] == "pallas" for d in took), took
    text = tick.as_text()
    calls = re.findall(r"%?(" + MLA_TRACE_NAME + r"[\w.\-]*) = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 5, calls
    assert "[1536,128,640]" not in text
    assert not _big_copies(tick, {pool.shape, pool.shape[1:],
                                  (5 * 1537, 128, 640), (1537, 128, 640)})
    mem = tick.memory_analysis()
    assert mem.alias_size_in_bytes >= pool.nbytes
    assert mem.temp_size_in_bytes < 0.1e9      # 21 MB; 269 with the gather


def test_joyai_2048_bucket_projects_one_row_onto_the_vocabulary(
        one_chip, no_compile_cache, monkeypatch):
    """The whole-prompt program of JoyAI's 2,048 bucket at the
    benchmark's widths (4 lanes of 24 blocks instead of 64): the head's
    product is the last row's, so the program holds no `[2048, 129280]`
    logits (529 MB in bf16; 5.9 ms of the bucket, PERF.md PR 43) and no
    re-laid copy of the head's `[2048, 129280]` table."""
    import json
    import os

    import fengshen_tpu.ops.pallas as kernels
    from benchmarks.lib import manifest
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None,
                                            "described v5e"))
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    model, cfg = manifest.family(config).build(config)
    assert (cfg.hidden_size, cfg.vocab_size) == (2048, 129280)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=4, buckets=(2048,), max_new_tokens=1024,
        kv_layout="paged", kv_block_size=128, kv_num_blocks=4 * 24 + 1,
        kv_max_blocks_per_slot=24))
    ids = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)
    compiled = eng._prefill_jit.lower(
        _abstract(params, one_chip), ids, ids,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    assert not _rows_by_vocab(compiled, 2048, 129280)
    assert "[1,2048,129280]" not in compiled.as_text()


@pytest.mark.parametrize("seq, total, padded", [
    (2048, 36864, False), (2048, 4096, True), (256, 4096, True)],
    ids=["kimi_window", "joyai_2048", "joyai_256"])
def test_latent_prefill_kernel_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, seq, total, padded):
    """The full form's kernel for the chip at the two cells' geometry:
    32 heads of 128 + 64 and 128 over rows of 640 (rank 512), a window
    of 2,048 queries at a traced offset onto Kimi-Linear's lane of
    36,864 rows, and JoyAI's largest and smallest bucket onto its lane
    of 4,096 with the left padding as `key_valid`. The program is the
    one custom call under the scope's name; the lane is an operand as
    it lies (no copy, slice or transpose of it), nothing `[32, seq,
    total]` exists and nothing `[total, 32, 256]` either."""
    from fengshen_tpu.ops.latent_attention import PREFILL_SCOPE
    from fengshen_tpu.ops.pallas.latent_attention import (
        _ineligible_reason, pallas_latent_prefill_attention)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    args = [shape((1, seq, 32, 128)), shape((1, seq, 32, 64)),
            shape((1, total, 640)), shape((512, 32, 256)),
            shape((), jnp.int32)]
    assert _ineligible_reason(*args[:4]) is None
    if padded:
        args.append(shape((1, total), jnp.bool_))

    def call(q_nope, q_shared, rows, w_kvb, start, key_valid=None):
        return pallas_latent_prefill_attention(
            q_nope, q_shared, rows, w_kvb, start, key_valid=key_valid,
            scale=192 ** -0.5)
    compiled = jax.jit(call).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and PREFILL_SCOPE in text
    assert not _big_copies(compiled, {(1, total, 640), (total, 640)})
    wide = [line.strip()[:120] for line in text.splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[[\d,]*(" +
                        f"32,{seq},{total}|{total},32,256)", line)]
    assert not wide, wide
    # the padded query and the output, nothing the lane's size
    assert compiled.memory_analysis().temp_size_in_bytes < 40e6


def test_joyai_prefill_buckets_read_the_prompt_through_the_latent_kernel(
        one_chip, no_compile_cache, monkeypatch):
    """The whole-prompt programs of the JoyAI cell's four buckets at the
    benchmark's widths (4 lanes of 24 blocks instead of 64): every
    layer's full form takes the Mosaic kernel by the call's shape, with
    the bucket's left padding as `key_valid` and the batch-1 cache's
    4,096 rows as the lane; the 2,048 bucket's compiled program holds
    five such calls by the scope's name and no `[32, 2048, 4096]` score
    tensor (1 GB in float32: the dense chain's, PERF.md PR 43) and no
    `[4096, 32, 256]` expansion of the whole lane."""
    import json
    import os

    import fengshen_tpu.ops.pallas as kernels
    from benchmarks.lib import manifest
    from fengshen_tpu.ops.latent_attention import PREFILL_SCOPE
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None,
                                            "described v5e"))
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    model, _ = manifest.family(config).build(config)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    buckets = (256, 512, 1024, 2048)
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=4, buckets=buckets, max_new_tokens=1024,
        kv_layout="paged", kv_block_size=128, kv_num_blocks=4 * 24 + 1,
        kv_max_blocks_per_slot=24))
    monkeypatch.setattr(kernels, "_TRACED", {})
    for bucket in buckets:
        ids = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
        lowered = eng._prefill_jit.lower(
            _abstract(params, one_chip), ids, ids,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    # the prompts' own call sites (the abstract `init` of each
    # program's cache is one token wide and takes the walk, on record)
    took = [d for d in kernels.traced_dispatch()
            if d["op"] == "mla_prefill_attention" and
            "rows=(1, 4096, 640)" in d["detail"]]
    assert [(d["impl"], d["detail"]) for d in took] == [
        ("pallas", f"q=(1, {bucket}, 32, 128)+64:bfloat16 "
                   "rows=(1, 4096, 640):bfloat16 key_valid")
        for bucket in buckets]
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = re.findall(r"%?(" + PREFILL_SCOPE + r"[\w.\-]*) = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 5, calls
    wide = [line.strip()[:120] for line in text.splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[[\d,]*"
                        r"(32,2048,4096|4096,32,256)", line)]
    assert not wide, wide
    # 1.4 GB with the dense chain's scores and probabilities
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.fixture(scope="module")
def qwen3next_engine():
    """The benchmark's Qwen3-Next configuration at its full widths (one
    period, 256 of 512 experts held, half the vocabulary, 18,432
    positions) behind the engine, parameters as shapes, 8 lanes of 144
    blocks instead of 64: the three programs of its serving path."""
    import json
    import os

    from benchmarks.lib import manifest
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        config = json.load(f)
    model, cfg = manifest.family(config).build(config)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads,
            cfg.num_experts, cfg.experts_held) == (2048, 256, 2, 512,
                                                   (0, 256))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=8, buckets=(2048,), max_new_tokens=2048,
        kv_layout="paged", kv_block_size=128, kv_num_blocks=8 * 144 + 1,
        kv_max_blocks_per_slot=144))
    return eng, params


def _big_copies(compiled, shapes):
    """Lines of the compiled program that copy, transpose or slice an
    array of one of `shapes`."""
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"(copy|transpose|dynamic-slice)\(", line)
        if m and tuple(int(d) for d in m.group(1).split(",") if d) \
                in shapes:
            found.append(line.strip()[:120])
    return found


def _qwen3next_window_args(eng, params, one_chip):
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    fresh = jax.eval_shape(eng._fresh_jit)
    return (_abstract(params, one_chip), _abstract(fresh, one_chip),
            i32(1, 2048), i32(1, eng.seq_capacity), i32(), i32(),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))


def test_qwen3next_window_program_walks_the_keys_in_blocks(
        one_chip, no_compile_cache, qwen3next_engine):
    """A 2,048-token window onto the carried batch-1 cache of 18,432
    rows: no `[16, 2048, 18432]` score tensor (2.4 GB in float32), no
    copy of the cache's rows, the donated cache (rows and both states)
    aliased to the returned one."""
    eng, params = qwen3next_engine
    args = _qwen3next_window_args(eng, params, one_chip)
    compiled = eng._window_jit.lower(*args).compile()
    wide = [line.strip()[:120] for line in compiled.as_text().splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[[\d,]*(2048[\d,]*"
                        r"18432|18432[\d,]*2048)", line)]
    assert not wide, wide
    cache = args[1]["model"]
    rows = cache["cached_key"].shape
    assert rows == (1, 1, 18432, 1, 512)
    assert not _big_copies(compiled, {rows, rows[1:], rows[2:]})
    # the head projects the one row asked for: no `[2048, 75968]`
    # logits (311 MB in bf16), nor a re-laid copy of its table
    assert not _rows_by_vocab(compiled, 2048, eng.model.config.vocab_size)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.0e9              # 0.55 GB, PR 32
    held = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= held


def test_qwen3next_assign_and_tick_keep_pool_and_both_states_in_place(
        one_chip, no_compile_cache, qwen3next_engine, monkeypatch):
    """The assign program (a primed prompt's rows and BOTH states into a
    lane) and the decode tick over the paged pool: no copy, transpose or
    slice of a pool- or state-shaped array, the donated leaves aliased
    to the returned ones, and the tick's temporaries far under what K/V
    repeated per query head would take (8 lanes x 18,432 tokens x 16
    heads x 256 is 1.2 GB for K alone). The full layer's read is the
    folded kernel, chosen from the rows' shape: no gather of the live
    blocks (`[8 lanes, 1024 tokens, 512]` a step of the xla walk) is
    left in the tick."""
    import fengshen_tpu.ops.pallas as kernels
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None,
                                            "described v5e"))
    eng, params = qwen3next_engine
    tree = eng._cache["model"]
    assert tree["cached_key"].shape == (1, 8 * 144 + 1, 128, 1, 512)
    assert tree["state_delta"].shape == (3, 8, 32, 128, 128)
    assert tree["state_conv"].shape == (3, 8, 3, 8192)
    held = {name: tree[name] for name in
            ("cached_key", "cached_value", "state_delta", "state_conv")}
    shapes = {leaf.shape for leaf in held.values()}
    shapes |= {s[1:] for s in shapes}
    nbytes = sum(leaf.nbytes for leaf in held.values())
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    primed, _ = jax.eval_shape(
        eng._window_jit, *_qwen3next_window_args(eng, params, one_chip))
    assign = eng._assign_jit.lower(
        *_abstract((eng._cache, eng._history, eng._mask, eng._last_tok,
                    primed), one_chip),
        i32(eng.seq_capacity), i32(eng.seq_capacity),
        i32(eng.max_blocks_per_slot), i32(), i32()).compile()
    assert not _big_copies(assign, shapes)
    assert assign.memory_analysis().alias_size_in_bytes >= nbytes
    tick = eng._decode_jit.lower(*_abstract(
        (params, eng._cache, eng._history, eng._mask,
         jnp.asarray(eng._last_tok), jnp.asarray(eng._pos),
         jnp.asarray(eng._phys), jnp.asarray(eng._active), eng._keys),
        one_chip)).compile()
    assert not _big_copies(tick, shapes)
    mem = tick.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < 0.3e9
    took = [d for d in kernels.traced_dispatch()
            if d["op"] == "folded_decode_attention"]
    assert took and all(d["impl"] == "pallas" for d in took), took
    text = tick.as_text()
    kernel = [line for line in text.splitlines()
              if re.match(r"\s*%?fstpu_gated_attention_decode[\w.]* = ", line)]
    assert len(kernel) == 1 and "tpu_custom_call" in kernel[0], kernel
    assert not re.search(r"\[8,1024,512\]|\[64,128,512\]", text)


@pytest.fixture(scope="module")
def keye_engine():
    """The benchmark's Keye configuration at its full widths (4 layers,
    all 128 experts, the whole vocabulary, 33,280 positions) behind the
    engine, parameters as shapes, 4 lanes of 260 blocks instead of 16."""
    import json
    import os

    from benchmarks.lib import manifest
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        config = json.load(f)
    model, cfg = manifest.family(config).build(config)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads,
            cfg.num_experts, cfg.index_heads, cfg.index_head_dim,
            cfg.index_topk) == (2048, 128, 4, 128, 16, 64, 2048)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=4, buckets=(2048,), max_new_tokens=512,
        kv_layout="paged", kv_block_size=128, kv_num_blocks=4 * 260 + 1,
        kv_max_blocks_per_slot=260))
    return eng, params


def test_keye_tick_reads_three_kinds_of_row_in_place(one_chip,
                                                     no_compile_cache,
                                                     keye_engine):
    """The decode tick over the paged pool of K, V and indexer-key
    rows: no copy, transpose or slice of a K- or V-pool-shaped array (a
    pool is addressed by its own axes: merging its token axis or
    splitting a row into heads before the gather re-laid both K/V pools
    out, 2.3 GB of temporaries at 16 lanes; PERF.md, PR 36), the
    donated pool aliased to the returned one. Of the indexer keys' pool
    exactly TWO copies a tick are known and left (the 64-wide leaf is
    kept tokens-minor and re-laid out and back around the layers'
    writes, once for all four; a scatter into it viewed flat paid that
    every layer): a third would be a regression."""
    eng, params = keye_engine
    tree = eng._cache["model"]
    assert tree["cached_key"].shape == (4, 1041, 128, 1, 512)
    assert tree["cached_index_key"].shape == (4, 1041, 128, 1, 64)
    held = {name: tree[name] for name in
            ("cached_key", "cached_value", "cached_index_key")}
    shapes = {leaf.shape for leaf in held.values()}
    shapes |= {s[1:] for s in shapes}
    nbytes = sum(leaf.nbytes for leaf in held.values())
    tick = eng._decode_jit.lower(*_abstract(
        (params, eng._cache, eng._history, eng._mask,
         jnp.asarray(eng._last_tok), jnp.asarray(eng._pos),
         jnp.asarray(eng._phys), jnp.asarray(eng._active), eng._keys),
        one_chip)).compile()
    copies = _big_copies(tick, shapes)
    assert len(copies) == 2 and all("[4,1041,128,1,64]" in c
                                    for c in copies), copies
    mem = tick.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    # re-laid copies of the indexer keys' pool, 68 MB each at 4 lanes
    assert mem.temp_size_in_bytes < 0.2e9              # 0.15 GB, PR 36


def test_keye_window_program_scores_and_selects_in_tiles(
        one_chip, no_compile_cache, keye_engine):
    """A 2,048-token window onto the carried batch-1 cache of 33,280
    rows: no `[2048, 33280]` plane of scores or of the mask (273 MB in
    float32; a tile of 256 queries is the unit), the donated cache
    aliased to the returned one."""
    eng, params = keye_engine
    args = _qwen3next_window_args(eng, params, one_chip)
    compiled = eng._window_jit.lower(*args).compile()
    wide = [line.strip()[:120] for line in compiled.as_text().splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[[\d,]*(2048[\d,]*"
                        r"33280|33280[\d,]*2048)", line)]
    assert not wide, wide
    cache = args[1]["model"]
    assert cache["cached_index_key"].shape == (4, 1, 33280, 1, 64)
    # the head projects the one row asked for: no `[2048, 151936]`
    # logits (622 MB in bf16), nor a re-laid copy of its table
    assert eng.model.config.vocab_size == 151936
    assert not _rows_by_vocab(compiled, 2048, 151936)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.4e9     # 0.29 GB; 0.65 GB, PR 36
    held = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= held


@pytest.fixture(scope="module")
def trinity_engine():
    """The benchmark's Trinity configuration at its full widths (the
    published layers 5-9, 32 of 256 experts held, an eighth of the
    vocabulary, 33,792 positions) behind the engine, parameters as
    shapes, 4 lanes of 264 + 48 blocks instead of 16."""
    import json
    import os

    from benchmarks.lib import manifest
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "trinity-large-preview.json")) as f:
        config = json.load(f)
    model, cfg = manifest.family(config).build(config)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads,
            cfg.num_experts, cfg.experts_held, cfg.sliding_window) == (
        3072, 128, 8, 256, (0, 32), 4096)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=4, buckets=(2048,), max_new_tokens=1024,
        kv_layout="paged", kv_block_size=128, kv_num_blocks=4 * 264 + 1,
        kv_max_blocks_per_slot=264, kv_ring_num_blocks=4 * 48 + 1,
        kv_ring_blocks_per_slot=48))
    return eng, params


def test_trinity_assign_and_tick_keep_both_pools_in_place(
        one_chip, no_compile_cache, trinity_engine, monkeypatch):
    """The assign program and the decode tick over TWO pools (the full
    layer's rows behind the lane-long table, the four window layers' a
    ring behind a second one): no copy, transpose or slice of an array
    of either pool's shape, both donated pools aliased to the returned
    ones; with the backend's kernels on, both kinds of layer read
    through the paged Mosaic kernel (8 KV heads of 128: the Mistral
    cells' shape) and the experts stay on `ragged_dot`."""
    from fengshen_tpu.ops import pallas as kernels
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "aot"))
    eng, params = trinity_engine
    tree = eng._cache["model"]
    full, ring = tree["full_rows"], tree["window_rows"]
    assert full["cached_key"].shape == (1, 1057, 128, 8, 128)
    assert ring["cached_window_key"].shape == (4, 193, 128, 8, 128)
    assert full["block_table"].shape == (1, 4, 264)
    assert ring["block_table"].shape == (4, 4, 48)
    pools = [full["cached_key"], full["cached_value"],
             ring["cached_window_key"], ring["cached_window_value"]]
    shapes = {p.shape for p in pools} | {p.shape[1:] for p in pools}
    nbytes = sum(p.nbytes for p in pools)
    tick = eng._decode_jit.lower(*_abstract(
        (params, eng._cache, eng._history, eng._mask,
         jnp.asarray(eng._last_tok), jnp.asarray(eng._pos),
         jnp.asarray(eng._phys), jnp.asarray(eng._active), eng._keys),
        one_chip)).compile()
    assert not _big_copies(tick, shapes)
    assert tick.memory_analysis().alias_size_in_bytes >= nbytes
    text = tick.as_text()
    assert text.count("fstpu_decode_attention") >= 5
    # four expert layers x gate, up and down: tables of 3,072 x 3,072
    # outgrow the kernel's VMEM and 64 rows are not whole tiles
    assert len(re.findall(r"ragged-dot-none[\w.\-]* = [^\n]*custom-call\(",
                          text)) == 12
    assert "fstpu_moe_experts_gate_up" not in text
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    window_args = _qwen3next_window_args(eng, params, one_chip)
    primed, _ = jax.eval_shape(eng._window_jit, *window_args)
    assign = eng._assign_jit.lower(*(_abstract(
        (eng._cache, eng._history, eng._mask, eng._last_tok, primed),
        one_chip) + (i32(eng.seq_capacity), i32(eng.seq_capacity),
                     i32(264), i32(48), i32(), i32()))).compile()
    assert not _big_copies(assign, shapes)
    assert assign.memory_analysis().alias_size_in_bytes >= nbytes


def test_trinity_window_program_reads_a_band_and_walks_in_blocks(
        one_chip, no_compile_cache, trinity_engine):
    """A 2,048-token window onto the carried batch-1 cache of 33,792
    rows: no `[.., 2048, 33792]` score tensor in the full layer, no
    scores wider than a tile of 512 queries against 1,024 keys in the
    window layers, no copy of either kind's rows, the donated cache
    aliased to the returned one."""
    eng, params = trinity_engine
    args = _qwen3next_window_args(eng, params, one_chip)
    compiled = eng._window_jit.lower(*args).compile()
    wide = [line.strip()[:120] for line in compiled.as_text().splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[[\d,]*(2048[\d,]*"
                        r"33792|33792[\d,]*2048)", line)]
    assert not wide, wide
    cache = args[1]["model"]
    assert cache["window_rows"]["cached_window_key"].shape == \
        (4, 1, 33792, 8, 128)
    assert not _big_copies(compiled, {
        leaf.shape for leaf in jax.tree_util.tree_leaves(cache)
        if len(leaf.shape) == 5})
    assert not _rows_by_vocab(compiled, 2048, eng.model.config.vocab_size)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.0e9              # 0.57 GB, PR 41
    held = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= held


@pytest.fixture(scope="module")
def kimi_engine():
    """The benchmark's Kimi-Linear configuration at its full widths (the
    published layers 1-5, 128 of 256 experts held, half the vocabulary,
    36,864 positions) behind the engine, parameters as shapes, 8 lanes
    of 288 blocks instead of 64."""
    import json
    import os

    from benchmarks.lib import manifest
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    model, cfg = manifest.family(config).build(config)
    assert (cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim,
            cfg.latent_width, cfg.num_experts, cfg.experts_held) == (
        2304, 32, 128, 640, 256, (0, 128))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=8, buckets=(2048,), max_new_tokens=4096,
        kv_layout="paged", kv_block_size=128, kv_num_blocks=8 * 288 + 1,
        kv_max_blocks_per_slot=288))
    return eng, params


def test_kimi_tick_reads_the_latent_pool_through_the_kernel_in_place(
        one_chip, no_compile_cache, kimi_engine, monkeypatch):
    """The decode tick over ONE latent layer's paged rows beside four
    layers' two states a lane: no copy, transpose or slice of the pool
    or of a state stack, the donated cache aliased to the returned one;
    with the backend's kernels on, the latent read is PR 43's Mosaic
    kernel (JoyAI's row, 640 wide) and, at 8 lanes, 64 rows are not
    whole tiles for the grouped matmul (the cell's 64 lanes give 512)."""
    from fengshen_tpu.ops import pallas as kernels
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "aot"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    eng, params = kimi_engine
    tree = eng._cache["model"]
    assert tree["cached_latent"].shape == (1, 2305, 128, 1, 640)
    assert tree["state_delta"].shape == (4, 8, 32, 128, 128)
    assert tree["state_conv"].shape == (4, 8, 3, 12288)
    assert tree["block_table"].shape == (1, 8, 288)
    held = [tree["cached_latent"], tree["state_delta"], tree["state_conv"]]
    tick = eng._decode_jit.lower(*_abstract(
        (params, eng._cache, eng._history, eng._mask,
         jnp.asarray(eng._last_tok), jnp.asarray(eng._pos),
         jnp.asarray(eng._phys), jnp.asarray(eng._active), eng._keys),
        one_chip)).compile()
    assert not _big_copies(tick, {x.shape for x in held} |
                           {x.shape[1:] for x in held})
    mem = tick.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(x.nbytes for x in held)
    assert mem.temp_size_in_bytes < 0.1e9
    took = {(d["op"], d["impl"]) for d in kernels.traced_dispatch()}
    assert ("mla_decode_attention", "pallas") in took
    # a tick takes the delta rule's one-token step: the window's seam
    # is not asked
    assert "gated_delta_prefill" not in {op for op, _ in took}


def test_kimi_window_program_walks_the_latent_lane_in_blocks(
        one_chip, no_compile_cache, kimi_engine, monkeypatch):
    """A 2,048-token window onto the carried batch-1 cache of 36,864
    latent rows: no `[.., 2048, 36864]` score tensor (9.7 GB in float32
    over 32 heads), no `[36864, 32, 256]` expansion of the whole lane,
    no copy of the lane, the donated cache (rows and both states)
    aliased to the returned one; the full form is ONE Mosaic call (the
    seam's kernel, by the window's shape: the lane an operand as it
    lies in the cache), the four KDA layers' per-channel delta rule is
    four Mosaic calls of one lowering (the seam's kernel, by the gate's
    rank: no `solve_triangular` custom call and none of the `jax.numpy`
    form's `[1, 32, 32, 1, 64, 64]` systems is left in the program),
    the experts' products are the Mosaic grouped matmul (2 slots of
    2,304 x 1,024 under its VMEM budget)."""
    from fengshen_tpu.ops import pallas as kernels
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "aot"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    eng, params = kimi_engine
    args = _qwen3next_window_args(eng, params, one_chip)
    compiled = eng._window_jit.lower(*args).compile()
    text = compiled.as_text()
    wide = [line.strip()[:120] for line in text.splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[[\d,]*(2048[\d,]*"
                        r"36864|36864[\d,]*2048|36864,32,256)", line)]
    assert not wide, wide
    cache = args[1]["model"]
    rows = cache["cached_latent"].shape
    assert rows == (1, 1, 36864, 1, 640)
    assert not _big_copies(compiled, {rows, rows[1:], rows[2:]})
    assert not _rows_by_vocab(compiled, 2048, eng.model.config.vocab_size)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.0e9              # 0.55 GB, PR 45
    held = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= held
    assert len(re.findall(
        r"%?fstpu_mla_prefill_attention[\w.\-]* = [^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", text)) == 1
    assert text.count("fstpu_moe_experts_gate_up") >= 4
    sites = kernels.traced_dispatch()
    assert {"op": "mla_prefill_attention", "impl": "pallas",
            "detail": "q=(1, 2048, 32, 128)+64:bfloat16 "
                      "rows=(1, 36864, 640):bfloat16"} in sites
    assert len(re.findall(
        r"%?fstpu_gated_delta_prefill[\w.\-]* = [^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", text)) == 4
    assert "solve_triangular" not in text.lower()
    assert "TriangularSolve" not in text and "triangular-solve" not in text
    assert "f32[1,32,32,1,64,64]" not in text
    assert {"op": "gated_delta_prefill", "impl": "pallas",
            "detail": "q=(1, 2048, 32, 128):float32 v=(1, 2048, 32, 128):"
                      "bfloat16 g=(1, 2048, 32, 128)"} in sites
    # what else the record holds of this seam is the one-token pass
    # that shapes the cache
    assert all("shorter than a chunk" in d["detail"] for d in sites
               if d["op"] == "gated_delta_prefill" and d["impl"] == "xla")
    assert any(d["op"] == "grouped_matmul" and d["impl"] == "pallas" and
               "rows=(16384, 2304)" in d["detail"] for d in sites)


def test_sdar_block_tick_reads_a_blocks_queries_through_the_folded_kernel(
        one_chip, no_compile_cache, monkeypatch):
    """The engine's block tick at the benchmark's widths and lanes
    (`sdar-30b-a3b-chat`, two layers of it, a pool of 4 blocks a lane):
    the block's four queries a lane are ONE read of the folded Mosaic
    kernel a layer (`q=(64, 4, 32, 128)`: 32 query rows a KV head against
    the same fetched block), the 2,048 assignment rows go through the
    Mosaic grouped matmul, the pool is aliased to the returned one, and
    no `[lanes, kv heads x 8, ...]` repeat of a lane exists (the dense
    seam's lowering for 4 KV heads)."""
    import json
    import os

    import fengshen_tpu.ops.pallas as kernels
    from benchmarks.lib import manifest
    from fengshen_tpu.ops.gated_attention import DECODE_SCOPE
    from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None,
                                            "described v5e"))
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        config = json.load(f)
    config["num_hidden_layers"] = 2
    model, _ = manifest.family(config).build(config)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=64, buckets=(256,), max_new_tokens=256,
        kv_layout="paged", kv_block_size=128, kv_num_blocks=64 * 4 + 1,
        kv_max_blocks_per_slot=4, denoise_steps=2, remasking="sequential"))
    assert eng.block_length == 4
    monkeypatch.setattr(kernels, "_TRACED", {})
    tick = eng._decode_jit.lower(*_abstract(
        tuple(jnp.asarray(a) if isinstance(a, np.ndarray) else a
              for a in eng._decode_args(eng._active)), one_chip)).compile()
    took = {d["op"]: d for d in kernels.traced_dispatch()}
    assert took["folded_decode_attention"]["impl"] == "pallas"
    assert "q=(64, 4, 32, 128)" in took["folded_decode_attention"]["detail"]
    assert took["grouped_matmul"]["impl"] == "pallas"
    assert "rows=(2048, 2048)" in took["grouped_matmul"]["detail"]
    text = tick.as_text()
    reads = re.findall(r"%?(" + DECODE_SCOPE + r"[\w.\-]*) = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(reads) == 2, reads
    assert "ragged-dot" not in text
    pool = eng._cache["model"]["cached_key"]
    assert tick.memory_analysis().alias_size_in_bytes >= 2 * pool.nbytes
