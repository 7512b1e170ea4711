"""The lowered decode, window and assign programs of the serving
families. Those whose cache declares NO ring are, byte for byte, what
they were before the pool learned a ring (PR 41): `serving/paged_cache.py` and the engine
took a second table and a second free list, and a model without
`cached_window_*` leaves must not see either. The hashes are of
`jit(...).lower(...).as_text()` at tiny sizes on the CPU, taken on the
commit PR 41 started from and equal on its own tree; a PR that changes
one of these programs on purpose replaces its hash here and says so.

PR 42 replaced none: the seam that sends the routed experts' decode
ticks to the Mosaic grouped matmul decides by the call's shape ON A TPU
(`tests/test_compile_for_v5e.py` pins those programs: eight Mosaic
calls in JoyAI's and Keye's tick, twelve `ragged-dot` in Trinity's);
off it every call stays `ragged_dot`, and nothing else moved. It added
Trinity's three programs (a ring and its table beside the lane-long
one), taken on the commit PR 42 started from and equal on its tree.

PR 44 added `<family>.prefill`, the whole-prompt program of a bucket
(`_prefill_jit`: every admission of a prompt the ladder holds), for the
families whose cache admits one: llama and joyai. The caches of sala,
qwen3_next, keye and trinity declare positional leaves (a state, a
ring, an indexer's keys: `engine._positional`), so every prompt of
theirs goes by windows and they have no such program. Taken on the
commit PR 44 started from and equal on its tree: the two prefill
bodies and the six decoder skeletons (ROADMAP D13, D14) are merged
against these.

PR 45 replaced none: `ops/gated_delta.py` took a second gate shape (one
log-decay a key channel) beside the scalar one, and a scalar-gated call
lowers to the program it lowered to (Qwen3-Next's three); what
`models/joyai`, `models/sala` and `models/qwen3_next` shared moved to
`models/model_utils.py` under the same bodies. It added Kimi-Linear's
three programs (a latent row a token in one layer of five beside two
states a lane in the others; a positional cache, so no whole-prompt
program), taken on its own tree.

PR 46 replaced the seven `*.window` and the two `*.prefill` hashes ON
PURPOSE: a prefill program now tells the model which row's logits it
keeps (`logits_row`; `models/model_utils.head_rows`), and the model
slices its hidden states to that row BEFORE the head, so the head's
product is `[1, 1, vocab]` where it was `[1, width, vocab]` with one
row kept (`tests/test_logits_row.py` pins the equality of that row and
the shape). The seven `*.decode` and seven `*.assign` hashes are the
ones PR 45 left: no tick and no assignment changed, which is the proof
that `logits_row=None` is the program it was.

PR 47 replaced `joyai.prefill`, `joyai.window` and `kimi_linear.window`
ON PURPOSE: the full form of latent attention is one seam
(`ops/latent_attention.latent_prefill_attention`), and JoyAI's
prefill reads rows `0 .. start + S` of its lane in blocks under an
online softmax (off a TPU: `latent_prefill_walk`, with the left
padding as `key_valid`) where it expanded all `max_position_embeddings`
rows into dense scores; Kimi-Linear's window calls the same walk
through the seam (the mask gained a batch axis for `key_valid`, nothing
else). `tests/test_joyai.py` and `tests/test_kimi_linear.py` pin the
logits against the old chain and the old call. Both models' `*.decode`
and `*.assign` hashes are the ones PR 45 left: the absorbed tick and
`write_latent` did not change.

PR 48 replaced none: the engine took a FOURTH decode program, the block
tick, for a model that declares a generation block
(`generation_block()`: `models/sdar`), with its own window and assign
programs (no head, no first token; a lane's first block) — and a model
that declares none is handed the programs it was handed. The folded
entry of the decode seam took `S <= 8` queries a lane that share one
extent by folding them into the query heads before the `S = 1` body
(Qwen3-Next's three stand); `write_rows` moved from `models/sala` to
`models/model_utils.py` under the same body (sala, qwen3_next, keye). It
added SDAR's three programs, taken on its own tree."""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                         EngineConfig)

SHA = {
    "llama.decode": "5a1add90e836d82a",
    "llama.window": "1ce1b0090b6e086c",
    "llama.assign": "13aa0c5a11ba3006",
    "llama.prefill": "647bd6cb7a07bc79",
    "joyai.decode": "3f1780b6a2d39053",
    "joyai.window": "c7e8f7acd2adacda",
    "joyai.assign": "96810c12d7c41873",
    "joyai.prefill": "2f56076b0be4f486",
    "sala.decode": "8aba89589fbc0375",
    "sala.window": "61d348bea48383be",
    "sala.assign": "7805764a91b2a79d",
    "qwen3_next.decode": "c7d5fcadf09da984",
    "qwen3_next.window": "18f7cbcf1d4a3705",
    "qwen3_next.assign": "b290aa7ede5bb08c",
    "keye.decode": "ebf5fa33299f43a1",
    "keye.window": "c215eb2d7712672e",
    "keye.assign": "2162ac0abf6da2ca",
    "trinity.decode": "f500c48b548b710b",
    "trinity.window": "0ea2aa37da139612",
    "trinity.assign": "c63004b277bfeb20",
    "kimi_linear.decode": "9d90cc775e211cad",
    "kimi_linear.window": "f774c9a50075f2f8",
    "kimi_linear.assign": "6b9219d02e589a96",
    "sdar.decode": "600c739fd19fe820",
    "sdar.window": "53e244f9d49b9f57",
    "sdar.assign": "e7b099ef2620a96f",
}


def _model(family):
    if family == "llama":
        from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            scan_layers=True))
    if family == "joyai":
        from fengshen_tpu.models.joyai import JoyAIConfig, JoyAIForCausalLM
        return JoyAIForCausalLM(JoyAIConfig.small_test_config())
    if family == "sala":
        from fengshen_tpu.models.sala import SalaConfig, SalaForCausalLM
        return SalaForCausalLM(SalaConfig.small_test_config())
    if family == "qwen3_next":
        from fengshen_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                    Qwen3NextForCausalLM)
        return Qwen3NextForCausalLM(Qwen3NextConfig.small_test_config())
    if family == "kimi_linear":
        from fengshen_tpu.models.kimi_linear import (KimiLinearConfig,
                                                     KimiLinearForCausalLM)
        return KimiLinearForCausalLM(KimiLinearConfig.small_test_config())
    if family == "sdar":
        from fengshen_tpu.models.sdar import SdarConfig, SdarForCausalLM
        return SdarForCausalLM(SdarConfig.small_test_config())
    if family == "trinity":
        from fengshen_tpu.models.trinity import (TrinityConfig,
                                                 TrinityForCausalLM)
        return TrinityForCausalLM(TrinityConfig.small_test_config())
    from fengshen_tpu.models.keye import KeyeConfig, KeyeForCausalLM
    return KeyeForCausalLM(KeyeConfig.small_test_config())


@pytest.fixture(scope="module")
def lowered():
    """{family: {program: text}}, each family's engine built once."""
    made = {}

    def of(family):
        if family in made:
            return made[family]
        model = _model(family)
        params = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
        eng = ContinuousBatchingEngine(model, params, EngineConfig(
            num_slots=2, buckets=(16,), max_new_tokens=8,
            kv_layout="paged", kv_block_size=16))
        assert bool(eng.ring_blocks) == (family == "trinity")
        sds = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        if family == "sdar":
            # the block tick's programs: whole blocks, no head, no
            # first token; a lane's first block and its flags
            window_args = (params, jax.eval_shape(eng._fresh_jit),
                           i32(1, 16), i32(), i32())
            primed = jax.eval_shape(eng._window_jit, *window_args)
            block = eng.block_length
            assign_args = sds((eng._cache, eng._block_tokens,
                               eng._block_masked, primed)) + (
                i32(block), jax.ShapeDtypeStruct((block,), jnp.bool_),
                i32(eng.max_blocks_per_slot), i32())
        else:
            window_args = (
                params, jax.eval_shape(eng._fresh_jit), i32(1, 16),
                i32(1, eng.seq_capacity), i32(), i32(),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            primed, _ = jax.eval_shape(eng._window_jit, *window_args)
            assign_args = sds((eng._cache, eng._history, eng._mask,
                               eng._last_tok, primed)) + (
                i32(eng.seq_capacity), i32(eng.seq_capacity),
                i32(eng.max_blocks_per_slot)) + (
                (i32(eng.ring_blocks),) if eng.ring_blocks else ()) + (
                i32(), i32())
        made[family] = {
            "decode": eng._decode_jit.lower(
                *sds(eng._decode_args(eng._active))).as_text(),
            "window": eng._window_jit.lower(*window_args).as_text(),
            "assign": eng._assign_jit.lower(*assign_args).as_text()}
        # a cache with positional leaves, and a block grid, take every
        # prompt by windows
        assert eng._from_zero == (f"{family}.prefill" not in SHA)
        if not eng._from_zero:
            made[family]["prefill"] = eng._prefill_jit.lower(
                params, i32(1, 16), i32(1, 16),
                jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
        return made[family]
    return of


@pytest.mark.parametrize("name", sorted(SHA))
def test_a_serving_program_is_unchanged(lowered, name):
    family, program = name.split(".")
    text = lowered(family)[program]
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == SHA[name]
