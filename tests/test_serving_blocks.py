"""The engine's block tick (docs/serving.md "Block generation") on
`models/sdar` at a tiny size in float32: greedy output token-identical
to the plain reference's generation loop
(`benchmarks/references/sdar.generate`, which imports nothing of the
program), whatever tick and phase a lane was admitted at."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import sdar as family
from benchmarks.lib import weights
from benchmarks.references import sdar as reference
from fengshen_tpu.models.sdar import SdarConfig, SdarForCausalLM
from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                         EngineConfig)

#: what the reference reads of a configuration (the benchmark's list)
REFERENCE_KEYS = family.REFERENCE_KEYS + family.GENERATION_KEYS + (
    "param_dtype",)
L = 4


@pytest.fixture(scope="module")
def sdar():
    cfg = SdarConfig.small_test_config()
    model = SdarForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = weights.fill_like(weights.base_key(23), shapes)
    ref_cfg = {k: getattr(cfg, k) for k in REFERENCE_KEYS}
    return cfg, model, params, ref_cfg, weights.flat(params)


def engine_of(sdar, **kw):
    _, model, params, _, _ = sdar
    kw = {"num_slots": 3, "buckets": (8,), "max_new_tokens": 16,
          "kv_layout": "paged", "kv_block_size": 8, **kw}
    return ContinuousBatchingEngine(model, params, EngineConfig(**kw))


@pytest.fixture(scope="module")
def wanted(sdar):
    """The reference's tokens, computed once a (prompt, n, steps, rule)."""
    _, _, _, ref_cfg, flat = sdar
    made = {}

    def of(prompt, n_new, steps, remasking):
        key = (tuple(int(t) for t in prompt), n_new, steps, remasking)
        if key not in made:
            made[key] = reference.generate(ref_cfg, "highest", flat, prompt,
                                           n_new, steps, remasking)[0]
        return made[key]
    return of


def prompts_of(cfg, seed=0):
    """Eight prompts on five lanes' worth of ticks: tails 0-3, one
    shorter than a block, one of a whole block, one past the bucket (two
    windows), one that holds the mask token's id. Each with the output
    length that ends its sequence at 24 or 32 positions (the reference
    compiles once a length)."""
    rng = np.random.RandomState(seed)
    lengths = [9, 14, 3, 19, 8, 12, 4, 11]
    prompts = [rng.randint(1, cfg.vocab_size - 1, (n,)) for n in lengths]
    prompts[5][[2, 9]] = cfg.mask_token_id
    n_new = [24 - n - k for n, k in zip(lengths, [0, 1, 2, 3, 0, 5, 3, 2])]
    n_new[3] = 32 - 19 - 2
    return prompts, n_new


@pytest.mark.parametrize("remasking", ["sequential", "low_confidence"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_tokens_are_the_references(sdar, wanted, steps, remasking):
    """Eight requests through three lanes, tick-ahead: lanes are
    admitted at different ticks and phases, released and taken again."""
    cfg = sdar[0]
    prompts, n_new = prompts_of(cfg)
    eng = engine_of(sdar, denoise_steps=steps, remasking=remasking)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, n_new)]
    eng.run_until_idle()
    for req, p, n in zip(reqs, prompts, n_new):
        assert req.finish_reason == "length" and len(req.tokens) == n
        np.testing.assert_array_equal(req.tokens,
                                      wanted(p, n, steps, remasking))
    assert eng._decode_jit._cache_size() == 1       # one decode program
    assert eng._window_jit._cache_size() == 1
    assert eng._assign_jit._cache_size() == 1
    assert eng.stats()["kv_blocks_used"] == 0
    # commits deliver whole blocks: the first less the prompt's tail,
    # the last cut at max_new_tokens
    for req, p, n in zip(reqs, prompts, n_new):
        sizes = [e["n"] for e in eng.debug_request(req.request_id)["events"]
                 if e["event"] == "commit"]
        first = min(L - len(p) % L, n)
        assert sizes[0] == first and sum(sizes) == n
        assert all(s == L for s in sizes[1:-1]) and req.ttft_s is not None


@pytest.mark.parametrize("layout", ["paged", "slot"])
def test_step_and_the_tick_ahead_loop_agree(sdar, wanted, layout):
    cfg = sdar[0]
    prompts, n_new = prompts_of(cfg, seed=1)
    got = []
    for drive in ("step", "ahead"):
        eng = engine_of(sdar, denoise_steps=2, kv_layout=layout)
        reqs = [eng.submit(p, n) for p, n in zip(prompts, n_new)]
        if drive == "step":
            while eng.step() or not eng.idle():
                pass
        else:
            eng.run_until_idle()
        got.append([r.tokens for r in reqs])
    assert got[0] == got[1]
    for toks, p, n in zip(got[0], prompts, n_new):
        np.testing.assert_array_equal(toks, wanted(p, n, 2,
                                                   "low_confidence"))


@pytest.mark.parametrize("n_new", [1, 2, 5, 7])
def test_max_new_tokens_cuts_the_last_block(sdar, wanted, n_new):
    """`max_new_tokens` is not a multiple of the block (2: what the
    benchmark's bucket warm-up asks)."""
    cfg = sdar[0]
    prompt = np.random.RandomState(4).randint(1, 60, (10,))
    eng = engine_of(sdar, denoise_steps=2, remasking="sequential")
    req = eng.submit(prompt, n_new)
    eng.run_until_idle()
    assert req.finish_reason == "length" and len(req.tokens) == n_new
    full = wanted(prompt, 14, 2, "sequential")
    np.testing.assert_array_equal(req.tokens, full[:n_new])
    # forwards: the first block (2 masked of 4: one reveal, one commit),
    # then three a block
    blocks = -(-(2 + n_new) // L)
    counts = eng.metrics.registry
    from fengshen_tpu.observability import render_prometheus
    text = dict(line.split(" ") for line in render_prometheus(
        counts).splitlines() if line.startswith("fstpu_")
        and "{" not in line)
    assert int(float(text["fstpu_serving_block_forwards_total"])) == \
        2 + 3 * (blocks - 1)
    assert int(float(text["fstpu_serving_block_commit_forwards_total"])) \
        == blocks
    assert int(float(text["fstpu_serving_decode_tokens_total"])) == n_new
    assert int(float(text["fstpu_serving_prefill_head_rows_total"])) == 0
    # a tick's attention reads cursor + L keys a live lane
    per_block = [8 + L * b + L for b in range(blocks)]
    assert int(float(text["fstpu_serving_kv_tokens_attended_total"])) == \
        2 * per_block[0] + 3 * sum(per_block[1:])


def test_an_eos_inside_a_block_drops_the_blocks_tail(sdar, wanted):
    cfg = sdar[0]
    prompt = np.random.RandomState(6).randint(1, 60, (9,))
    full = list(wanted(prompt, 15, 4, "low_confidence"))
    eos = full[5]                       # position 9 + 5: inside block 3
    cut = full.index(eos) + 1
    eng = engine_of(sdar, eos_token_id=int(eos))
    req = eng.submit(prompt, 15)
    other = eng.submit(prompt[:7], 9)
    eng.run_until_idle()
    assert req.finish_reason == "eos" and req.tokens == full[:cut]
    assert other.finish_reason in ("length", "eos")
    assert eng.stats()["kv_blocks_used"] == 0


def test_a_stream_is_fed_a_block_at_a_time(sdar):
    eng = engine_of(sdar, denoise_steps=2)
    req = eng.submit(np.arange(1, 7), 9, stream=True)
    stream = eng.streams.get(req.request_id)
    seen = []
    while not req.done:
        eng.step()
        seen.append(len(req.tokens))
    grown = sorted(set(seen))
    assert grown == [0, 2, 6, 9]        # tail 2, then whole blocks, cut
    assert stream is not None and req.tokens == list(req.tokens)


def test_a_blocks_tokens_reach_the_socket_in_one_wakeup(sdar):
    """A commit publishes a block's tokens together, so the handler
    thread's account (docs/streaming.md "Observability") reads more
    than one token delivered a wake-up: 9 tokens in the three commits
    above."""
    from fengshen_tpu.api.main import _engine_stream

    class Pipe:
        encode = staticmethod(lambda text: [int(t) for t in text.split()])
        decode = staticmethod(lambda ids: " ".join(map(str, ids)))

    eng = engine_of(sdar, denoise_steps=2)
    eng.start()
    try:
        code, _, frames = _engine_stream(
            eng, Pipe, {"input_text": "1 2 3 4 5 6", "max_new_tokens": 9},
            60.0)
        assert code == 200
        sent = list(frames)
    finally:
        eng.stop()
    assert len(sent) == 9 + 1               # a frame a token, and `done`
    count = {k: eng.metrics.registry.get(f"fstpu_stream_{k}_total").value()
             for k in ("tokens", "tokens_delivered", "wakeups")}
    assert count["tokens_delivered"] == 9 == count["tokens"]
    assert 1 <= count["wakeups"] <= 3
    assert count["tokens_delivered"] / count["wakeups"] >= 3.0


@pytest.mark.parametrize("kw, why", [
    ({"spec_mode": "prompt_lookup"}, "draft window is causal"),
    ({"do_sample": True}, "sampled reveal is not built"),
    ({"repetition_penalty": 1.2}, "logits controls"),
    ({"min_length": 3}, "logits controls"),
    ({"kv_dtype": "int8"}, "rewritten every forward"),
    ({"denoise_steps": 3}, "must divide"),
    ({"buckets": (6,)}, "whole blocks"),
], ids=["spec", "sample", "penalty", "min_length", "int8", "steps",
        "buckets"])
def test_what_a_block_engine_refuses_at_construction(sdar, kw, why):
    with pytest.raises(ValueError, match=why):
        engine_of(sdar, **kw)


def test_handoff_and_resume_are_refused_with_their_reasons(sdar):
    from fengshen_tpu.serving import handoff
    eng = engine_of(sdar)
    req = eng.submit(np.arange(1, 10), 8)
    eng.step()
    with pytest.raises(handoff.HandoffError, match="inside a generation "
                                                   "block"):
        handoff.export_lane(eng, req.request_id)
    with pytest.raises(ValueError, match="a block at a time"):
        eng.submit(np.arange(1, 10), 8, resume_tokens=[3, 4])
    with pytest.raises(ValueError, match="unknown remasking"):
        EngineConfig(remasking="random")
    eng.run_until_idle()


def test_a_model_without_a_generation_block_keeps_the_plain_tick(sdar):
    """`block_length` 1 declares none: one token a lane a tick, causal,
    through the same folded read at one query; the lane is still filled
    from position 0 (the model reads positions as they lie)."""
    cfg = SdarConfig.small_test_config(block_length=1)
    model = SdarForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=2, buckets=(8,), max_new_tokens=4, kv_layout="paged",
        kv_block_size=8, denoise_steps=7))      # read by block engines only
    assert not eng.block_length and eng._from_zero
    assert eng._positional == []
    prompt = np.arange(1, 7)
    toks = eng.generate_all([prompt])[0]
    ids = list(prompt)
    for t in toks:      # greedy, causal: each token from a plain forward
        logits = model.apply({"params": params}, np.asarray(ids)[None])
        assert int(np.asarray(logits)[0, -1].argmax()) == t
        ids.append(t)
