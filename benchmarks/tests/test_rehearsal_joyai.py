"""`joyai_reason_saturated` end to end at a tiny size on the CPU, as
`test_rehearsal.py` rehearses the other cells: the new family, mix,
reference and readers through the harness's own path. No time here is a
result."""

import importlib

import pytest

from benchmarks import run
from benchmarks.lib import device, manifest
from benchmarks.tests import expected, tiny_joyai

MAN = manifest.load()
CELL = "joyai_reason_saturated"
COUNTERS = expected.counters(MAN, CELL)
OWN = {"moe_experts_touched_share.reason",
       "moe_expert_load_max_over_mean.reason",
       "moe_decode_roofline_share.reason",
       "mla_decode_attn_roofline_share.reason"}


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def execute(trace, seed=2 ** 31 + 11, seconds=2.0, control=None):
    return run.execute(MAN, manifest.cell(MAN, CELL), tiny_joyai.joyai(),
                       tiny_joyai.reason(), tiny_joyai.SERVE_LIMITS, seed,
                       seconds, trace, control)


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end_and_is_correct(trace):
    result = execute(trace)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    e2e, per = manifest.metrics_of(MAN, CELL)
    if not trace:
        assert set(result["metrics"]) == {m["name"] for m in e2e} == {
            "serve_tokens_per_s", "setup_s"}
    else:
        # readers of device time find no device plane on a CPU and
        # return nothing; the counters are all there
        assert COUNTERS <= set(result["metrics"]) <= \
            {m["name"] for m in per}
        got = {k: v["value"] for k, v in result["metrics"].items()}
        assert 0 < got["moe_experts_touched_share.reason"] <= 100
        assert 1 <= got["moe_expert_load_max_over_mean.reason"] <= 8
        assert got["compiles_in_window"] == 0


def test_the_cell_reports_the_common_entries_and_its_own():
    _, per = manifest.metrics_of(MAN, CELL)
    names = {m["name"] for m in per}
    assert {n for n in names if n.endswith(".reason")} == OWN
    # no Mosaic kernel walks live blocks here (the latent read is xla)
    assert expected.common(MAN) - names == {"decode_live_block_share.serve"}
    assert {"moe_experts_touched_share.reason", "lane_occupancy.serve",
            "sched_taken_share.serve"} <= COUNTERS
    assert {"compile_s", "compiles_in_window", "runtime_start_s"} <= names
    for n in names:
        assert callable(manifest.reader(n))


def test_the_control_runs_and_the_reference_says_what_it_judged(capsys):
    """The control's path through the harness with a reference that
    abstains (`pick_margin`): a line a request says how many rows were
    judged, fewer than asked, and the control's number is reported. At
    this size no precision moves a served token (14 tokens, 256 words):
    that the control FAILS is a reading at the published widths
    (limits/joyai_reason_saturated.json)."""
    import json
    import re
    result = execute(False, control="int8")
    assert result["correct"] is True
    said = capsys.readouterr().out
    counts = [tuple(map(int, m.groups())) for m in re.finditer(
        r"joyai reference: judges (\d+) of (\d+) rows", said)]
    assert len(counts) == 3 and all(0 < a <= b for a, b in counts)
    assert sum(a for a, _ in counts) < sum(b for _, b in counts)
    line, = [x for x in said.splitlines() if "control (int8): " in x]
    control = json.loads(line.split("control (int8): ", 1)[1])
    assert control["control"] >= 0 and len(control["per_request"]) == 3


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from fengshen_tpu.serving import engine as engine_module
    real = engine_module._select_token

    def altered(logits, *args, **kw):
        return (real(logits, *args, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(engine_module, "_select_token", altered)
    result = execute(False)
    assert result["correct"] is False
    assert result["failed"] == 0


def test_the_reference_imports_nothing_of_the_program():
    module = importlib.import_module("benchmarks.references.joyai")
    with open(module.__file__) as f:
        assert "fengshen_tpu" not in f.read()


def test_reference_leaves_are_the_programs_leaves_at_the_published_size():
    """The seed fills leaves by path: the reference regenerates the
    program's weights only if both name and shape every leaf alike."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import weights
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    family = manifest.family(config)
    model, _ = family.build(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    program = {weights.path_str(p): (tuple(leaf.shape), leaf.dtype)
               for p, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    reference = importlib.import_module(family.REFERENCE).param_shapes(
        family.reference_config(config))
    assert program == {k: (tuple(s), jnp.dtype(d))
                       for k, (s, d) in reference.items()}
    n = sum(int(jnp.prod(jnp.asarray(s))) for s, _ in program.values())
    assert n == 5_558_141_952          # 11.12 GB in bf16: ISSUE 26


def test_the_configuration_keeps_every_published_width():
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    published = dict(
        hidden_size=2048, intermediate_size=7168, moe_intermediate_size=768,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_attention_heads=32,
        n_routed_experts=256, num_experts_per_tok=8, n_shared_experts=1,
        vocab_size=129280, routed_scaling_factor=2.5, rope_theta=32000000)
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "max_position_embeddings": 131072}
