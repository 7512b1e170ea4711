"""`sala_longdoc_saturated` end to end at a tiny size on the CPU, as
`test_rehearsal_joyai.py` rehearses JoyAI's cell: the new family, mix,
reference and readers through the harness's own path. No time here is a
result."""

import importlib

import pytest

from benchmarks import run
from benchmarks.lib import device, manifest
from benchmarks.tests import expected, tiny_sala

MAN = manifest.load()
CELL = "sala_longdoc_saturated"
COUNTERS = expected.counters(MAN, CELL)


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def execute(trace, seed=2 ** 31 + 17, seconds=2.0, control=None):
    return run.execute(MAN, manifest.cell(MAN, CELL), tiny_sala.sala(),
                       tiny_sala.longdoc(), tiny_sala.SERVE_LIMITS, seed,
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
        # every prompt is past the tiny dense_len: 6 blocks of 16 of
        # 100-208 cached tokens
        assert 40 < got["sparse_attended_share.longdoc"] < 100
        assert got["compiles_in_window"] == 0
        assert got["deferred_admissions.serve"] == 0


def test_the_cell_reports_the_common_entries_and_its_own():
    _, per = manifest.metrics_of(MAN, CELL)
    names = {m["name"] for m in per}
    assert {m["name"] for m in MAN["per_layer"]
            if m["name"].endswith(".longdoc")} <= names
    # no Mosaic kernel walks live blocks here (the sparse read is xla)
    assert expected.common(MAN) - names == {"decode_live_block_share.serve"}
    assert {"sparse_attended_share.longdoc", "lane_occupancy.serve",
            "sched_taken_share.serve"} <= COUNTERS
    assert {"compile_s", "compiles_in_window", "runtime_start_s"} <= names
    for n in names:
        assert callable(manifest.reader(n))


def test_readers_find_nothing_without_the_programs_spans_and_counters():
    """On a program that lacks the new scopes, spans and counters (the
    parent) every new reader returns None and does not raise."""
    obs = {"cell": manifest.cell(MAN, CELL), "config": tiny_sala.sala(),
           "mix": tiny_sala.longdoc(), "peaks": {}, "trace": None,
           "window": (0.0, 1.0), "stats_open": {}, "stats_close": {},
           "polls": [], "memory_peak_bytes": None}
    for m in expected.by_cell(MAN, CELL):
        assert manifest.reader(m["name"])(obs) is None, m["name"]


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
    module = importlib.import_module("benchmarks.references.sala")
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
    assert n == 5_039_448_064          # 10.08 GB in bf16: ISSUE 30


def test_the_configuration_keeps_every_published_width():
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    published = dict(
        hidden_size=4096, intermediate_size=16384, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, lightning_nh=32,
        lightning_nkv=32, lightning_head_dim=128, vocab_size=73448,
        scale_emb=12, scale_depth=1.4, dim_model_base=256, rope_theta=10000,
        mup_denominator=32, rms_norm_eps=1e-6)
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers", "mixer_types",
                                 "max_position_embeddings"]
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["max_position_embeddings"] == 524288
    # the cut is the published entries 9-24, one sparse layer in four
    assert config["mixer_types"] == config["published"]["mixer_types"][9:25]
    assert config["mixer_types"].count("minicpm4") == 4
    assert config["residual_depth"] == 32
    assert set(config["assumed"]) >= {
        "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
        "window_size", "dense_len", "why"}
