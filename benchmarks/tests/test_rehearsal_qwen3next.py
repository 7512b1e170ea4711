"""`qwen3next_longchat_saturated` end to end at a tiny size on the CPU,
as `test_rehearsal_sala.py` rehearses SALA's cell: the new family, mix,
reference and readers through the harness's own path. No time here is a
result."""

import importlib

import pytest

from benchmarks import run
from benchmarks.lib import device, manifest
from benchmarks.tests import expected, tiny_qwen3next

MAN = manifest.load()
CELL = "qwen3next_longchat_saturated"
COUNTERS = expected.counters(MAN, CELL)


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def execute(trace, seed=2 ** 31 + 17, seconds=2.0, control=None):
    return run.execute(MAN, manifest.cell(MAN, CELL),
                       tiny_qwen3next.qwen3next(), tiny_qwen3next.longchat(),
                       tiny_qwen3next.SERVE_LIMITS, seed, seconds, trace,
                       control)


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
        # the router routes over all 8 outputs, the chip holds 4
        assert 20 < got["moe_held_assignment_share.longchat"] < 80
        assert 0 < got["moe_experts_touched_share.longchat"] <= 100
        assert got["compiles_in_window"] == 0
        assert got["deferred_admissions.serve"] == 0


def test_the_cell_reports_the_common_entries_and_its_own():
    _, per = manifest.metrics_of(MAN, CELL)
    names = {m["name"] for m in per}
    # every `.longchat` entry is this cell's (Keye's cell shares the
    # experts' five) and every common entry lists it
    assert {m["name"] for m in MAN["per_layer"]
            if m["name"].endswith(".longchat")} <= names
    assert expected.common(MAN) <= names
    assert {"moe_held_assignment_share.longchat", "lane_occupancy.serve",
            "decode_live_block_share.serve"} <= COUNTERS
    assert {"compile_s", "compiles_in_window", "runtime_start_s"} <= names
    for n in names:
        assert callable(manifest.reader(n))


def test_readers_find_nothing_without_the_programs_spans_and_counters():
    """On a program that lacks the new scopes, spans and counters (the
    parent) every new reader returns None and does not raise."""
    obs = {"cell": manifest.cell(MAN, CELL),
           "config": tiny_qwen3next.qwen3next(),
           "mix": tiny_qwen3next.longchat(), "peaks": {}, "trace": None,
           "window": (0.0, 1.0), "stats_open": {}, "stats_close": {},
           "polls": [], "memory_peak_bytes": None}
    for m in expected.by_cell(MAN, CELL):
        assert manifest.reader(m["name"])(obs) is None, m["name"]


def test_the_job_hands_serve_http_its_own_check_back():
    """`serve_http_mean` lends `serve_http` the mean statistic for one
    run only: the other serving cells keep `lib.check`'s."""
    from benchmarks.lib import check
    from benchmarks.lib.jobs import serve_http
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    assert config["job"] == "serve_http_mean"
    execute(False)
    assert serve_http.check is check


def test_the_mean_gap_by_hand():
    """Two served tokens: one the reference's best, one 0.3 under it:
    mean 0.15 decides, the control's tokens are read the same way."""
    import numpy as np

    from benchmarks.lib import check_mean

    class Reference:
        @staticmethod
        def param_shapes(cfg):
            return {}

        @staticmethod
        def forward_logits(cfg, matmul, params, ids, rows):
            out = np.zeros((len(rows), 4), np.float32)
            out[0] = [0.0, 1.0, 0.2, 0.0]       # row P-1 scores token 0
            out[1] = [0.5, 0.0, 0.2, 0.0]       # row P scores token 1
            if matmul == "int8":
                out[0, 3] = 2.0                 # the control says 3, then 2
                out[1, 2] = 2.0
            return out

    finished = [{"index": 0, "prompt_len": 5, "output_len": 2,
                 "tokens": [1, 2]}]
    spec = {"sample": 1, "pad_to": 16, "rows": 4, "limit": 0.2}
    got = check_mean.served_gap(Reference, {}, 7, finished, spec, 4, "int8")
    (what, value, limit, ok), = got["numbers"]
    assert abs(value - 0.15) < 1e-6 and ok and limit == 0.2
    assert abs(got["control"] - (1.0 + 0.3) / 2) < 1e-6
    assert got["tokens"] == 2 and got["per_request"] == [value]
    spec["limit"] = 0.1
    assert not check_mean.served_gap(Reference, {}, 7, finished, spec,
                                     4)["numbers"][0][3]


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
    module = importlib.import_module("benchmarks.references.qwen3_next")
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
    assert n == 3_677_613_120          # 7.36 GB in bf16: ISSUE 32


def test_the_configuration_keeps_every_published_width():
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    published = dict(
        hidden_size=2048, intermediate_size=5120, num_attention_heads=16,
        num_key_value_heads=2, head_dim=256, partial_rotary_factor=0.25,
        rope_theta=10000000, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        num_experts_per_tok=10, norm_topk_prob=True, rms_norm_eps=1e-6,
        full_attention_interval=4, tie_word_embeddings=False)
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "max_position_embeddings"]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "max_position_embeddings": 262144}
    # the share: the router keeps its published width, half is held
    assert config["router_width"] == 512
    assert config["experts_held"] == [0, 256] and config["num_experts"] == 256
    assert config["vocab_size"] * 2 == config["published"]["vocab_size"]
    assert "2" in config["deployment"] and set(config) >= {
        "assumed", "not_built", "reduced_why", "deployment"}
