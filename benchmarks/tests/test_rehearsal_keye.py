"""`keye_longctx_saturated` end to end at a tiny size on the CPU, as
`test_rehearsal_sala.py` rehearses SALA's cell: the new family, mix,
reference and readers through the harness's own path. No time here is a
result."""

import importlib

import pytest

from benchmarks import run
from benchmarks.lib import device, manifest
from benchmarks.tests import expected, tiny_keye

MAN = manifest.load()
CELL = "keye_longctx_saturated"
NEW = {"index_selected_share.longctx", "index_select_device_share.longctx",
       "index_score_roofline_share.longctx",
       "indexed_prefill_attn_roofline_share.longctx",
       "indexed_decode_attn_roofline_share.longctx",
       "mixer_device_share.longctx"}
COUNTERS = expected.counters(MAN, CELL)


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def execute(trace, seed=2 ** 31 + 17, seconds=2.0, control=None):
    return run.execute(MAN, manifest.cell(MAN, CELL), tiny_keye.keye(),
                       tiny_keye.longctx(), tiny_keye.SERVE_LIMITS, seed,
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
        # every prompt is past the tiny topk: 16 of 100-208 cached tokens
        assert 7 < got["index_selected_share.longctx"] < 17
        assert got["compiles_in_window"] == 0
        assert got["deferred_admissions.serve"] == 0


def test_the_cell_reports_its_six_metrics_and_the_accepted_ones():
    _, per = manifest.metrics_of(MAN, CELL)
    names = {m["name"] for m in per}
    assert {n for n in names if n.endswith(".longctx")} == NEW
    # the experts' entries are the `.longchat` ones (the same `ops/moe`)
    assert {"compile_s", "compiles_in_window", "runtime_start_s",
            "moe_decode_roofline_share.longchat",
            "moe_experts_touched_share.longchat"} <= names
    # no Mosaic kernel walks live blocks here (the indexed read is xla)
    assert expected.common(MAN) - names == {"decode_live_block_share.serve"}
    assert {"index_selected_share.longctx", "lane_occupancy.serve",
            "sched_taken_share.serve"} <= COUNTERS
    for n in names:
        assert callable(manifest.reader(n))
    assert len(MAN["per_layer"]) <= 128


def test_readers_find_nothing_without_the_programs_spans_and_counters():
    """On a program that lacks the new scopes, spans and counters (the
    parent) every new reader returns None and does not raise."""
    obs = {"cell": manifest.cell(MAN, CELL), "config": tiny_keye.keye(),
           "mix": tiny_keye.longctx(), "peaks": {}, "trace": None,
           "window": (0.0, 1.0), "stats_open": {}, "stats_close": {},
           "polls": [], "memory_peak_bytes": None}
    for name in NEW:
        assert manifest.reader(name)(obs) is None, name


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
    module = importlib.import_module("benchmarks.references.keye")
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
    assert n == 3_123_858_944          # 6.25 GB in bf16: ISSUE 36


def test_the_configuration_keeps_every_published_number():
    import json
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert config["source"] == row["source_url"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "max_position_embeddings": 262144}
    assert (config["num_experts"], config["vocab_size"]) == (128, 151936)
    assert config["job"] == "serve_http_paired"
    entry = next(c for c in MAN["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == config["reduced"]


def test_the_job_hands_serve_http_its_own_check_back():
    """`serve_http_paired` lends `serve_http` the paired statistic for
    one run only: the other serving cells keep `lib.check`'s."""
    from benchmarks.lib import check
    from benchmarks.lib.jobs import serve_http
    execute(False)
    assert serve_http.check is check


def test_the_paired_gap_by_hand():
    """Two served tokens, one the reference's best, one 0.3 under it
    (mean 0.15); the reference's own bf16 picks read 0 and 0.1 (mean
    0.05): 0.10 decides, and the control is read against the same
    floor."""
    import numpy as np

    from benchmarks.lib import check_paired

    class Reference:
        @staticmethod
        def param_shapes(cfg):
            return {}

        @staticmethod
        def forward_logits(cfg, matmul, params, ids, rows):
            out = np.zeros((len(rows), 4), np.float32)
            out[0] = [0.0, 1.0, 0.2, 0.0]       # row P-1 scores token 0
            out[1] = [0.5, 0.4, 0.2, 0.0]       # row P scores token 1
            if matmul == "bf16":
                out[1, 1] = 0.6                 # its own bf16 says 1, then 1
            if matmul == "int8":
                out[0, 3] = 2.0                 # the control says 3, then 2
                out[1, 2] = 2.0
            return out

    finished = [{"index": 0, "prompt_len": 5, "output_len": 2,
                 "tokens": [1, 2]}]
    spec = {"sample": 1, "pad_to": 16, "rows": 4, "limit": 0.12,
            "own_matmul": "bf16"}
    got = check_paired.served_gap(Reference, {}, 7, finished, spec, 4,
                                  "int8")
    (what, value, limit, ok), = got["numbers"]
    assert abs(value - (0.15 - 0.05)) < 1e-6 and ok and limit == 0.12
    assert abs(got["control"] - ((1.0 + 0.3) / 2 - 0.05)) < 1e-6
    assert got["tokens"] == 2
    assert abs(got["per_request"][0] - value) < 1e-6
    spec["limit"] = 0.05
    assert not check_paired.served_gap(Reference, {}, 7, finished, spec,
                                       4)["numbers"][0][3]
