"""`trinity_mixedlen_saturated` end to end at a tiny size on the CPU, as
`test_rehearsal_keye.py` rehearses Keye's cell: the new family, mix,
reference and readers through the harness's own path, a ring beside the
lane-long table. What the cell reports is derived from the manifest
(`expected.py`). No time here is a result."""

import importlib

import pytest

from benchmarks import run
from benchmarks.lib import device, manifest
from benchmarks.tests import expected, tiny_trinity

MAN = manifest.load()
CELL = "trinity_mixedlen_saturated"
NEW = {"window_decode_attn_roofline_share.mixedlen",
       "full_decode_attn_roofline_share.mixedlen",
       "window_prefill_attn_roofline_share.mixedlen",
       "full_prefill_attn_roofline_share.mixedlen",
       "moe_prefill_roofline_share.mixedlen",
       "window_attended_share.mixedlen", "kv_ring_bytes_share.mixedlen",
       "mixer_device_share.mixedlen"}
COUNTERS = expected.counters(MAN, CELL)


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def execute(trace, seed=2 ** 31 + 29, seconds=2.0, control=None):
    return run.execute(MAN, manifest.cell(MAN, CELL), tiny_trinity.trinity(),
                       tiny_trinity.mixedlen(), tiny_trinity.SERVE_LIMITS,
                       seed, seconds, trace, control)


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
        # the tiny window 64 of 43-208 cached tokens
        assert 30 < got["window_attended_share.mixedlen"] < 100
        # 4 ring layers of at most 3 blocks beside 1 full layer of up to
        # 7: under what one table for all five would hold
        assert 20 < got["kv_ring_bytes_share.mixedlen"] <= 100
        assert got["compiles_in_window"] == 0
        assert got["deferred_admissions.serve"] == 0
        assert 0 < got["moe_held_assignment_share.longchat"] < 100


def test_the_cell_reports_its_eight_metrics_and_the_accepted_ones():
    _, per = manifest.metrics_of(MAN, CELL)
    names = {m["name"] for m in per}
    assert {n for n in names if n.endswith(".mixedlen")} == NEW
    # the experts' entries are the `.longchat` ones (the same `ops/moe`)
    assert {"compile_s", "compiles_in_window", "runtime_start_s"} | {
        m["name"] for m in MAN["per_layer"]
        if m["name"].startswith("moe_") and
        m["name"].endswith(".longchat")} <= names
    # the paged Mosaic kernel does walk each lane's live blocks here
    # (31-32 % of the lane-long table on the chip), but an accepted
    # test pins that entry's cells and a PR that adds a cell may not
    # edit it (PERF.md section 7)
    assert expected.common(MAN) - names == {"decode_live_block_share.serve"}
    assert {"window_attended_share.mixedlen", "kv_ring_bytes_share.mixedlen",
            "lane_occupancy.serve", "sched_taken_share.serve"} <= COUNTERS
    for n in names:
        assert callable(manifest.reader(n))
    assert len(MAN["per_layer"]) <= 128
    assert [m["name"] for m in expected.by_cell(MAN, CELL)]


def test_readers_find_nothing_without_the_programs_spans_and_counters():
    """On a program that lacks the new scopes, spans and counters (the
    parent) every new reader returns None and does not raise."""
    obs = {"cell": manifest.cell(MAN, CELL),
           "config": tiny_trinity.trinity(), "mix": tiny_trinity.mixedlen(),
           "peaks": {}, "trace": None, "window": (0.0, 1.0),
           "stats_open": {}, "stats_close": {}, "polls": [],
           "memory_peak_bytes": None}
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
    module = importlib.import_module("benchmarks.references.trinity")
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
    assert n == 4_321_903_872          # 8.64 GB in bf16: ISSUE 41


def test_the_configuration_keeps_every_published_number():
    import json
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size", "max_position_embeddings"}
    assert config["source"] == row["source_url"]
    assert {k: v for k, v in config["published"].items()
            if k != "layer_types"} == {
        k: row["config"][k] for k in config["reduced"]
        if k != "layer_types"}
    # the cut: the published layers 5-9 of the pattern, a share of eight
    assert config["layer_types"] == row["config"]["layer_types"][5:10]
    assert config["num_dense_layers"] == 1      # layer 5 of 0-5
    assert config["experts_held"] == [0, 32] and \
        config["router_width"] == row["config"]["num_experts"]
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert len(config["assumed"]["why"]) >= 7
    entry = next(c for c in MAN["configs"]
                 if c["name"] == "trinity-large-preview")
    assert entry["reduced"] == config["reduced"]


def test_the_cells_parameters_are_the_issues():
    """ISSUE 41, Tentpole 7, letter for letter."""
    from benchmarks.lib import traffic
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-preview", "mixedlen_closed_24", 1)
    mix = traffic.load_mix(cell["traffic"])
    assert (mix["loop"], mix["clients"], mix["table_size"],
            mix["greedy"]) == ("closed", 24, 32, True)
    assert mix["prompt_len"] == {"dist": "log_uniform", "min": 1024,
                                 "max": 32768}
    assert mix["output_len"] == {"dist": "log_uniform", "min": 128,
                                 "max": 1024}
    assert mix["engine_args"] == {
        "buckets": [2048], "max_new_tokens": 1024,
        "kv_max_blocks_per_slot": 264, "kv_ring_blocks_per_slot": 48,
        "max_queue": 24}
    assert mix["ramp"] == traffic.load_mix("longctx_closed_24")["ramp"]
    assert (mix["check"]["sample"], mix["check"]["pad_to"]) == (4, 33792)
    config = manifest.config_of(MAN, cell)
    assert config["engine_args"] == {
        "num_slots": 16, "kv_layout": "paged", "kv_dtype": "fp32",
        "kv_block_size": 128, "kv_num_blocks": 16 * 264 + 1,
        "kv_ring_num_blocks": 16 * 48 + 1}
    # no request outgrows the lane
    assert max(p + o for p, o in traffic.request_table(mix)) <= \
        config["max_position_embeddings"] == 264 * 128
