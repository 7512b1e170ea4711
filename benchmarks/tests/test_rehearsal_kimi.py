"""`kimi_longreason_saturated` end to end at a tiny size on the CPU, as
`test_rehearsal_trinity.py` rehearses Trinity's cell: the new family,
mix, reference and readers through the harness's own path, a latent
layer's rows behind the block table beside two states a lane. What the
cell reports is derived from the manifest (`expected.py`). No time here
is a result."""

import importlib

import pytest

from benchmarks import run
from benchmarks.lib import device, manifest
from benchmarks.tests import expected, tiny_kimi

MAN = manifest.load()
CELL = "kimi_longreason_saturated"
NEW = {"kda_decode_roofline_share.longreason",
       "kda_prefill_roofline_share.longreason",
       "mla_decode_attn_roofline_share.longreason",
       "mla_prefill_attn_roofline_share.longreason",
       "moe_prefill_roofline_share.longreason",
       "mixer_device_share.longreason", "cache_bytes_share.longreason"}
COUNTERS = expected.counters(MAN, CELL)


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def execute(trace, seed=2 ** 31 + 31, seconds=2.0, control=None):
    return run.execute(MAN, manifest.cell(MAN, CELL), tiny_kimi.kimi(),
                       tiny_kimi.longreason(), tiny_kimi.SERVE_LIMITS,
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
        # one latent layer's rows and four layers' small states against
        # a row in all five layers
        assert 20 < got["cache_bytes_share.longreason"] < 100
        # the router routes over all 8 outputs, the chip holds 4
        assert 20 < got["moe_held_assignment_share.longchat"] < 80
        assert 0 < got["moe_experts_touched_share.longchat"] <= 100
        assert got["compiles_in_window"] == 0
        assert got["deferred_admissions.serve"] == 0


def test_the_cell_reports_its_seven_metrics_and_the_accepted_ones():
    _, per = manifest.metrics_of(MAN, CELL)
    names = {m["name"] for m in per}
    assert {n for n in names if n.endswith(".longreason")} == NEW
    # the experts' entries are the `.longchat` ones (the same `ops/moe`)
    assert {"compile_s", "compiles_in_window", "runtime_start_s"} | {
        m["name"] for m in MAN["per_layer"]
        if m["name"].startswith("moe_") and
        m["name"].endswith(".longchat")} <= names
    # the paged Mosaic latent kernel does walk each lane's live blocks
    # here, but an accepted test pins that entry's cells and a PR that
    # adds a cell may not edit it (PERF.md section 7)
    assert expected.common(MAN) - names == {"decode_live_block_share.serve"}
    assert {"cache_bytes_share.longreason", "lane_occupancy.serve",
            "moe_held_assignment_share.longchat",
            "sched_taken_share.serve"} <= COUNTERS
    for n in names:
        assert callable(manifest.reader(n))
    for m in expected.by_cell(MAN, CELL):
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"


def test_readers_find_nothing_without_the_programs_spans_and_counters():
    """On a program that lacks the new scopes, spans and counters (the
    parent) every new reader returns None and does not raise."""
    obs = {"cell": manifest.cell(MAN, CELL), "config": tiny_kimi.kimi(),
           "mix": tiny_kimi.longreason(), "peaks": {}, "trace": None,
           "window": (0.0, 1.0), "stats_open": {}, "stats_close": {},
           "polls": [], "memory_peak_bytes": None}
    for m in expected.by_cell(MAN, CELL):
        assert manifest.reader(m["name"])(obs) is None, m["name"]


def test_the_cache_share_reads_the_polls_by_hand():
    """Two polls inside the window (a third before it is left out): 20
    and 30 blocks of 32 tokens in use, 2 and 3 live lanes."""
    from benchmarks.lib import costs_kimi
    cfg = tiny_kimi.kimi()
    obs = {"config": cfg, "window": (10.0, 20.0),
           "polls": [(5.0, 99, 24, 3, 0), (12.0, 20, 24, 2, 0),
                     (18.0, 30, 24, 3, 0)]}
    got = manifest.reader("cache_bytes_share.longreason")(obs)
    # a row of 40 values padded to 128 x 2 B; states 4 x 16 x 16 x 4 B +
    # 3 x 192 x 2 B a KDA layer, four of them
    rows = 25 * 32 * 256
    states = 2.5 * 4 * (4096 + 1152)
    assert got == pytest.approx(100.0 * (rows + states) / (5 * rows))
    assert got == pytest.approx(100.0 * costs_kimi.cache_bytes_share(
        25, 2.5, 32, cfg))


def test_the_job_hands_serve_http_its_own_check_back():
    from benchmarks.lib import check
    from benchmarks.lib.jobs import serve_http
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    assert config["job"] in ("serve_http", "serve_http_mean",
                             "serve_http_paired")
    execute(False)
    assert serve_http.check is check


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
    module = importlib.import_module("benchmarks.references.kimi_linear")
    with open(module.__file__) as f:
        text = f.read()
    assert "fengshen_tpu" not in text
    # KDA is the recurrence itself, a scan over tokens
    assert "jax.lax.scan(token" in text and "solve_triangular" not in text


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
    assert n == 4_282_936_192          # 8.57 GB in bf16: ISSUE 45


def test_the_configuration_keeps_every_published_width():
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    published = dict(
        hidden_size=2304, intermediate_size=9216, moe_intermediate_size=1024,
        num_attention_heads=32, num_key_value_heads=32, head_dim=72,
        kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, mla_use_nope=True,
        num_experts_per_token=8, num_shared_experts=1,
        routed_scaling_factor=2.446, moe_renormalize=True,
        moe_router_activation_func="sigmoid", first_k_dense_replace=1,
        rms_norm_eps=1e-5, rope_theta=10000, model_max_length=1048576,
        num_nextn_predict_layers=0, tie_word_embeddings=False)
    assert {k: config[k] for k in published} == published
    lin = config["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert (lin["kda_layers"], lin["full_attn_layers"]) == ([1, 2, 3, 5], [4])
    assert config["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size", "max_position_embeddings"]
    pub = config["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"],
            pub["max_position_embeddings"]) == (27, 256, 163840, 1048576)
    assert pub["linear_attn_config"]["full_attn_layers"] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert len(pub["linear_attn_config"]["kda_layers"]) == 20
    # the share: the router keeps its published width, half is held
    assert config["router_width"] == 256
    assert config["experts_held"] == [0, 128] and config["num_experts"] == 128
    assert config["vocab_size"] * 2 == pub["vocab_size"]
    assert "2" in config["deployment"] and set(config) >= {
        "assumed", "not_built", "reduced_why", "deployment"}
    # 64 lanes x 288 blocks + the null block
    assert config["engine_args"]["kv_num_blocks"] == 64 * 288 + 1


def test_the_mix_is_the_issues_letter_for_letter():
    from benchmarks.lib import traffic
    mix = traffic.load_mix("longreason_closed_96")
    assert (mix["loop"], mix["clients"], mix["table_size"],
            mix["greedy"]) == ("closed", 96, 32, True)
    assert mix["prompt_len"] == {"dist": "log_uniform", "min": 2048,
                                 "max": 32768}
    assert mix["output_len"] == {"dist": "log_uniform", "min": 1024,
                                 "max": 4096}
    assert mix["engine_args"] == {
        "buckets": [2048], "max_new_tokens": 4096,
        "kv_max_blocks_per_slot": 288, "max_queue": 96}
    assert mix["pairing"] == traffic.load_mix(
        "longchat_closed_96")["pairing"]
    assert (mix["check"]["sample"], mix["check"]["pad_to"]) == (4, 36864)
    assert mix["ramp"]["open_after_completed"] == 64
    assert mix["ramp"]["every_lane_occupied"] is True
