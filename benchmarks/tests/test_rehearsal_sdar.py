"""`sdar_blockgen_saturated` end to end at a tiny size on the CPU, as
`test_rehearsal_keye.py` rehearses Keye's cell: the new family, mix,
reference, job kind and readers through the harness's own path. No time
here is a result."""

import importlib

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import device, manifest, traffic
from benchmarks.tests import expected, tiny_sdar

MAN = manifest.load()
CELL = "sdar_blockgen_saturated"
NEW = {"tokens_per_forward.blockgen", "commit_forward_share.blockgen",
       "block_attn_decode_roofline_share.blockgen",
       "block_attn_prefill_roofline_share.blockgen",
       "reveal_device_share.blockgen"}
#: the accepted readers of the experts, whose lists gained the cell
EXPERTS = {"moe_decode_roofline_share.longchat", "moe_device_share.longchat",
           "moe_experts_touched_share.longchat",
           "moe_expert_load_max_over_mean.longchat"}
COUNTERS = expected.counters(MAN, CELL)


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def execute(trace, seed=2 ** 31 + 23, seconds=2.0, control=None,
            obs_out=None):
    return run.execute(MAN, manifest.cell(MAN, CELL), tiny_sdar.sdar(),
                       tiny_sdar.blockgen(), tiny_sdar.SERVE_LIMITS, seed,
                       seconds, trace, control, obs_out)


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end_and_is_correct(trace):
    obs = {}
    result = execute(trace, obs_out=obs)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    e2e, per = manifest.metrics_of(MAN, CELL)
    if not trace:
        assert set(result["metrics"]) == {m["name"] for m in e2e} == {
            "serve_tokens_per_s", "setup_s"}
        # every output token is a tick's: the clients' count against the
        # engine's counter, not "less the first tokens"
        assert obs["token_count_gap"] <= 2 * 3 * 4
    else:
        # readers of device time find no device plane on a CPU and
        # return nothing; the counters are all there
        assert COUNTERS <= set(result["metrics"]) <= \
            {m["name"] for m in per}
        got = {k: v["value"] for k, v in result["metrics"].items()}
        # two tokens a reveal forward, three forwards a block of four,
        # less the tails and the cut last blocks
        assert 0.8 < got["tokens_per_forward.blockgen"] <= 4 / 3
        assert 33 <= got["commit_forward_share.blockgen"] < 50
        assert 0 < got["moe_experts_touched_share.longchat"] <= 100
        assert got["moe_expert_load_max_over_mean.longchat"] >= 1
        assert got["compiles_in_window"] == 0
        assert got["deferred_admissions.serve"] == 0


def test_the_cell_reports_its_five_metrics_and_the_accepted_ones():
    _, per = manifest.metrics_of(MAN, CELL)
    names = {m["name"] for m in per}
    assert {n for n in names if n.endswith(".blockgen")} == NEW
    assert {n for n in names if n.startswith("moe_")} == EXPERTS
    assert {"compile_s", "compiles_in_window", "runtime_start_s"} <= names
    # the folded kernel walks a lane's live blocks, but the accepted
    # `test_block_share_readers.py` pins that entry's list to two cells
    # (PERF.md section 7, from PR 41: a `benchmark` issue's to open)
    assert expected.common(MAN) - names == {"decode_live_block_share.serve"}
    assert {"tokens_per_forward.blockgen", "lane_occupancy.serve",
            "sched_taken_share.serve"} <= COUNTERS
    for n in names:
        assert callable(manifest.reader(n))
    for m in per:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
        if m["name"] in EXPERTS:
            assert m["workloads"][-1] == CELL
    assert len(MAN["per_layer"]) <= 128


def test_readers_find_nothing_without_the_programs_spans_and_counters():
    """On a program that lacks the new scopes, spans and counters (the
    parent) every new reader returns None and does not raise."""
    obs = {"cell": manifest.cell(MAN, CELL), "config": tiny_sdar.sdar(),
           "mix": tiny_sdar.blockgen(), "peaks": {}, "trace": None,
           "window": (0.0, 1.0), "stats_open": {}, "stats_close": {},
           "polls": [], "memory_peak_bytes": None}
    for name in NEW:
        assert manifest.reader(name)(obs) is None, name


def test_a_token_revealed_otherwise_is_not_correct(monkeypatch):
    """A program that reveals another token than the one its logits
    put first serves tokens the reference's logits put under its
    best."""
    from fengshen_tpu.serving import engine as engine_module
    real = engine_module._select_token

    def altered(logits, *args, **kw):
        return (real(logits, *args, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(engine_module, "_select_token", altered)
    result = execute(False)
    assert result["correct"] is False
    assert result["failed"] == 0


def test_the_job_hands_serve_http_its_own_check_back():
    from benchmarks.lib import check
    from benchmarks.lib.jobs import serve_http
    execute(False)
    assert serve_http.check is check


def test_the_reference_imports_nothing_of_the_program():
    for name in ("benchmarks.references.sdar", "benchmarks.lib.check_blocks"):
        with open(importlib.import_module(name).__file__) as f:
            assert "fengshen_tpu" not in f.read()


def test_reference_leaves_are_the_programs_leaves_at_the_published_size():
    """The seed fills leaves by path: the reference regenerates the
    program's weights only if both name and shape every leaf alike."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import costs_step, weights
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    family = manifest.family(config)
    model, _ = family.build(config)
    assert model.generation_block() == (4, 151669)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    program = {weights.path_str(p): (tuple(leaf.shape), leaf.dtype)
               for p, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    reference = importlib.import_module(family.REFERENCE).param_shapes(
        family.reference_config(config))
    assert program == {k: (tuple(s), jnp.dtype(d))
                       for k, (s, d) in reference.items()}
    n = sum(int(np.prod(s)) for s, _ in program.values())
    assert n == 4_361_055_744          # 8.72 GB in bf16: ISSUE 48
    # `step_mfu.serve` reads the step off these leaves with no code of
    # its own: attention and the router whole, 8 of 128 experts, a layer
    body, head = costs_step.weight_flops_per_token(reference, config)
    assert body == 2.0 * 6 * (18_874_368 + 262_144 + 8 * 4_718_592)
    assert head == 2.0 * 2048 * 151936


def test_the_configuration_keeps_every_published_number():
    import json
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    differs = {k for k, v in row["config"].items()
               if k not in config or config[k] != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert config["source"] == row["source_url"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "max_position_embeddings": 32768}
    assert (config["num_experts"], config["vocab_size"]) == (128, 151936)
    assert config["job"] == "serve_http_blocks"
    assert (config["assumed"]["block_length"],
            config["assumed"]["mask_token_id"]) == (4, 151669)
    assert set(config["assumed"]["why"]) >= {"block_length", "mask_token_id",
                                             "no shift", "block grid"}
    assert "stage 0" in config["deployment"]
    assert config["engine_args"]["kv_num_blocks"] == 64 * 33 + 1
    entry = next(c for c in MAN["configs"]
                 if c["name"] == "sdar-30b-a3b-chat")
    assert entry["reduced"] == config["reduced"]


def test_the_mix_is_the_issues_letter_for_letter():
    mix = traffic.load_mix("blockgen_closed_96")
    assert (mix["loop"], mix["clients"], mix["table_size"],
            mix["greedy"]) == ("closed", 96, 32, True)
    assert mix["prompt_len"] == {"dist": "log_uniform", "min": 256,
                                 "max": 2048}
    assert mix["output_len"] == {"dist": "log_uniform", "min": 512,
                                 "max": 2048}
    assert mix["engine_args"] == {
        "buckets": [2048], "max_new_tokens": 2048,
        "kv_max_blocks_per_slot": 33, "max_queue": 96, "denoise_steps": 2,
        "remasking": "sequential"}
    assert mix["ramp"] == {"stagger_s": 0.1, "open_after_completed": 128,
                           "every_lane_occupied": True, "min_s": 45}
    assert mix["check"] == {"sample": 4, "pad_to": 4224,
                            "own_matmul": "bf16"}
    table = traffic.request_table(mix)
    assert 850 < sum(p for p, _ in table) / 32 < 880
    assert 1100 < sum(o for _, o in table) / 32 < 1120
    assert max(p + o for p, o in table) <= 4224 - 4


def test_the_doubled_forward_is_the_naive_loop_a_block():
    """`check_blocks.revealed_logits` (one forward a denoising step over
    the clean sequence followed by the step's noised copy) against the
    naive loop: for every block and every step, a forward of the
    sequence up to that block with the block noised as the step sees
    it, under the plain block mask."""
    import jax

    from benchmarks.lib import check_blocks, weights
    from benchmarks.references import sdar as reference
    config = tiny_sdar.sdar()
    config["program"] = dict(config["program"], param_dtype="float32")
    cfg = manifest.family(config).reference_config(config)
    params = jax.jit(lambda key: weights.fill(
        key, reference.param_shapes(cfg)))(weights.base_key(5))
    L, mask_id = cfg["block_length"], cfg["mask_token_id"]
    rng = np.random.default_rng(3)
    for prompt_len, n_out, steps in ((9, 11, 2), (8, 8, 4), (3, 6, 1)):
        ids = np.zeros((32,), np.int64)
        ids[:prompt_len + n_out] = rng.integers(1, 120, prompt_len + n_out)
        got = check_blocks.revealed_logits(
            reference, cfg, "highest", params, ids, prompt_len, n_out,
            steps, rows=16)
        assert got.shape == (n_out, cfg["vocab_size"])
        for j in range(n_out):
            p = prompt_len + j
            start = p // L * L
            first_new = max(prompt_len, start)
            step = (p - first_new) // (L // steps)
            fed = ids.copy()
            fed[first_new + step * (L // steps):start + L] = mask_id
            fed[start + L:] = 0
            want = np.asarray(reference.forward_logits(
                cfg, "highest", params, fed, np.asarray([p])))[0]
            np.testing.assert_allclose(got[j], want, atol=2e-5, rtol=2e-4)
