"""bench.py harness guard: every mode must produce its one JSON line on
the CPU mesh with tiny env shapes (the CPU asked for by name — the
harness refuses any other way of having no accelerator). A harness
regression (bad flag wiring, broken lever path) must fail HERE, not on
the chip.
"""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

pytestmark = pytest.mark.slow

TINY = {"BENCH_SEQ": "64", "BENCH_VOCAB": "256", "BENCH_HIDDEN": "64",
        "BENCH_INTER": "128", "BENCH_LAYERS": "2", "BENCH_HEADS": "4",
        "BENCH_BATCH": "2", "BENCH_ATTN": "dense"}


def _run_bench(monkeypatch, env: dict) -> dict:
    import importlib

    import bench

    for key in list(os.environ):
        if key.startswith("BENCH_"):
            monkeypatch.delenv(key)
    for key, val in {**TINY, **env}.items():
        monkeypatch.setenv(key, val)
    importlib.reload(bench)
    out = io.StringIO()
    with redirect_stdout(out):
        bench.main()
    lines = [l for l in out.getvalue().splitlines() if l.startswith("{")]
    assert lines, out.getvalue()
    row = json.loads(lines[-1])
    assert set(row) >= {"metric", "value", "unit", "vs_baseline"}
    assert row["value"] > 0
    # every row names the device that produced it
    assert (row["platform"], row["device_kind"]) == ("cpu", "cpu")
    assert row["device_count"] == 8
    return row


def test_bench_default_mode(monkeypatch):
    row = _run_bench(monkeypatch, {})
    assert row["metric"] == "llama300m_train_tokens_per_sec_per_chip"


def test_bench_default_levers(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_INT8_LMHEAD": "1",
                                   "BENCH_FUSED_CE": "4"})
    # the int8 lever changes numerics, so its row carries its own name
    assert row["metric"] == \
        "llama300m_int8_train_tokens_per_sec_per_chip"


def test_bench_lora_lever(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_LORA": "2"})
    assert row["metric"] == "llama300m_lora_train_tokens_per_sec_per_chip"


def test_bench_sharded_and_offload(monkeypatch):
    """Seed-failing until ISSUE 9: the offload row hard-coded
    pinned_host and raised at sharding construction on this backend.
    The capability probe (docs/offload.md) resolves the host kind, and
    the row records the RESOLVED placement so benchdiff never compares
    across placements."""
    from fengshen_tpu.trainer.memory import probe_memory_capabilities

    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "sharded",
                                   "BENCH_FSDP": "2", "BENCH_TP": "2",
                                   "BENCH_OFFLOAD": "1"})
    assert row["metric"] == \
        "llama300m_offload_update_tokens_per_sec_per_chip"
    assert row["offload"] == "opt"
    assert row["memory_kind"] == probe_memory_capabilities().host_kind


def test_bench_sharded_offload_opt_master(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "sharded",
                                   "BENCH_OFFLOAD": "opt_master"})
    assert row["metric"] == \
        "llama300m_offload_update_tokens_per_sec_per_chip"
    assert row["offload"] == "opt_master"


def test_bench_sharded_offload_auto_matches_plain_row(monkeypatch):
    """Acceptance (ISSUE 9): a small-shape rung at --offload=auto is
    within 5% tokens/s of --offload=none. On a shape that fits, auto
    resolves to level "none" and runs the IDENTICAL fused step program
    — the row keeps the base metric name and carries no placement
    fields, so the <5% bar holds by construction (same program, and
    benchdiff treats the rows as directly comparable)."""
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "sharded",
                                   "BENCH_OFFLOAD": "auto"})
    assert row["metric"] == \
        "llama300m_sharded_step_tokens_per_sec_per_chip"
    assert "offload" not in row and "memory_kind" not in row


def test_bench_offload_request_mapping(capsys):
    """BENCH_OFFLOAD contract: legacy truthy ints -> opt, ladder names
    pass through, unknown values WARN and fall back instead of letting
    the Trainer's argparse choices SystemExit the whole bench run."""
    import bench

    for raw, expect in (("", "none"), ("0", "none"), ("1", "opt"),
                        ("2", "opt"), ("auto", "auto"), ("opt", "opt"),
                        ("opt_master", "opt_master"), ("none", "none")):
        os.environ["BENCH_OFFLOAD"] = raw
        try:
            assert bench._offload_request() == expect, raw
        finally:
            del os.environ["BENCH_OFFLOAD"]
    os.environ["BENCH_OFFLOAD"] = "zero3"
    try:
        assert bench._offload_request("auto") == "auto"
    finally:
        del os.environ["BENCH_OFFLOAD"]
    assert "unrecognized BENCH_OFFLOAD" in capsys.readouterr().err


def test_bench_large_ladder_rung(monkeypatch):
    """Seed-failing until ISSUE 9 (same pinned_host abort as the
    offload row — the large mode always offloaded): the rung now runs
    end-to-end at the level --offload=auto resolves on the live
    backend."""
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "large",
                                   "BENCH_KV": "2",
                                   "BENCH_FUSED_CE": "4"})
    assert row["metric"].startswith("llama13bshape_l2")


def test_bench_decode_greedy(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "decode",
                                   "BENCH_PROMPT": "16",
                                   "BENCH_NEW_TOKENS": "16",
                                   "BENCH_DECODE_RUNS": "1"})
    assert row["metric"] == "llama300m_decode_tokens_per_sec_per_chip"


def test_bench_decode_int8(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "decode",
                                   "BENCH_INT8_LMHEAD": "1",
                                   "BENCH_PROMPT": "16",
                                   "BENCH_NEW_TOKENS": "16",
                                   "BENCH_DECODE_RUNS": "1"})
    assert row["metric"] == \
        "llama300m_int8_decode_tokens_per_sec_per_chip"


def test_bench_decode_spec(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "decode",
                                   "BENCH_DECODE": "spec",
                                   "BENCH_SPEC_GAMMA": "2",
                                   "BENCH_DRAFT_LAYERS": "1",
                                   "BENCH_PROMPT": "16",
                                   "BENCH_NEW_TOKENS": "16",
                                   "BENCH_DECODE_RUNS": "1"})
    assert row["metric"] == \
        "llama300m_spec_decode_tokens_per_sec_per_chip"


def test_bench_decode_lookup(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "decode",
                                   "BENCH_DECODE": "lookup",
                                   "BENCH_SPEC_GAMMA": "2",
                                   "BENCH_PROMPT": "16",
                                   "BENCH_NEW_TOKENS": "16",
                                   "BENCH_DECODE_RUNS": "1"})
    assert row["metric"] == \
        "llama300m_lookup_decode_tokens_per_sec_per_chip"


def test_bench_decode_beam(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "decode",
                                   "BENCH_DECODE": "beam",
                                   "BENCH_PROMPT": "16",
                                   "BENCH_NEW_TOKENS": "16",
                                   "BENCH_DECODE_RUNS": "1"})
    assert row["metric"] == "t5beam4_decode_tokens_per_sec_per_chip"


# ---- fresh-process OOM ladder -----------------------------------------
# Runtime OOMs surface as a bare "ResourceExhausted" (not "Ran out of
# memory"), and an OOM'd rung's buffers can OOM the NEXT rung when rungs
# share a process. The ladder matches both signatures and runs each rung
# via _spawn_rung; these tests drive the ladder decision logic through a
# stub spawner.


def test_is_oom_text_matches_both_forms():
    import bench

    assert bench._is_oom_text(
        "RESOURCE_EXHAUSTED: TPU backend error (ResourceExhausted).")
    assert bench._is_oom_text(
        "XlaRuntimeError: Ran out of memory in memory space hbm")
    assert not bench._is_oom_text("INTERNAL: HTTP 500: compile helper")


def test_ladder_steps_down_on_oom_then_stops():
    import bench

    calls = []

    def spawn(env):
        calls.append(env)
        return (0, "") if len(calls) == 3 else \
            (1, "jax.errors.JaxRuntimeError: RESOURCE_EXHAUSTED: TPU "
                "backend error (ResourceExhausted).")

    bench._ladder_of_rungs(
        [{"BENCH_BATCH": b} for b in (28, 24, 16, 8)], "t",
        spawn=spawn)
    assert [c["BENCH_BATCH"] for c in calls] == [28, 24, 16]


def test_ladder_propagates_non_oom_failure():
    import bench

    def spawn(env):
        return 7, "ValueError: something real broke"

    with pytest.raises(SystemExit) as exc:
        bench._ladder_of_rungs([{"BENCH_BATCH": 28},
                                {"BENCH_BATCH": 8}], "t", spawn=spawn)
    assert exc.value.code == 7


def test_ladder_raises_when_every_rung_ooms():
    import bench

    def spawn(env):
        return 1, "Ran out of memory in memory space hbm"

    with pytest.raises(RuntimeError, match="every ladder rung OOM"):
        bench._ladder_of_rungs([{"BENCH_BATCH": 28}], "t", spawn=spawn)


def test_bench_sharded_steps_per_exec(monkeypatch):
    row = _run_bench(monkeypatch, {"BENCH_CONFIG": "sharded",
                                   "BENCH_STEPS_PER_EXEC": "3"})
    assert row["metric"] == "llama300m_sharded_step_tokens_per_sec_per_chip"


def test_no_accelerator_is_an_error_unless_cpu_is_asked_for(monkeypatch):
    """jax drops to the CPU on its own when it finds no accelerator; a
    leaf bench path must then exit non-zero instead of emitting a CPU
    number under a device metric's name."""
    import bench

    bench._require_accelerator()        # JAX_PLATFORMS=cpu: by name
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as exc:
        bench._require_accelerator()
    assert "not 'tpu'" in str(exc.value.code)
