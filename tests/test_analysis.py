"""fslint (fengshen_tpu.analysis) — rule fixtures, engine mechanics,
baseline workflow, CLI contract, and the fast-lane whole-package gate.

This file supersedes the old regex lint in test_lint_excepts.py: the
AST `blanket-except` rule gives the same guarantee (no silent blanket
handlers anywhere in fengshen_tpu/) without string/comment false
positives, and the whole-package test below enforces it along with the
five SPMD rules.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from fengshen_tpu.analysis import (all_rule_ids, build_index, check_file,
                                   check_paths, default_project_root,
                                   make_rules)
from fengshen_tpu.analysis import baseline as baseline_mod
from fengshen_tpu.analysis.cli import _changed_py_files
from fengshen_tpu.analysis.cli import main as fslint_main

REPO = default_project_root()
PKG = os.path.join(REPO, "fengshen_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "analysis_fixtures")

RULE_IDS = ("blanket-except", "blocking-transfer",
            "blocking-under-lock", "donated-buffer-use",
            "host-divergence", "lock-order", "metric-contract",
            "metrics-in-traced-code", "nondet-iteration",
            "partition-spec-axes", "resource-lifecycle",
            "retrace-hazard", "unguarded-shared-state")

CONCURRENCY_RULE_IDS = ("blocking-under-lock", "lock-order",
                        "unguarded-shared-state")

DATAFLOW_RULE_IDS = ("donated-buffer-use", "metric-contract",
                     "resource-lifecycle")


def _fixture(rule_id: str, kind: str) -> str:
    path = os.path.join(FIXTURES,
                        f"{rule_id.replace('-', '_')}_{kind}.py")
    assert os.path.exists(path), f"missing fixture {path}"
    return path


def test_registry_has_the_shipped_rules():
    assert set(RULE_IDS) <= set(all_rule_ids())


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_rule_fires_on_bad_fixture(rule_id):
    findings = check_file(_fixture(rule_id, "bad"), make_rules(), REPO)
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, f"{rule_id} found nothing in its known-bad fixture"
    for f in hits:
        assert f.line > 0 and f.hint and f.code


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_rule_quiet_on_clean_fixture(rule_id):
    findings = check_file(_fixture(rule_id, "clean"), make_rules(), REPO)
    hits = [f for f in findings if f.rule == rule_id]
    assert not hits, (
        f"{rule_id} false-positives on idiomatic clean code:\n"
        + "\n".join(f.render() for f in hits))


def test_clean_fixtures_are_fully_clean():
    """No rule — not just the one under test — fires on a clean
    fixture: cross-rule noise in the clean set means a precision bug."""
    for rule_id in RULE_IDS:
        findings = check_file(_fixture(rule_id, "clean"), make_rules(),
                              REPO)
        assert not findings, "\n".join(f.render() for f in findings)


def test_package_is_clean_under_shipped_baseline():
    """The fast-lane gate: the full analyzer over fengshen_tpu/ must
    report zero non-baselined findings on the merged tree."""
    findings = check_paths([PKG], make_rules(), REPO)
    entries = baseline_mod.load_baseline(
        baseline_mod.default_baseline_path(REPO))
    new, _, stale = baseline_mod.split_by_baseline(findings, entries)
    assert not new, (
        "fslint found non-baselined findings — fix them, suppress with "
        "a justified `# fslint: disable=<rule>`, or (legacy only) "
        "baseline them:\n" + "\n".join(f.render() for f in new))
    assert not stale, (
        "stale baseline entries (the finding no longer fires) — run "
        f"--write-baseline or delete them: {stale}")


def test_sharding_rules_are_clean():
    """The declarative-sharding gate (docs/sharding.md): every
    `*PARAM_LOGICAL_AXES` / `*LOGICAL_AXIS_RULES` table in the package
    validates against the vocabularies — with NO baseline escape hatch
    (a typo'd logical or mesh axis silently replicates a dimension, so
    these tables must stay clean, not baselined)."""
    from fengshen_tpu.analysis.rules.partition_spec_axes import (
        logical_axes, mesh_axes)
    # the gate is only meaningful if both vocabularies parse
    assert logical_axes(REPO), "LOGICAL_AXES not parseable from " \
        "fengshen_tpu/sharding/axes.py"
    assert mesh_axes(REPO), "mesh axes not parseable from " \
        "fengshen_tpu/parallel/mesh.py"
    findings = [f for f in check_paths([PKG], make_rules(), REPO)
                if f.rule == "partition-spec-axes"]
    assert not findings, "\n".join(f.render() for f in findings)


def _write(tmp_path, name, body):
    path = tmp_path / name
    path.write_text(textwrap.dedent(body), encoding="utf-8")
    return str(path)


def test_per_line_suppression(tmp_path):
    bad = """
    def f(fn):
        try:
            fn()
        except Exception:
            pass
    """
    path = _write(tmp_path, "mod.py", bad)
    assert [f.rule for f in check_file(path, make_rules(), REPO)] == \
        ["blanket-except"]

    suppressed = bad.replace(
        "except Exception:",
        "except Exception:  # fslint: disable=blanket-except")
    path = _write(tmp_path, "mod2.py", suppressed)
    assert not check_file(path, make_rules(), REPO)

    # bare `disable` silences every rule on the line
    suppressed_all = bad.replace("except Exception:",
                                 "except Exception:  # fslint: disable")
    path = _write(tmp_path, "mod3.py", suppressed_all)
    assert not check_file(path, make_rules(), REPO)

    # a different rule id does NOT silence it
    wrong = bad.replace(
        "except Exception:",
        "except Exception:  # fslint: disable=host-divergence")
    path = _write(tmp_path, "mod4.py", wrong)
    assert [f.rule for f in check_file(path, make_rules(), REPO)] == \
        ["blanket-except"]


def test_baseline_pins_by_code_not_line(tmp_path):
    src = """
    def f(fn):
        try:
            fn()
        except Exception:
            pass
    """
    path = _write(tmp_path, "legacy.py", src)
    findings = check_file(path, make_rules(), REPO)
    assert len(findings) == 1

    bl = tmp_path / "baseline.json"
    baseline_mod.write_baseline(str(bl), findings)
    entries = baseline_mod.load_baseline(str(bl))
    assert entries and "justification" in entries[0]

    # unrelated lines added ABOVE: line number moves, baseline holds
    shifted = "import os  # noqa: F401\nimport sys  # noqa: F401\n" + \
        textwrap.dedent(src)
    (tmp_path / "legacy.py").write_text(shifted, encoding="utf-8")
    findings2 = check_file(str(tmp_path / "legacy.py"), make_rules(),
                           REPO)
    new, baselined, stale = baseline_mod.split_by_baseline(findings2,
                                                           entries)
    assert not new and len(baselined) == 1 and not stale

    # the flagged LINE itself changes: finding resurfaces, entry stale
    edited = textwrap.dedent(src).replace("except Exception:",
                                          "except BaseException:")
    (tmp_path / "legacy.py").write_text(edited, encoding="utf-8")
    findings3 = check_file(str(tmp_path / "legacy.py"), make_rules(),
                           REPO)
    new, baselined, stale = baseline_mod.split_by_baseline(findings3,
                                                           entries)
    assert len(new) == 1 and not baselined and len(stale) == 1


def test_json_output_is_sorted_and_stable(tmp_path, capsys):
    _write(tmp_path, "b.py", """
    import random, jax

    @jax.jit
    def f(x):
        return x + random.random()

    def g(fn):
        try:
            fn()
        except Exception:
            pass
    """)
    _write(tmp_path, "a.py", """
    def h(fn):
        try:
            fn()
        except:
            pass
    """)
    argv = [str(tmp_path), "--json", "--no-baseline"]
    assert fslint_main(argv) == 1
    out1 = capsys.readouterr().out
    assert fslint_main(argv) == 1
    out2 = capsys.readouterr().out
    assert out1 == out2, "--json output is not deterministic"

    report = json.loads(out1)
    keys = [(f["path"], f["line"], f["col"], f["rule"])
            for f in report["findings"]]
    assert keys == sorted(keys)
    assert [f["rule"] for f in report["findings"]] == \
        ["blanket-except", "host-divergence", "blanket-except"]


def test_cli_select_ignore_and_unknown_rule(tmp_path, capsys):
    path = _write(tmp_path, "m.py", """
    def f(fn):
        try:
            fn()
        except Exception:
            pass
    """)
    assert fslint_main([path, "--no-baseline",
                        "--select", "blanket-except"]) == 1
    capsys.readouterr()
    assert fslint_main([path, "--no-baseline",
                        "--ignore", "blanket-except"]) == 0
    capsys.readouterr()
    assert fslint_main([path, "--select", "no-such-rule"]) == 2
    assert "unknown rule" in capsys.readouterr().err


def test_cli_write_baseline_roundtrip(tmp_path, capsys):
    path = _write(tmp_path, "m.py", """
    def f(fn):
        try:
            fn()
        except Exception:
            pass
    """)
    bl = str(tmp_path / "bl.json")
    assert fslint_main([path, "--baseline", bl,
                        "--write-baseline"]) == 0
    capsys.readouterr()
    # baselined now: exit 0; byte-stable on rewrite
    assert fslint_main([path, "--baseline", bl]) == 0
    first = open(bl, encoding="utf-8").read()
    assert fslint_main([path, "--baseline", bl,
                        "--write-baseline"]) == 0
    assert open(bl, encoding="utf-8").read() == first


def test_partial_write_baseline_keeps_other_rules(tmp_path, capsys):
    """--write-baseline with --select must not delete baseline entries
    for rules (or paths) it never re-checked."""
    path = _write(tmp_path, "m.py", """
    import random, jax

    @jax.jit
    def f(x):
        return x + random.random()

    def g(fn):
        try:
            fn()
        except Exception:
            pass
    """)
    bl = str(tmp_path / "bl.json")
    assert fslint_main([path, "--baseline", bl,
                        "--write-baseline"]) == 0
    capsys.readouterr()
    entries = baseline_mod.load_baseline(bl)
    assert sorted(e["rule"] for e in entries) == \
        ["blanket-except", "host-divergence"]

    # rewrite only the blanket-except view: host-divergence must survive
    assert fslint_main([path, "--baseline", bl, "--select",
                        "blanket-except", "--write-baseline"]) == 0
    capsys.readouterr()
    entries = baseline_mod.load_baseline(bl)
    assert sorted(e["rule"] for e in entries) == \
        ["blanket-except", "host-divergence"]
    # and the full gate still passes against the merged baseline
    assert fslint_main([path, "--baseline", bl]) == 0


def test_blocking_transfer_taint_skips_static_shape_math(tmp_path):
    """Trace-time-static host math in traced code must NOT fire: config
    attributes, `.shape` metadata, mesh sizes, annotated scalars."""
    path = _write(tmp_path, "shapes.py", """
    import math
    import jax

    class Cfg:
        hidden_size = 512


    def run(cfg, n_experts: int, mesh):
        @jax.jit
        def step(x):
            b, s, h = x.shape
            tokens = b * s
            capacity = max(1, int(math.ceil(tokens / n_experts)))
            inter = int(2 * 4 * cfg.hidden_size / 3)
            width = int(mesh.shape["tensor"])
            loss = (x ** 2).mean()
            return loss * capacity * inter * width, float(loss)

        return step
    """)
    findings = check_file(path, make_rules(), REPO)
    assert [f.rule for f in findings] == ["blocking-transfer"]
    assert "float" in findings[0].message


def test_nonexistent_path_fails_loudly(tmp_path, capsys):
    """A typo'd path must not lint nothing and report 'clean' — that
    would make the CI gate vacuous."""
    missing = str(tmp_path / "no_such_dir")
    with pytest.raises(FileNotFoundError):
        check_paths([missing], make_rules(), REPO)
    assert fslint_main([missing, "--no-baseline"]) == 2
    assert "no such file" in capsys.readouterr().err


def test_host_divergence_environ_as_call_argument(tmp_path):
    path = _write(tmp_path, "env.py", """
    import os
    import jax

    @jax.jit
    def f(x):
        env = dict(os.environ)
        return x * len(env)
    """)
    findings = check_file(path, make_rules(), REPO)
    assert [f.rule for f in findings] == ["host-divergence"]


def test_retrace_hazard_ignores_local_shadowing(tmp_path):
    path = _write(tmp_path, "shadow.py", """
    import jax
    import jax.numpy as jnp

    MASK = jnp.zeros((4,))

    @jax.jit
    def f(x):
        MASK = x * 2  # local rebinding, not a closure
        return MASK

    @jax.jit
    def g(x):
        return x + MASK  # the real closure still fires
    """)
    findings = check_file(path, make_rules(), REPO)
    assert len(findings) == 1
    assert findings[0].rule == "retrace-hazard" and "g" in \
        findings[0].message


def test_blocking_transfer_taints_loop_targets(tmp_path):
    path = _write(tmp_path, "loop.py", """
    import jax

    @jax.jit
    def f(xs):
        total = 0.0
        for x in xs:
            total += x.item()
        return total
    """)
    findings = check_file(path, make_rules(), REPO)
    assert [f.rule for f in findings] == ["blocking-transfer"]


def test_parse_error_is_a_finding(tmp_path):
    path = _write(tmp_path, "broken.py", "def f(:\n")
    findings = check_file(path, make_rules(), REPO)
    assert [f.rule for f in findings] == ["parse-error"]


def test_traced_context_spans_local_call_chains(tmp_path):
    """A hazard two calls below a jit entry point is still caught."""
    path = _write(tmp_path, "chain.py", """
    import time
    import jax

    def leaf(x):
        return x * time.time()

    def mid(x):
        return leaf(x) + 1

    def run(xs):
        return jax.jit(mid)(xs)
    """)
    findings = check_file(path, make_rules(), REPO)
    assert [f.rule for f in findings] == ["host-divergence"]


def test_offload_policy_internals_are_clean():
    """Regression fixture for the memory-placement subsystem (ISSUE 9,
    docs/offload.md): the capability probe runs OUTSIDE traced code by
    construction (its tiny transfer + block_until_ready are host-side),
    the placement math is pure host integers, and the gauges are set
    between jit boundaries — none of `host-divergence`,
    `blocking-transfer`, or `metrics-in-traced-code` may fire on the
    fixture or on the real modules (trainer/memory.py and the
    train_state/param_streaming wiring). A hit means a probe or gauge
    leaked into a traced program (a real SPMD hazard) or a rule lost
    precision."""
    fixture = os.path.join(FIXTURES, "offload_policy_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    paths = [os.path.join(PKG, "trainer", "memory.py"),
             os.path.join(PKG, "trainer", "train_state.py"),
             os.path.join(PKG, "trainer", "param_streaming.py")]
    findings = check_paths(paths, make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_spec_decode_internals_are_clean():
    """Regression fixture for the speculative decode tick (ISSUE 7):
    the drafter + verify + accept/commit stay ONE pure traced program
    (the n-gram matcher is a tempting place to leak an `.item()` or a
    metrics bump), host syncs and counters strictly between jit
    boundaries — neither `metrics-in-traced-code`,
    `blocking-transfer` nor `host-divergence` may fire on the fixture
    or on the real modules (the serving package and utils/generate.py,
    which owns the shared drafter/accept helpers)."""
    fixture = os.path.join(FIXTURES, "spec_decode_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    paths = [os.path.join(PKG, "serving"),
             os.path.join(PKG, "utils", "generate.py")]
    findings = check_paths(paths, make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_flight_recorder_internals_are_clean():
    """Regression fixture for the request-timeline / flight-recorder
    tier (ISSUE 8): lifecycle timestamps, the event ring, phase
    histograms, and the post-mortem dump are HOST-side bookkeeping
    between jit boundaries — `metrics-in-traced-code`,
    `blocking-transfer` and `host-divergence` must all stay silent on
    the fixture and on the real modules (the observability package,
    the serving package whose engine appends the timeline events, and
    the api layer's debug endpoints). A hit means a clock/counter/sync
    leaked into a traced program (a real hazard: timelines must never
    add traced work) or a rule lost precision."""
    fixture = os.path.join(FIXTURES, "flight_recorder_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    paths = [os.path.join(PKG, "observability"),
             os.path.join(PKG, "serving"),
             os.path.join(PKG, "api")]
    findings = check_paths(paths, make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_fleet_router_internals_are_clean():
    """Regression fixture for the fleet router (ISSUE 10,
    docs/fleet.md): the router is pure host-side stdlib — clocks,
    seeded backoff jitter, breaker counters, fleet metrics — and must
    STAY outside every traced program. Neither `host-divergence`,
    `blocking-transfer` nor `metrics-in-traced-code` may fire on the
    fixture or on the real `fengshen_tpu/fleet/` package. A hit means
    routing state leaked into a traced program (a real SPMD hazard) or
    a rule lost precision."""
    fixture = os.path.join(FIXTURES, "fleet_router_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    fleet_pkg = os.path.join(PKG, "fleet")
    findings = check_paths([fleet_pkg], make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_disagg_internals_are_clean():
    """Regression fixture for the prefill/decode disaggregation tier
    (ISSUE 13, docs/disaggregation.md): lane export/adopt is EAGER
    host-orchestrated array work between jit boundaries (zero new
    compiled programs), the transfer plane is blocking stdlib HTTP on
    the coordinator thread, and the `fstpu_disagg_*` counters mutate
    only around those host steps — neither `host-divergence`,
    `blocking-transfer` nor `metrics-in-traced-code` may fire on the
    fixture or on the real disagg package + `serving/handoff.py`. A
    hit means a lane gather/scatter or a KV push leaked into a traced
    program (a real hazard: compile-count drift or a device-blocking
    decode tick) or a rule lost precision."""
    fixture = os.path.join(FIXTURES, "disagg_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    paths = [os.path.join(PKG, "disagg"),
             os.path.join(PKG, "serving", "handoff.py")]
    findings = check_paths(paths, make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_evac_internals_are_clean():
    """Regression fixture for the preemption-tolerance tier (ISSUE 16,
    docs/fault_tolerance.md "Preemption runbook"): the commit journal
    appends on the scheduler thread under a plain lock, the drain-time
    lane export is an EAGER host-side gather (a drain adds zero
    compiled programs), the evacuation push is blocking HTTP on the
    drain thread, and the resume prefill is host-side token concat
    riding the SAME bucketed prefill program — neither
    `host-divergence`, `blocking-transfer` nor
    `metrics-in-traced-code` may fire on the fixture or on the real
    evacuation/resume modules (the disagg package that owns
    `evacuate_all`, `serving/handoff.py`'s detach-as-evacuated, and
    the engine that owns the journal + resume admission). A hit means
    a journal append, an evacuation push, or a resume concat leaked
    into a traced program (a real hazard: per-token journal work must
    cost dict-append, and a recovery must never retrace) or a rule
    lost precision."""
    fixture = os.path.join(FIXTURES, "evac_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    paths = [os.path.join(PKG, "disagg"),
             os.path.join(PKG, "serving", "handoff.py"),
             os.path.join(PKG, "serving", "engine.py")]
    findings = check_paths(paths, make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_streaming_internals_are_clean():
    """Regression fixture for the streaming tier (ISSUE 20,
    docs/streaming.md): the per-lane key ring splits IN-GRAPH inside
    the jitted tick (reproducibility is a property of the carried
    keys, not of host randomness), the commit-then-publish stream sync
    is plain-lock host work on the scheduler thread, and SSE framing +
    the blocking socket write + the TTFB observation live on the
    reader's delivery thread — neither `metrics-in-traced-code`,
    `blocking-transfer` nor `host-divergence` may fire on the fixture
    or on the real modules (the streaming package, the serving engine
    that owns the ring + `_sync_stream`, and the api/fleet layers that
    frame and proxy the wire). A hit means a publish, a socket write,
    or a counter leaked into a traced program (a real hazard:
    streaming must add ZERO per-token compiled work) or a rule lost
    precision."""
    fixture = os.path.join(FIXTURES, "streaming_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    paths = [os.path.join(PKG, "streaming"),
             os.path.join(PKG, "serving"),
             os.path.join(PKG, "api"),
             os.path.join(PKG, "fleet")]
    findings = check_paths(paths, make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_trace_context_internals_are_clean():
    """Regression fixture for the distributed-tracing tier (ISSUE 11,
    docs/observability.md "Distributed tracing"): trace/span ids come
    from a host-side `random.Random`, span stamps from host clocks,
    and the ledger/assembly are plain-dict work on the router and
    scheduler threads — neither `host-divergence`,
    `blocking-transfer` nor `metrics-in-traced-code` may fire on the
    fixture or on the real modules (the observability package that
    owns the ledger, the fleet package that records the spans, and
    the serving+api layers the context flows through). A hit means a
    trace id mint / wall anchor / counter leaked into a traced
    program (a real hazard: tracing must add ZERO per-token work) or
    a rule lost precision."""
    fixture = os.path.join(FIXTURES, "trace_context_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    paths = [os.path.join(PKG, "observability"),
             os.path.join(PKG, "fleet"),
             os.path.join(PKG, "serving"),
             os.path.join(PKG, "api")]
    findings = check_paths(paths, make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_paged_cache_internals_are_clean():
    """Regression fixture for the paged KV cache (ISSUE 6): block
    free-list math stays host-side, the traced gather/scatter decode
    stays pure — neither `metrics-in-traced-code`, `blocking-transfer`
    nor `host-divergence` may fire on the fixture or on the real
    serving package. A hit means either the allocator leaked into
    traced code (a real hazard: a python list mutated under trace is a
    silent retrace/divergence bug) or a rule lost precision."""
    fixture = os.path.join(FIXTURES, "paged_cache_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    serving_pkg = os.path.join(PKG, "serving")
    findings = check_paths([serving_pkg], make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


def test_pallas_internals_are_clean():
    """Regression fixture for the kernel dispatch seam (docs/
    kernels.md): the capability probe is cached host-side and the
    pallas-vs-xla decision is a compile-time constant — NOT a value
    re-read inside a traced function (the retrace hazard the seam
    exists to avoid) — and the dispatch gauge / loud startup line stay
    between jit boundaries. Neither `metrics-in-traced-code`,
    `blocking-transfer` nor `host-divergence` may fire on the fixture
    or on the real kernel layer + its two biggest consumers (the llama
    decode path and the serving engine)."""
    fixture = os.path.join(FIXTURES, "pallas_kernels_clean.py")
    findings = check_file(fixture, make_rules(), REPO)
    assert not findings, "\n".join(f.render() for f in findings)

    kernel_layer = [
        os.path.join(PKG, "ops", "pallas"),
        os.path.join(PKG, "models", "llama", "modeling_llama.py"),
        os.path.join(PKG, "serving", "engine.py"),
    ]
    findings = check_paths(kernel_layer, make_rules(), REPO)
    hits = [f for f in findings
            if f.rule in ("metrics-in-traced-code", "blocking-transfer",
                          "host-divergence")]
    assert not hits, "\n".join(f.render() for f in hits)


# -- fslint v2: cross-module concurrency rules ------------------------------


def test_concurrency_rules_clean_on_package():
    """The fast-lane concurrency gate: the three whole-package rules
    (`unguarded-shared-state`, `blocking-under-lock`, `lock-order`)
    must report ZERO findings over the merged tree — not baselined,
    zero. Every deliberate design (the engine's tick-owns-the-lock
    scheduler, warmup under `_cv`) carries an inline
    `# fslint: disable=<rule>; <rationale>` at the site, so a hit here
    is either a new concurrency bug or an undocumented design
    decision. The baseline stays empty for these rules by policy."""
    rules = make_rules(select=list(CONCURRENCY_RULE_IDS))
    findings = check_paths([PKG], rules, REPO)
    assert not findings, (
        "concurrency rules fired on the package — fix the race/"
        "inversion or suppress at the site with a rationale:\n"
        + "\n".join(f.render() for f in findings))
    entries = baseline_mod.load_baseline(
        baseline_mod.default_baseline_path(REPO))
    assert not [e for e in entries
                if e["rule"] in CONCURRENCY_RULE_IDS], \
        "concurrency findings must be fixed or line-suppressed, " \
        "never baselined"


def test_dataflow_rules_clean_on_package():
    """The dataflow gate, same policy as the concurrency gate: the
    three PR-17 rules (`donated-buffer-use`, `resource-lifecycle`,
    `metric-contract`) report ZERO findings
    over the merged tree with an EMPTY baseline. Every real leak the
    sweep found was fixed at the site (serving/engine.py `_admit`,
    serving/handoff.py `adopt_lane`, the bert_dataloader shard
    writers), every donation site uses the rebind idiom, and the
    metrics reference table in docs/observability.md matches the
    registrations — so a hit here is a regression, not legacy debt."""
    rules = make_rules(select=list(DATAFLOW_RULE_IDS))
    findings = check_paths([PKG], rules, REPO)
    assert not findings, (
        "dataflow rules fired on the package — fix the leak/stale "
        "read/contract drift or suppress at the site with a "
        "rationale:\n" + "\n".join(f.render() for f in findings))
    entries = baseline_mod.load_baseline(
        baseline_mod.default_baseline_path(REPO))
    assert not [e for e in entries
                if e["rule"] in DATAFLOW_RULE_IDS], \
        "dataflow findings must be fixed or line-suppressed, " \
        "never baselined"


def test_donation_witness_chain():
    """The bad fixture's finding carries the full witness chain:
    binding line, donating call line, and the stale read."""
    findings = check_file(_fixture("donated-buffer-use", "bad"),
                          make_rules(select=["donated-buffer-use"]),
                          REPO)
    assert len(findings) == 1
    msg = findings[0].message
    assert "donate_argnums bound at" in msg
    assert "donating call at" in msg and "read at" in msg


def test_lifecycle_witness_chains():
    """Both finding kinds fire on the bad fixture, each with its
    witness: the leak names the raising call, the double-release the
    first release site."""
    findings = check_file(_fixture("resource-lifecycle", "bad"),
                          make_rules(select=["resource-lifecycle"]),
                          REPO)
    msgs = sorted(f.message for f in findings)
    assert len(msgs) == 2
    assert any("pad_prompt" in m and "release skipped" in m
               for m in msgs)
    assert any("released twice" in m and "first release" in m
               for m in msgs)


def test_cross_module_lock_discipline(tmp_path):
    """The project index resolves calls ACROSS files: a blocking call
    two modules away from the `with lock:` body is still caught."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("", encoding="utf-8")
    (pkg / "transport.py").write_text(textwrap.dedent("""
        import urllib.request

        def fetch(url):
            return urllib.request.urlopen(url).read()
        """), encoding="utf-8")
    (pkg / "router.py").write_text(textwrap.dedent("""
        import threading

        from pkg.transport import fetch


        class Router:
            def __init__(self):
                self._lock = threading.Lock()
                self.state = {}

            def refresh(self, url):
                with self._lock:
                    self.state["health"] = fetch(url)
        """), encoding="utf-8")
    rules = make_rules(select=["blocking-under-lock"])
    findings = check_paths([str(pkg)], rules,
                           project_root=str(tmp_path))
    assert [f.rule for f in findings] == ["blocking-under-lock"]
    assert "pkg/router.py" == findings[0].path
    assert "fetch" in findings[0].message
    assert "urlopen" in findings[0].message


def test_index_cache_invalidates_on_content_change(tmp_path):
    """The on-disk index cache keys per-file entries by content hash:
    editing a file (same path) must re-summarize it, never serve the
    stale summary — the cache can only ever be a speedup."""
    mod = tmp_path / "counter.py"
    clean = textwrap.dedent("""
        import threading


        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def bump(self):
                with self._lock:
                    self._n += 1

            def read(self):
                with self._lock:
                    return self._n
        """)
    mod.write_text(clean, encoding="utf-8")
    cache = str(tmp_path / "cache.json")
    rules = make_rules(select=["unguarded-shared-state"])

    assert not check_paths([str(mod)], rules,
                           project_root=str(tmp_path),
                           index_cache=cache)
    assert os.path.exists(cache)

    # same content, warm cache: still clean (cache round-trips)
    assert not check_paths([str(mod)], rules,
                           project_root=str(tmp_path),
                           index_cache=cache)

    # introduce an unguarded write; the warm cache must not mask it
    mod.write_text(
        clean + "    def reset(self):\n        self._n = 0\n",
        encoding="utf-8")
    findings = check_paths([str(mod)], rules,
                           project_root=str(tmp_path),
                           index_cache=cache)
    assert [f.rule for f in findings] == ["unguarded-shared-state"]
    assert "self._n = 0" == findings[0].code

    # revert: clean again, via the now-twice-rewritten cache
    mod.write_text(clean, encoding="utf-8")
    assert not check_paths([str(mod)], rules,
                           project_root=str(tmp_path),
                           index_cache=cache)


def test_json_deterministic_across_hash_seeds():
    """Byte-identical `--json` output under different
    PYTHONHASHSEED values: the project index iterates sets/dicts in
    sorted order everywhere, so CI can diff reports across hosts.
    Runs over the fixtures tree (known findings, all three concurrency
    rules active) in subprocesses so the seed actually varies."""
    argv = [sys.executable, "-m", "fengshen_tpu.analysis", FIXTURES,
            "--json", "--no-baseline", "--no-index-cache"]
    outs = []
    for seed in ("0", "4242"):
        env = dict(os.environ, PYTHONHASHSEED=seed,
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run(argv, capture_output=True, text=True,
                              timeout=120, env=env, cwd=REPO)
        assert proc.returncode == 1, proc.stderr
        outs.append(proc.stdout)
    assert outs[0] == outs[1], "--json output varies with hash seed"
    report = json.loads(outs[0])
    fired = {f["rule"] for f in report["findings"]}
    assert set(CONCURRENCY_RULE_IDS) <= fired
    assert set(DATAFLOW_RULE_IDS) <= fired


def test_changed_file_discovery(tmp_path):
    """`--changed` file discovery: modified-vs-HEAD plus untracked,
    .py only, deleted files dropped."""
    repo = tmp_path / "repo"
    repo.mkdir()
    env = dict(os.environ, GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")

    def git(*argv):
        subprocess.run(["git", *argv], cwd=str(repo), check=True,
                       capture_output=True, env=env)

    git("init", "-q")
    (repo / "a.py").write_text("A = 1\n", encoding="utf-8")
    (repo / "gone.py").write_text("G = 1\n", encoding="utf-8")
    (repo / "notes.md").write_text("x\n", encoding="utf-8")
    git("add", "-A")
    git("commit", "-qm", "seed")

    (repo / "a.py").write_text("A = 2\n", encoding="utf-8")   # modified
    (repo / "b.py").write_text("B = 1\n", encoding="utf-8")   # untracked
    (repo / "notes.md").write_text("y\n", encoding="utf-8")   # not .py
    (repo / "gone.py").unlink()                               # deleted

    changed = _changed_py_files(str(repo))
    assert [os.path.basename(p) for p in changed] == ["a.py", "b.py"]

    with pytest.raises(RuntimeError):
        _changed_py_files(str(tmp_path))  # not a git repository


def test_cli_github_format(capsys):
    """`--format=github` renders one ::error workflow annotation per
    finding, carrying file/line/col and the rule id."""
    bad = os.path.join(FIXTURES, "lock_order_bad.py")
    rc = fslint_main([bad, "--select", "lock-order", "--no-baseline",
                      "--no-index-cache", "--format=github"])
    assert rc == 1
    out = capsys.readouterr().out.splitlines()
    assert out and all(
        line.startswith("::error file=tests/analysis_fixtures/"
                        "lock_order_bad.py,line=") and
        "title=fslint lock-order::" in line
        for line in out)


def test_sarif_deterministic_across_hash_seeds():
    """`--format=sarif` (the `make lint-ci` artifact) is byte-stable
    across PYTHONHASHSEED values and structurally a SARIF 2.1.0 log:
    one run, rules sorted by id, one result per finding with a
    1-based startColumn."""
    argv = [sys.executable, "-m", "fengshen_tpu.analysis", FIXTURES,
            "--format=sarif", "--no-baseline", "--no-index-cache"]
    outs = []
    for seed in ("0", "4242"):
        env = dict(os.environ, PYTHONHASHSEED=seed,
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run(argv, capture_output=True, text=True,
                              timeout=120, env=env, cwd=REPO)
        assert proc.returncode == 1, proc.stderr
        outs.append(proc.stdout)
    assert outs[0] == outs[1], "SARIF output varies with hash seed"
    log = json.loads(outs[0])
    assert log["version"] == "2.1.0"
    (run,) = log["runs"]
    rule_ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert rule_ids == sorted(rule_ids)
    assert set(RULE_IDS) <= set(rule_ids)
    assert run["results"], "fixtures tree must produce SARIF results"
    for res in run["results"]:
        assert res["level"] == "error" and res["ruleId"] in rule_ids
        region = res["locations"][0]["physicalLocation"]["region"]
        assert region["startLine"] >= 1 and region["startColumn"] >= 1


def test_cli_stats_in_json_report(capsys):
    """`--stats` adds a stats block to the JSON report: files indexed,
    rules run, index-cache hit/miss split, and wall time."""
    bad = os.path.join(FIXTURES, "lock_order_bad.py")
    rc = fslint_main([bad, "--json", "--stats", "--no-baseline",
                      "--no-index-cache"])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    stats = report["stats"]
    assert stats["files"] == 1
    assert stats["rules"] == len(make_rules())
    assert stats["index_cache_hits"] == 0      # --no-index-cache
    # no disk cache: the file is either summarised fresh or served
    # from the in-process memo
    assert stats["index_cache_misses"] + stats["memo_hit"] == 1
    assert stats["wall_time_s"] >= 0

    # without --stats the report carries no stats key (determinism:
    # wall time is the one non-reproducible field)
    rc = fslint_main([bad, "--json", "--no-baseline",
                      "--no-index-cache"])
    assert rc == 1
    assert "stats" not in json.loads(capsys.readouterr().out)


@pytest.mark.parametrize("fmt", ["text", "sarif"])
def test_cli_stats_on_stderr_for_non_json(fmt, capsys):
    clean = os.path.join(FIXTURES, "lock_order_clean.py")
    rc = fslint_main([clean, f"--format={fmt}", "--stats",
                      "--no-baseline", "--no-index-cache"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "fslint stats: " in err
    stats = json.loads(err.split("fslint stats: ", 1)[1])
    assert stats["files"] == 1


def test_warm_cache_whole_package_under_budget(tmp_path):
    """Fast-lane smoke: with a warm index cache the whole-package
    index build serves every file summary from the cache — the
    dataflow findings ride in the cached summaries, so nothing is
    re-analyzed — and finishes in a fraction of the cold-build time."""
    import time

    from fengshen_tpu.analysis import engine as engine_mod
    from fengshen_tpu.analysis import project as project_mod

    cache = str(tmp_path / "cache.json")
    files = sorted(engine_mod.iter_py_files([PKG]))

    t0 = time.monotonic()
    cold = project_mod.build_index(files, REPO, cache_path=cache)
    cold_s = time.monotonic() - t0
    stats = dict(project_mod.LAST_BUILD_STATS)
    assert stats["cache_misses"] == stats["files"] > 100

    t0 = time.monotonic()
    warm = project_mod.build_index(files, REPO, cache_path=cache)
    warm_s = time.monotonic() - t0
    stats = dict(project_mod.LAST_BUILD_STATS)
    assert stats["cache_hits"] == stats["files"]
    assert stats["cache_misses"] == 0

    # warm is observed ~20x cheaper than cold (~0.3s vs ~6.5s); a 3x
    # bar with a 2s floor stays green on slow CI while still tripping
    # if the cache stops serving (or the flow engines re-run)
    assert warm_s < max(2.0, cold_s / 3), (cold_s, warm_s)

    # and the round-tripped summaries carry the dataflow facts intact
    rel = "fengshen_tpu/serving/engine.py"
    assert warm.files[rel].lifecycle_findings == \
        cold.files[rel].lifecycle_findings
    assert warm.files[rel].metrics == cold.files[rel].metrics
