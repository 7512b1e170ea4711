"""Bench trajectory comparator: diff BENCH_r*.json rounds, flag
regressions, emit a deterministic verdict.

    python -m fengshen_tpu.observability.benchdiff [--dir .]
        [--threshold 0.15] [--json] [--strict] [--baseline FILE]
    make benchdiff

Every bench round lands as `BENCH_r<NN>.json`:

    {"n": 3, "cmd": "...", "rc": 1, "tail": "<stderr tail>",
     "parsed": null | {...row...} | [{...}, ...]}

where each parsed row is the one-line BENCH schema
serving/bench.py emits ({"metric", "value", "unit", "vs_baseline", and
optionally "mfu", "degraded", ...}). The comparator:

- classifies each round: ``ok`` (rc 0 + parsed rows) or ``failed``
  (anything else);
- diffs every metric against the MOST RECENT prior round that carried
  it (rounds often rotate BENCH_CONFIG, so "previous round" is per
  metric, not per file), and against `BASELINE.json`'s ``published``
  table when a metric appears there;
- flags ``regression`` / ``improvement`` when |delta| exceeds
  ``--threshold`` (relative), ``flat`` otherwise, and ``incomparable``
  when exactly one side is a degraded CPU-fallback number (a rescue
  row must never read as a hardware regression), when the two sides
  ran at different memory placements (the ``offload`` +
  ``memory_kind`` row fields, docs/offload.md — an offloaded-update
  row is a different program from a device-resident one), or when two
  fleet rows (docs/fleet.md) carry different ``replicas`` counts — a
  3-replica aggregate must never diff against a 2-replica one;
- prints a deterministic report (sorted rounds, sorted metrics,
  ``sort_keys`` JSON) and an overall verdict: ``REGRESSED`` /
  ``OK`` / ``NO_SIGNAL`` (no parseable rounds at all).

Exit codes: 0 on OK/NO_SIGNAL (and on REGRESSED without ``--strict`` —
the Makefile target reports, CI decides), 3 on REGRESSED with
``--strict``, 2 when the directory has no BENCH files. Pure stdlib.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import List, Optional, Tuple

_ROUND_RE = re.compile(r"^BENCH_r(\d+)\.json$")

DEFAULT_THRESHOLD = 0.15

VERDICT_REGRESSED = "REGRESSED"
VERDICT_OK = "OK"
VERDICT_NO_SIGNAL = "NO_SIGNAL"


def load_rounds(directory: str) -> List[Tuple[int, str, dict]]:
    """(round number, filename, payload) for every BENCH_r*.json,
    sorted by round number."""
    rounds = []
    for name in sorted(os.listdir(directory)):
        m = _ROUND_RE.match(name)
        if not m:
            continue
        with open(os.path.join(directory, name)) as f:
            rounds.append((int(m.group(1)), name, json.load(f)))
    rounds.sort(key=lambda r: (r[0], r[1]))
    return rounds


def _rows(parsed) -> List[dict]:
    if isinstance(parsed, dict):
        parsed = [parsed]
    if not isinstance(parsed, list):
        return []
    return [r for r in parsed
            if isinstance(r, dict) and "metric" in r and "value" in r]


def classify_round(payload: dict) -> Tuple[str, List[dict]]:
    """('ok'|'failed', parsed rows)."""
    rows = _rows(payload.get("parsed"))
    if int(payload.get("rc", 1)) == 0 and rows:
        return "ok", rows
    return "failed", rows


def _placement(row: dict) -> str:
    """The memory-placement identity of a BENCH row (docs/offload.md):
    offload ladder level + resolved memory kind. Rows without the
    fields are level "none" (the pre-offload row shape); rows at
    different placements measure different programs and must never be
    compared."""
    level = str(row.get("offload") or "none")
    kind = str(row.get("memory_kind") or "")
    return f"{level}:{kind}" if level != "none" else "none"


def _identity(row: dict) -> str:
    """The full comparison identity of a BENCH row: memory placement
    plus — for fleet rows (docs/fleet.md) — the replica count, plus —
    for disaggregated rows (docs/disaggregation.md) — the phase
    topology. Two fleet rounds at different N measure different
    deployments exactly like two offload rounds at different
    placements measure different programs, and a
    ``prefill=1,decode=2`` topology is a different deployment from a
    ``homogeneous`` 3-replica one even at equal N; all of them diff as
    ``incomparable``, never regression/flat. Fault-drill rows
    (docs/fault_tolerance.md) carry a ``drill`` key for the same
    reason: a preemption round must never be compared against an
    undisturbed one. Kernel-bench rows (docs/kernels.md) carry a
    ``kernel`` key with the dispatch decision (``pallas`` | ``xla``):
    a Mosaic-kernel round and a stock-lowering round measure different
    programs, so they too diff as incomparable. Multimodal rows
    (docs/serving.md "Multimodal engines") carry an ``engine_type``
    key (``batch_image`` | ``embedding`` | ``continuous``): a
    diffusion-serving round and a text-serving round share metric
    names but measure different engines entirely."""
    parts = [_placement(row)]
    if "replicas" in row:
        parts.append(f"replicas={int(row['replicas'])}")
    if "topology" in row:
        parts.append(f"topology={row['topology']}")
    if "drill" in row:
        parts.append(f"drill={row['drill']}")
    if "kernel" in row:
        parts.append(f"kernel={row['kernel']}")
    if "engine_type" in row:
        parts.append(f"engine_type={row['engine_type']}")
    # streaming rows (docs/streaming.md): a token-by-token SSE round
    # measures a different delivery path than a batch round, and a
    # self_draft round runs a different decode program than a
    # prompt_lookup one — both keys join the identity so they only
    # ever diff against their own kind
    if "stream" in row:
        parts.append(f"stream={bool(row['stream'])}")
    if "spec_mode" in row:
        parts.append(f"spec_mode={row['spec_mode']}")
    return "|".join(parts)


def _compare(metric: str, round_n: int, value: float, degraded: bool,
             placement: str, prev_round, prev_value: float,
             prev_degraded: bool, prev_placement: str,
             threshold: float) -> dict:
    comparison = {
        "metric": metric,
        "round": round_n,
        "prev_round": prev_round,
        "value": value,
        "prev_value": prev_value,
    }
    if degraded != prev_degraded or placement != prev_placement:
        comparison.update(status="incomparable", delta_pct=None)
        return comparison
    if prev_value == 0:
        # no relative delta exists; any move off zero is a real change
        # (all BENCH metrics are higher-is-better), never "flat +0%"
        if value == 0:
            comparison.update(status="flat", delta_pct=0.0)
        else:
            comparison.update(
                status="improvement" if value > 0 else "regression",
                delta_pct=None)
        return comparison
    delta = (value - prev_value) / prev_value
    if delta < -threshold:
        status = "regression"
    elif delta > threshold:
        status = "improvement"
    else:
        status = "flat"
    comparison.update(status=status, delta_pct=round(delta * 100.0, 2))
    return comparison


def diff_rounds(rounds: List[Tuple[int, str, dict]],
                baseline: Optional[dict] = None,
                threshold: float = DEFAULT_THRESHOLD) -> dict:
    """The full trajectory report (deterministic: rounds ascend,
    metrics sort lexically, all floats rounded)."""
    published = dict((baseline or {}).get("published") or {})
    report_rounds = []
    comparisons = []
    # metric -> (round, value, degraded): "previous" is per metric
    last_seen: dict = {}
    for round_n, fname, payload in rounds:
        status, rows = classify_round(payload)
        entry = {
            "round": round_n,
            "file": fname,
            "status": status,
            "metrics": {r["metric"]: r["value"]
                        for r in sorted(rows,
                                        key=lambda r: r["metric"])},
        }
        if status != "ok":
            tail = (payload.get("tail") or "").strip()
            entry["detail"] = tail.splitlines()[-1][:160] if tail else ""
        report_rounds.append(entry)
        for row in sorted(rows, key=lambda r: r["metric"]):
            metric = str(row["metric"])
            value = float(row["value"])
            degraded = bool(row.get("degraded"))
            placement = _identity(row)
            prev = last_seen.get(metric)
            if prev is not None:
                comparisons.append(_compare(
                    metric, round_n, value, degraded, placement,
                    *prev, threshold))
            elif metric in published and not degraded:
                # published baselines predate the placement fields:
                # they are level-"none" hardware rows
                comparisons.append(_compare(
                    metric, round_n, value, degraded, placement,
                    "baseline", float(published[metric]), False,
                    "none", threshold))
            last_seen[metric] = (round_n, value, degraded, placement)
    counts = {s: sum(1 for r in report_rounds if r["status"] == s)
              for s in ("ok", "failed")}
    regressions = [c for c in comparisons if c["status"] == "regression"]
    if regressions:
        verdict = VERDICT_REGRESSED
    elif counts["ok"]:
        verdict = VERDICT_OK
    else:
        verdict = VERDICT_NO_SIGNAL
    return {
        "schema": 1,
        "threshold": threshold,
        "rounds": report_rounds,
        "comparisons": comparisons,
        "counts": counts,
        "regressions": len(regressions),
        "verdict": verdict,
    }


def render(report: dict) -> str:
    """Human-readable, line-per-fact, deterministic."""
    counts = report["counts"]
    lines = [
        f"benchdiff: rounds={len(report['rounds'])} ok={counts['ok']} "
        f"failed={counts['failed']} "
        f"comparisons={len(report['comparisons'])} "
        f"regressions={report['regressions']} "
        f"threshold={report['threshold']:g}"]
    for entry in report["rounds"]:
        head = f"r{entry['round']:02d} {entry['status'].upper()}"
        if entry["metrics"]:
            body = " ".join(f"{m}={v:g}"
                            for m, v in sorted(entry["metrics"].items()))
        else:
            body = entry.get("detail", "")
        lines.append(f"{head} {body}".rstrip())
    for c in report["comparisons"]:
        prev = c["prev_round"]
        prev_label = prev if prev == "baseline" else f"r{prev:02d}"
        delta = ("n/a" if c["delta_pct"] is None
                 else f"{c['delta_pct']:+g}%")
        lines.append(
            f"r{c['round']:02d} {c['metric']}: {c['prev_value']:g} -> "
            f"{c['value']:g} ({delta}) vs {prev_label} "
            f"{c['status'].upper()}")
    lines.append(f"verdict: {report['verdict']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m fengshen_tpu.observability.benchdiff",
        description="diff BENCH_r*.json rounds and flag regressions")
    parser.add_argument("--dir", default=".",
                        help="directory holding BENCH_r*.json")
    parser.add_argument("--threshold", default=DEFAULT_THRESHOLD,
                        type=float,
                        help="relative change flagged as regression/"
                             "improvement (default 0.15)")
    parser.add_argument("--baseline", default=None,
                        help="BASELINE.json path (default: "
                             "<dir>/BASELINE.json when present)")
    parser.add_argument("--json", action="store_true",
                        help="emit the report as sorted JSON")
    parser.add_argument("--strict", action="store_true",
                        help="exit 3 on a REGRESSED verdict")
    args = parser.parse_args(argv)

    rounds = load_rounds(args.dir)
    if not rounds:
        print(f"benchdiff: no BENCH_r*.json under {args.dir}",
              file=sys.stderr)
        return 2
    baseline = None
    baseline_path = args.baseline or os.path.join(args.dir,
                                                  "BASELINE.json")
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baseline = json.load(f)
    report = diff_rounds(rounds, baseline=baseline,
                         threshold=args.threshold)
    if args.json:
        print(json.dumps(report, sort_keys=True, indent=1))
    else:
        print(render(report))
    if args.strict and report["verdict"] == VERDICT_REGRESSED:
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
