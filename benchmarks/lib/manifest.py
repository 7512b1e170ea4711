"""`BENCHMARK.json` and the files its names point at. Everything that
belongs to one configuration, one traffic mix or one metric is a file
found by that name; nothing here knows any of them."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"benchmarks: no workload {name!r} in BENCHMARK.json")


def config_of(manifest: dict, workload: dict) -> dict:
    entry = next(c for c in manifest["configs"]
                 if c["name"] == workload["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def _reports(metric: dict, workload: str, e2e_here=None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return e2e_here is None or metric["moves"] in e2e_here


def metrics_of(manifest: dict, workload: str) -> tuple:
    """(end-to-end entries, per-layer entries) this cell reports."""
    e2e = [m for m in manifest["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    per = [m for m in manifest["per_layer"]
           if _reports(m, workload, names)]
    return e2e, per


def reader(metric_name: str):
    """The `read(obs)` of `benchmarks/metrics/<name>.py`."""
    path = os.path.join(BENCH, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics." + metric_name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def family(config: dict):
    return importlib.import_module("benchmarks.families." + config["family"])


def job(config: dict):
    return importlib.import_module("benchmarks.lib.jobs." + config["job"])
