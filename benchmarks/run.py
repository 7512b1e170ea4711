"""One run of one cell of `BENCHMARK.json`.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: builds the model on the device from the seed, starts
what the cell's job kind needs, warms the cell's own shapes, measures
for `--seconds`, checks the outputs against the plain reference, prints
earlier lines as it goes and one JSON object last. It runs on the chip
or not at all. Nothing here names a cell, a configuration, a traffic
mix or a metric: each is a file found by the name `BENCHMARK.json`
gives it, and a job kind is the `job` field of a configuration.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: seconds of the window a `--trace 1` run traces (serving), and steps
#: of it (training): traces are large and tracing slows the host
TRACE_SECONDS = 6.0
TRACE_STEPS = 4


def parse(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def main(argv=None, control=None) -> int:
    args = parse(argv)
    from benchmarks.lib import check, manifest, traffic
    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    result = execute(man, cell, manifest.config_of(man, cell),
                     traffic.load_mix(cell["traffic"]),
                     check.limits_of(cell["name"]), args.seed, args.seconds,
                     bool(args.trace), control)
    print(json.dumps(result), flush=True)
    return 0


def execute(man: dict, cell: dict, config: dict, mix: dict, limits: dict,
            seed: int, seconds: float, trace: bool, control=None,
            obs_out=None) -> dict:
    """One run of `cell` given its files' contents; the result object.
    `obs_out`, a dict, receives what the run observed (the tools read
    it; the driver's runs never pass it)."""
    from benchmarks.lib import device, manifest, xplane
    from benchmarks.lib.compile_meter import CompileMeter
    from benchmarks.lib.runlog import say

    import jax
    # every program goes to the persistent cache, the small ones too;
    # the program itself places the cache (JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_compile_cache: fengshen_tpu/compile_cache.py)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from fengshen_tpu.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    # the first `jax.devices()` of a process brings the TPU runtime up:
    # 4.8-10.8 s for the same code on the same machine (PERF.md, PR 23),
    # and no line of the repo or of the benchmark runs inside it. It is
    # timed apart, shown by a per-layer metric of its own, and is not
    # set-up
    t_gate = time.perf_counter()
    dev = device.gate(cell["chips"])
    runtime_start = time.perf_counter() - t_gate
    peaks = device.peaks(dev["kind"])
    meter = CompileMeter()
    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {seed}, {seconds}s, device "
        f"{dev}, compile cache {cache_dir}")

    run_dir = os.path.join(ROOT, ".bench_run", cell["name"])
    os.makedirs(run_dir, exist_ok=True)
    obs = {"cell": cell, "config": config, "mix": mix,
           "chips": cell["chips"], "peaks": peaks}
    phases: dict = {"imports_s": t_gate - T_START,
                    "tpu_runtime_s": runtime_start}
    ctx = {"cell": cell, "config": config, "mix": mix, "seed": seed,
           "seconds": seconds, "trace": trace,
           "trace_seconds": TRACE_SECONDS, "trace_steps": TRACE_STEPS,
           "trace_dir": os.path.join(run_dir, "trace"), "run_dir": run_dir,
           "chips": cell["chips"], "meter": meter, "obs": obs,
           "phases": phases, "family": manifest.family(config),
           "limits": limits, "control": control,
           "memory_peak": lambda: device.memory_peak_bytes(cell["chips"])}
    out = manifest.job(config).run(ctx)

    # the reference's seconds, where it ran before the window, are not
    # set-up either: every run pays them whatever the program does
    before = phases.get("reference_s", 0.0) if "fit_to_open_s" in phases \
        else 0.0
    obs["runtime_start_seconds"] = runtime_start
    obs["seconds_to_open"] = (out["t_open"] - T_START - runtime_start
                              - before)
    obs["compile_seconds"] = meter.seconds
    say("set-up, itemised (s): " + ", ".join(
        f"{k[:-2]} {v:.2f}" for k, v in phases.items()) +
        f"; compile or cache load {meter.seconds:.2f} in {meter.programs} "
        f"programs (cache hits {meter.hits}, misses {meter.misses})")

    e2e, per_layer = manifest.metrics_of(man, cell["name"])
    chosen = per_layer if trace else e2e
    metrics, correct = {}, True
    for m in chosen:
        value = manifest.reader(m["name"])(obs)
        if value is None:
            continue
        if not math.isfinite(value):
            say(f"metric {m['name']} is not finite: {value}")
            correct = False
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for what, value, limit, ok in out["numbers"]:
        say(f"compared: {what}: {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if ok else 'NOT CORRECT'}")
        correct &= bool(ok)
    if control:
        say(f"control ({control}): " + json.dumps(
            {k: obs["reference"].get(k) for k in ("control", "per_request")}))

    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": dict(dev, memory_peak_bytes=obs["memory_peak_bytes"])}
    if trace and obs.get("trace") is not None:
        trace, (lo, hi) = obs["trace"], obs["trace_window"]
        result["device"].update(
            busy_s=xplane.busy_seconds(trace, lo, hi), window_s=hi - lo)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in xplane.top_ops(trace, lo, hi)],
            "idle_gaps": [[n, s] for n, s in
                          xplane.idle_gaps(trace, lo, hi)]}
        say("trace planes and lines: " + json.dumps(trace["lines"]))
    if obs_out is not None:
        obs_out.update(obs)
    return result


if __name__ == "__main__":
    sys.exit(main())
