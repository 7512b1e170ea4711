"""What a rehearsal may expect of a cell's per-layer entries, derived
from `BENCHMARK.json` so that no test pins a count a later PR moves."""

from benchmarks.lib import manifest

#: the CPU backend reports no memory peak
NOT_ON_THE_CPU = ("hbm_peak_gb.",)


def counters(man: dict, cell: str) -> set:
    """The cell's per-layer entries that a traced CPU run reads too:
    those whose source is the program's counters (a CPU trace has no
    device plane, so the readers of device time return nothing)."""
    _, per = manifest.metrics_of(man, cell)
    return {m["name"] for m in per if m["source"] == "program_counter"
            and not m["name"].startswith(NOT_ON_THE_CPU)}


def by_cell(man: dict, cell: str) -> list:
    """The cell's entries that list it (the set-up entries follow every
    cell and read what the harness itself measured)."""
    return [m for m in manifest.metrics_of(man, cell)[1]
            if "workloads" in m]


def common(man: dict) -> set:
    """The entries every saturated serving cell appends itself to."""
    return {m["name"] for m in man["per_layer"]
            if m["name"].endswith(".serve")}
