"""`BENCHMARK.json` against the contract's limits on names and files."""

import json
import os
import re

import pytest

from benchmarks.lib import check, manifest, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MAN = manifest.load()


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["benchmarks"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert MAN["command"] == ["python3", "benchmarks/run.py"]
    raw = os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert raw <= 64 * 1024


def test_names_units_and_whys_use_the_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MAN[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    assert len(names) == len(set(names))
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_every_cells_files_exist_and_every_metric_has_a_reader(cell):
    w = manifest.cell(MAN, cell)
    config = manifest.config_of(MAN, w)
    entry = next(c for c in MAN["configs"] if c["name"] == w["config"])
    assert entry["file"].startswith("benchmarks/configs/")
    assert entry["source"] == config["source"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    for key in config["reduced"]:
        # no width is cut; `vocab_size` is no width: a share of an
        # expert- or tensor-parallel group holds a slice of the table
        assert not key.endswith(("_dim", "_rank"))
        assert not key.endswith("_size") or key == "vocab_size"
        assert config["published"][key] != config[key]
    assert manifest.job(config).run and manifest.family(config).build
    mix = traffic.load_mix(w["traffic"])
    assert mix["kind"] in ("requests", "token_rows")
    assert set(check.limits_of(cell))
    e2e, per = manifest.metrics_of(MAN, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and per
    for m in e2e + per:
        assert callable(manifest.reader(m["name"]))
    # a job kind is a file of its own under `lib/jobs`
    assert os.path.exists(os.path.join(
        manifest.BENCH, "lib", "jobs", config["job"] + ".py"))
    for m in per:       # a per-layer metric moves one metric of THIS cell
        assert m["moves"] in names


def _reader_files() -> dict:
    folder = os.path.join(manifest.BENCH, "metrics")
    return {f[:-3]: os.path.join(folder, f) for f in os.listdir(folder)
            if f.endswith(".py")}


def test_every_entry_has_its_reader_and_every_reader_its_entry():
    """One entry a metric, one file an entry: a retired entry takes its
    file with it and a file without an entry measures nothing."""
    entries = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert sorted(entries) == sorted(_reader_files())


def test_every_entry_lists_cells_that_exist_or_follows_every_cell():
    """A per-layer entry names the cells in which its reader finds
    something to read; only the set-up entries follow every cell,
    those a later PR adds too."""
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        if "workloads" not in m:
            assert m["moves"] == "setup_s", m["name"]
            continue
        assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
        assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]


def test_no_reader_is_a_delegation_to_another_entrys_file():
    """What two entries share is a function under `lib/`; a reader that
    loads another entry's file makes that entry one nobody may retire."""
    for name, path in _reader_files().items():
        with open(path) as f:
            text = f.read()
        assert "manifest.reader" not in text, name
        assert "benchmarks.metrics" not in text, name
        assert "def read(" in text or re.search(
            r"^(read = |from benchmarks\.lib\.\w+ import \w+ as read\b)",
            text, re.M), name


def test_the_per_layer_list_has_room(capsys):
    """The contract's limit; the free slots are printed for whoever
    sizes the next cell (`pytest -s`)."""
    limit = 128
    assert len(MAN["per_layer"]) <= limit
    with capsys.disabled():
        print(f"\nper_layer: {len(MAN['per_layer'])} of {limit} entries, "
              f"{limit - len(MAN['per_layer'])} free")


def test_every_file_under_paths_is_named_from_the_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, _, files in os.walk(manifest.BENCH):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), manifest.ROOT)
            assert allowed.match(rel) and len(rel) <= 200, rel


def test_run_py_names_no_cell_config_mix_or_metric():
    with open(os.path.join(manifest.BENCH, "run.py")) as f:
        text = f.read()
    words = [e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in MAN[g]]
    words += [w["traffic"] for w in MAN["workloads"]]
    assert not [w for w in words
                if re.search(r"(?<![\w.])" + re.escape(w) + r"(?![\w.])",
                             text)]
    assert json.dumps(MAN)          # and the file is plain JSON
