"""`BENCHMARK.json` held to its rules inside tier-1 (ISSUE 39 asked,
PR 41 brought it): the rules themselves live in ONE place,
`benchmarks/tests/test_manifest.py`, beside the yardstick they guard,
and are collected here by name so that a PR that breaks the manifest
fails the tests the driver runs: an entry a reader file and a file an
entry, `workloads` that name cells that exist, no reader that loads
another entry's file, at most 128 per-layer entries (the free slots
printed), every cell's files found by name, names and units from the
allowed characters."""

from benchmarks.tests.test_manifest import (  # noqa: F401
    test_every_cells_files_exist_and_every_metric_has_a_reader,
    test_every_entry_has_its_reader_and_every_reader_its_entry,
    test_every_entry_lists_cells_that_exist_or_follows_every_cell,
    test_every_file_under_paths_is_named_from_the_allowed_characters,
    test_names_units_and_whys_use_the_allowed_characters,
    test_no_reader_is_a_delegation_to_another_entrys_file,
    test_run_py_names_no_cell_config_mix_or_metric,
    test_the_per_layer_list_has_room, test_top_level_keys_and_limits)
