"""The two readers of the engine's block counters against an `obs` made
by hand: every answer can be checked on paper."""

import pytest

from benchmarks.lib import manifest

LIVE = "fstpu_serving_kv_blocks_live_total"
TABLED = "fstpu_serving_kv_blocks_tabled_total"
NAMES = ["decode_live_block_share.chat", "decode_live_block_share.serve"]


def read(name, obs):
    return manifest.reader(name)(obs)


@pytest.mark.parametrize("name", NAMES)
def test_live_over_tabled_blocks_from_counter_deltas(name):
    # 100 ticks of 32 lanes on a table of 10: 32,000 blocks tabled;
    # 5 lanes of 3 blocks and 27 free lanes of one null block a tick:
    # 4,200 walked
    obs = {"stats_open": {LIVE: 1000.0, TABLED: 64000.0},
           "stats_close": {LIVE: 5200.0, TABLED: 96000.0}}
    assert read(name, obs) == pytest.approx(100.0 * 4200 / 32000)
    assert read(name, obs) == pytest.approx(13.125)
    # every lane full to its row's end
    obs["stats_close"][LIVE] = 33000.0
    assert read(name, obs) == pytest.approx(100.0)
    # 100 ticks of 64 lanes on a table of 144; 63 lanes of 60 blocks
    # and one released lane's null block a tick (the folded kernel)
    obs = {"stats_open": {LIVE: 500.0, TABLED: 1000.0},
           "stats_close": {LIVE: 500.0 + 100 * (63 * 60 + 1),
                           TABLED: 1000.0 + 100 * 64 * 144}}
    assert read(name, obs) == pytest.approx(100.0 * 3781 / 9216)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_returns_none_and_does_not_raise(name):
    """The parent's engine has neither counter; a slot-layout engine
    has both at zero; a run without `/metrics` snapshots has no
    `stats_open`."""
    ticks = "fstpu_serving_decode_ticks_total"
    assert read(name, {}) is None
    assert read(name, {"trace": None}) is None
    assert read(name, {"stats_open": {ticks: 1.0},
                       "stats_close": {ticks: 9.0}}) is None
    assert read(name, {"stats_open": {LIVE: 0.0, TABLED: 0.0},
                       "stats_close": {LIVE: 0.0, TABLED: 0.0}}) is None


def test_both_are_declared_for_the_cells_whose_kernel_walks_live_blocks():
    man = manifest.load()
    got = {m["name"]: m for m in man["per_layer"] if m["name"] in NAMES}
    assert sorted(got) == NAMES
    assert got[NAMES[0]]["workloads"] == ["mistral_chat_steady"]
    # the paged decode kernel and the folded kernel (PR 31, PR 33)
    assert got[NAMES[1]]["workloads"] == ["mistral_doc_saturated",
                                          "qwen3next_longchat_saturated"]
    assert got[NAMES[0]]["moves"] == "gap_p50_ms"
    assert got[NAMES[1]]["moves"] == "serve_tokens_per_s"
    for m in got.values():
        assert (m["layer"], m["source"], m["better"], m["unit"]) == \
            ("kernels", "program_counter", "lower", "%")
