"""`decode_live_block_share.longchat` against an `obs` made by hand (as
`test_block_share_readers.py` checks its two siblings)."""

import pytest

from benchmarks.lib import manifest

LIVE = "fstpu_serving_kv_blocks_live_total"
TABLED = "fstpu_serving_kv_blocks_tabled_total"
NAME = "decode_live_block_share.longchat"


def test_live_over_tabled_blocks_from_counter_deltas():
    # 100 ticks of 64 lanes on a table of 144: 921,600 blocks tabled;
    # 63 lanes of 60 blocks and one released lane's null block a tick
    read = manifest.reader(NAME)
    obs = {"stats_open": {LIVE: 500.0, TABLED: 1000.0},
           "stats_close": {LIVE: 500.0 + 100 * (63 * 60 + 1),
                           TABLED: 1000.0 + 100 * 64 * 144}}
    assert read(obs) == pytest.approx(100.0 * 3781 / 9216)
    assert read({}) is None
    assert read({"stats_open": {LIVE: 0.0, TABLED: 0.0},
                 "stats_close": {LIVE: 0.0, TABLED: 0.0}}) is None


def test_declared_for_the_qwen3next_cell_only():
    entry, = [m for m in manifest.load()["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "serve_tokens_per_s",
                     "workloads": ["qwen3next_longchat_saturated"]}
