"""The request table belongs to the mix, not to the seed."""

from collections import Counter

import pytest

from benchmarks.lib import traffic

MIXES = ["doc_closed_64", "chat_open_steady"]


def _rows(mix, seed, b):
    return [(r["prompt_len"], r["output_len"])
            for r in traffic.block(mix, seed, b)]


@pytest.mark.parametrize("name", MIXES)
def test_every_block_is_the_same_multiset_under_any_seed(name):
    mix = traffic.load_mix(name)
    table = Counter(traffic.request_table(mix))
    assert sum(table.values()) == mix["table_size"] == 32
    for seed in (0, 7, 2 ** 31 + 5):
        for b in (0, 1, 17):
            assert Counter(_rows(mix, seed, b)) == table


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_differ_in_order_and_one_seed_repeats(name):
    mix = traffic.load_mix(name)
    assert _rows(mix, 1, 0) != _rows(mix, 2, 0)
    assert _rows(mix, 1, 0) != _rows(mix, 1, 1)
    assert _rows(mix, 1, 3) == _rows(mix, 1, 3)


def test_the_sequence_is_blocks_in_order():
    mix = traffic.load_mix("doc_closed_64")
    gen = traffic.requests(mix, 11)
    first = [next(gen) for _ in range(64)]
    assert [r["index"] for r in first] == list(range(64))
    assert first[32:] == traffic.block(mix, 11, 1)


def test_doc_table_is_the_grid_the_file_states():
    mix = traffic.load_mix("doc_closed_64")
    table = traffic.request_table(mix)
    prompts = sorted(p for p, _ in table)
    outputs = sorted(o for _, o in table)
    assert (prompts[0], prompts[-1]) == (512, 2048)
    assert (outputs[0], outputs[-1]) == (32, 128)
    ladder = sorted(mix["engine_args"]["buckets"])
    buckets = Counter(min(b for b in ladder if p <= b) for p in prompts)
    assert buckets == {512: 1, 1024: 15, 2048: 16}
    # outputs are spread over prompt lengths, so lanes do not finish in
    # waves: both halves of the prompt grid hold short and long outputs
    short = [o for p, o in table if p <= 1024]
    assert min(short) < 48 and max(short) > 96
    # a lane's blocks hold the largest bucket and the longest output
    lane = mix["engine_args"]["kv_max_blocks_per_slot"] * 128
    assert lane >= 2048 + mix["engine_args"]["max_new_tokens"]


def test_open_loop_gaps_have_the_files_rate_in_every_block():
    mix = traffic.load_mix("chat_open_steady")
    rate = mix["arrivals"]["rate_per_s"]
    for seed in (3, 2 ** 31 + 9):
        gaps = [r["gap_s"] for r in traffic.block(mix, seed, 4)]
        assert sum(gaps) == pytest.approx(32 / rate, rel=1e-12)
        assert sorted(gaps) == sorted(traffic.gap_table(mix))
    lens = traffic.request_table(mix)
    assert all(32 <= p <= 1024 and 16 <= o <= 256 for p, o in lens)
    assert max(p for p, _ in lens) <= max(mix["engine_args"]["buckets"])


def test_prompts_differ_and_seeds_over_31_bits_work():
    a = traffic.token_ids(2 ** 31 + 3, 0, 64, 32768)
    b = traffic.token_ids(2 ** 31 + 3, 1, 64, 32768)
    assert (a != b).any() and a.min() >= 1 and a.max() < 32768
    assert (a == traffic.token_ids(2 ** 31 + 3, 0, 64, 32768)).all()
    rows = traffic.token_rows(5, 10, 4, 16, 100)
    assert rows.shape == (4, 16)
    assert (rows[1] == traffic.token_rows(5, 11, 1, 16, 100)[0]).all()
    assert len({tuple(r) for r in rows}) == 4
