"""The one traffic generator: a mix file in, requests out.

A mix fixes a TABLE of `table_size` rows on the quantile grid of its
length distributions (and, for an open loop, of its inter-arrival
gaps). Every consecutive block of `table_size` requests is a
permutation of that table drawn from `--seed`, so every run offers the
same tokens, the same bucket counts and the same gaps per block; the
seed chooses the order and the token ids. Nothing here knows a mix by
name; a new mix is a new file of these parameters.
"""

from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def quantile_grid(dist: dict, k: int) -> list:
    """`k` values of `dist` at evenly spaced quantiles, in rising order."""
    kind = dist["dist"]
    if kind == "const":
        return [dist["value"]] * k
    if kind == "log_uniform":       # both ends are on the grid
        lo, hi = dist["min"], dist["max"]
        return [lo * (hi / lo) ** (i / (k - 1)) for i in range(k)]
    mid = [(i + 0.5) / k for i in range(k)]
    if kind == "log_normal":
        z = NormalDist()
        vals = [dist["median"] * math.exp(dist["sigma"] * z.inv_cdf(q))
                for q in mid]
        return [min(max(v, dist["min"]), dist["max"]) for v in vals]
    if kind == "exponential":       # scaled so the table's mean is exact
        vals = [-math.log(1.0 - q) for q in mid]
        scale = dist["mean"] * k / sum(vals)
        return [v * scale for v in vals]
    raise ValueError(f"unknown distribution {kind!r}")


def request_table(mix: dict) -> list:
    """[(prompt_len, output_len)] x table_size. Row i takes the i-th
    prompt quantile and the (i * stride + offset)-th output quantile:
    the file's fixed pairing, which spreads output lengths over prompt
    lengths and so over the lanes."""
    k = mix["table_size"]
    prompts = [int(round(v)) for v in quantile_grid(mix["prompt_len"], k)]
    outputs = [int(round(v)) for v in quantile_grid(mix["output_len"], k)]
    stride, offset = mix["pairing"]["stride"], mix["pairing"]["offset"]
    if math.gcd(stride, k) != 1:
        raise ValueError("pairing stride must be coprime with table_size")
    return [(prompts[i], outputs[(i * stride + offset) % k])
            for i in range(k)]


def gap_table(mix: dict) -> list:
    """Inter-arrival gaps (s) of an open loop, one per table row."""
    rate = mix["arrivals"]["rate_per_s"]
    dist = dict(mix["arrivals"], mean=1.0 / rate)
    return quantile_grid(dist, mix["table_size"])


def _rng(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def block(mix: dict, seed: int, b: int) -> list:
    """Requests of block `b`: a seeded permutation of the table (and,
    independently, of the gap table)."""
    table, k = request_table(mix), mix["table_size"]
    order = _rng(seed, b, 0).permutation(k)
    rows = [{"index": b * k + j, "prompt_len": table[i][0],
             "output_len": table[i][1]} for j, i in enumerate(order)]
    if mix["loop"] == "open":
        gaps = gap_table(mix)
        for row, g in zip(rows, _rng(seed, b, 1).permutation(k)):
            row["gap_s"] = gaps[g]
    return rows


def requests(mix: dict, seed: int):
    """The endless request sequence of a run, block after block."""
    b = 0
    while True:
        yield from block(mix, seed, b)
        b += 1


def token_ids(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    """Prompt `index`: `n` ids in [1, vocab), distinct from every other
    prompt (no shared prefixes beyond chance)."""
    return _rng(seed, index, 2).integers(1, vocab, n, dtype=np.int64)


def token_rows(seed: int, first_row: int, n_rows: int, seq: int,
               vocab: int) -> np.ndarray:
    """Training rows `first_row`..: full packed sequences of `seq` ids,
    every row different."""
    return np.stack([_rng(seed, r, 3).integers(1, vocab, seq, dtype=np.int64)
                     for r in range(first_row, first_row + n_rows)]
                    ).astype(np.int32)
