"""`check.served_gap` with another statistic: the MEAN gap of a served
token's logit below the reference's best, over every served token of
the sample, beside the widest (which is printed and decides nothing).

For a configuration whose routing is a discontinuous function of the
hidden state at most rows. Where a token's top-k experts stand within
rounding of a tie in some layer, the configuration's precision and
float32 pick different experts, both are right, and that row's logits
differ by a tenth of a layer's output whatever the precision: the
widest gap over all rows then reads the same for a sound program and
for a control two precision steps below (`references/joyai.py` cured
that by abstaining near ties, 2 % of rows judged). With 10 of 512 picks
of a near-uniform softmax in each of four layers NO row is clear of a
tie by what rounding moves (PERF.md, PR 32: 0 of 5,249 rows judged), so
nothing is left to abstain to. The mean judges every row: a flipped row
still serves the reference's best token or one close under it, and a
lower precision moves every row further (PERF.md section 7, from PR 26).
(Beside `check.py`, which a PR that adds a cell may not edit.)
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import traffic, weights
from benchmarks.lib.check import pick_sample
from benchmarks.lib.runlog import say


def served_gap(reference, ref_cfg: dict, seed: int, finished: list,
               spec: dict, vocab: int, control=None) -> dict:
    """As `check.served_gap` (same arguments, same result keys); the
    number compared with `spec["limit"]` is the mean gap over all served
    tokens of the sample."""
    import jax
    sample = pick_sample(finished, spec["sample"], seed)
    shapes = reference.param_shapes(ref_cfg)
    params = jax.jit(lambda key: weights.fill(key, shapes))(
        weights.base_key(seed))
    pad_to, n_rows = spec["pad_to"], spec["rows"]
    gaps, control_gaps, per_request = [], [], []
    for r in sample:
        prompt = traffic.token_ids(seed, r["index"], r["prompt_len"], vocab)
        served = np.asarray(r["tokens"], np.int64)
        ids = np.zeros((pad_to,), np.int32)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(served)] = served
        # row P-1+j scores served token j
        rows = np.full((n_rows,), len(prompt) - 1, np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        logits = np.asarray(reference.forward_logits(
            ref_cfg, "highest", params, ids, rows))[:len(served)]
        best = logits.max(-1)
        gap = best - logits[np.arange(len(served)), served]
        gaps.append(gap)
        per_request.append(float(gap.mean()))
        if control:
            low = np.asarray(reference.forward_logits(
                ref_cfg, control, params, ids, rows))[:len(served)]
            control_gaps.append(
                best - logits[np.arange(len(served)), low.argmax(-1)])
    every = np.concatenate(gaps) if gaps else np.zeros((0,))
    mean = float(every.mean()) if every.size else 0.0
    if every.size:
        say(f"served tokens' gaps below the reference's best: mean "
            f"{mean:.5f}, widest {every.max():.4f}, "
            f"{100.0 * (every > 0).mean():.2f} % of {every.size} tokens "
            "are not the reference's best")
    limit = spec["limit"]
    out = {"numbers": [(f"mean gap of a served token's logit below the "
                        f"reference's best ({len(sample)} requests, "
                        f"{every.size} tokens)", mean, limit,
                        bool(sample) and mean <= limit)],
           "per_request": per_request, "tokens": int(every.size)}
    if control:
        out["control"] = float(np.concatenate(control_gaps).mean())
    return out
