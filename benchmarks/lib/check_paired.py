"""`check.served_gap` with a PAIRED statistic: the mean gap of a served
token's logit below the reference's best, MINUS the same mean for the
token the reference itself puts first when its matmuls take the
configuration's own operands (`own_matmul` in the mix's `check`: `bf16`
for a bf16 configuration), over the same rows.

For a configuration in which discontinuous choices set a floor under
every precision. In `keye_longctx_saturated` the 8th of 128 experts
flips for 22-44 % of tokens a layer and a tenth of the 2,048 chosen
tokens in the deeper layers, between float32 and ANY lower precision;
each flip moves a row by a fixed amount, so the error grows with the
root of the rounding, not with it. The mean gap (`check_mean.py`) then
reads 0.006-0.024 for the reference with bf16 operands, 0.007-0.031 for
the sound program and 0.035-0.056 for the int8 control (PERF.md, PR
36): no limit has room on both sides, and the readings follow which
requests the sample drew (0.006-0.043 a request). Taken on the same
rows, the floor is common to all three and cancels: the program reads
-0.002 to 0.007 above the reference's own bf16, the int8 control
0.016-0.034 (`limits/keye_longctx_saturated.json`).
It costs one more pass of the reference.
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
    number compared with `spec["limit"]` is the mean gap of the served
    tokens less the mean gap of the reference's own first tokens under
    `spec["own_matmul"]`, over all served tokens of the sample."""
    import jax
    sample = pick_sample(finished, spec["sample"], seed)
    shapes = reference.param_shapes(ref_cfg)
    params = jax.jit(lambda key: weights.fill(key, shapes))(
        weights.base_key(seed))
    pad_to, n_rows = spec["pad_to"], spec["rows"]
    gaps = {"served": [], "own": [], "control": []}
    per_request = []
    for r in sample:
        prompt = traffic.token_ids(seed, r["index"], r["prompt_len"], vocab)
        served = np.asarray(r["tokens"], np.int64)
        ids = np.zeros((pad_to,), np.int32)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(served)] = served
        # row P-1+j scores served token j
        rows = np.full((n_rows,), len(prompt) - 1, np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))

        def logits_of(matmul):
            return np.asarray(reference.forward_logits(
                ref_cfg, matmul, params, ids, rows))[:len(served)]

        logits = logits_of("highest")
        at = np.arange(len(served))
        best = logits.max(-1)
        gaps["served"].append(best - logits[at, served])
        gaps["own"].append(
            best - logits[at, logits_of(spec["own_matmul"]).argmax(-1)])
        per_request.append(float(gaps["served"][-1].mean()
                                 - gaps["own"][-1].mean()))
        if control:
            gaps["control"].append(
                best - logits[at, logits_of(control).argmax(-1)])
    served, own = (np.concatenate(gaps[k]) if sample else np.zeros((0,))
                   for k in ("served", "own"))
    over = float(served.mean() - own.mean()) if served.size else 0.0
    if served.size:
        say(f"served tokens' gaps below the reference's best: mean "
            f"{served.mean():.5f}, widest {served.max():.4f}, "
            f"{100.0 * (served > 0).mean():.2f} % of {served.size} tokens "
            f"are not the reference's best; the reference's own first "
            f"tokens with {spec['own_matmul']} operands: mean "
            f"{own.mean():.5f}, widest {own.max():.4f}")
    limit = spec["limit"]
    out = {"numbers": [(f"mean gap of a served token's logit below the "
                        f"reference's best, over that of the reference's "
                        f"own {spec['own_matmul']} ({len(sample)} requests, "
                        f"{served.size} tokens)", over, limit,
                        bool(sample) and over <= limit)],
           "per_request": per_request, "tokens": int(served.size)}
    if control:
        out["control"] = float(np.concatenate(gaps["control"]).mean()
                               - own.mean())
    return out
