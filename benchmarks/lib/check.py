"""The comparisons that decide `correct`, against the plain references.

Each returns {"numbers": [(what, value, limit, ok)], ...}; limits come
from `benchmarks/limits/<cell>.json`, set from the readings PERF.md
records. A `control` (a lower-precision matmul name) is read beside
the sound number where the control tool asks for it.
"""

from __future__ import annotations

import json
import os
import statistics

import numpy as np

from benchmarks.lib import traffic, weights

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def limits_of(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", cell + ".json")) as f:
        return json.load(f)


def pick_sample(finished: list, n: int, seed: int) -> list:
    """`n` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    ordered = sorted(finished, key=lambda r: r["index"])
    longest = max(ordered, key=lambda r: (r["prompt_len"] + r["output_len"],
                                          -r["index"]))
    rest = [r for r in ordered if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    take = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(take)]


def served_gap(reference, ref_cfg: dict, seed: int, finished: list,
               spec: dict, vocab: int, control=None) -> dict:
    """The widest gap by which a served (greedy) token's logit lies
    below the reference's best, over every served token of a sample of
    finished requests. The reference runs once over each prompt with
    its served tokens."""
    import jax
    import jax.numpy as jnp
    sample = pick_sample(finished, spec["sample"], seed)
    shapes = reference.param_shapes(ref_cfg)
    params = jax.jit(lambda key: weights.fill(key, shapes))(
        weights.base_key(seed))
    pad_to = spec["pad_to"]
    n_rows = spec["rows"]       # one shape, so one compiled program
    widest, control_widest, n_tokens, per_request = 0.0, None, 0, []
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
        gaps = best - logits[np.arange(len(served)), served]
        per_request.append(float(gaps.max()))
        widest = max(widest, float(gaps.max()))
        n_tokens += len(served)
        if control:
            low = np.asarray(reference.forward_logits(
                ref_cfg, control, params, ids, rows))[:len(served)]
            cgaps = best - logits[np.arange(len(served)), low.argmax(-1)]
            control_widest = max(control_widest or 0.0, float(cgaps.max()))
    limit = spec["limit"]
    ok = bool(sample) and widest <= limit
    out = {"numbers": [(f"widest gap of a served token's logit below the "
                        f"reference's best ({len(sample)} requests, "
                        f"{n_tokens} tokens)", widest, limit, ok)],
           "per_request": per_request, "tokens": n_tokens}
    if control:
        out["control"] = control_widest
    return out


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """The gap between the program's norm and the reference's, by the
    worst leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but
    zero)."""
    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {set(program) ^ set(reference)}")
    floor = statistics.median(reference.values())
    return max(abs(program[k] - reference[k]) / max(reference[k], floor)
               for k in reference)


def training_numbers(program: dict, reference: dict, limits: dict) -> list:
    """Loss of each followed step, the first gradient's norm and the
    parameters' change, program against reference."""
    numbers = []
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        gap = abs(a - b)
        numbers.append((f"loss at step {i + 1}: |{a:.6f} - {b:.6f}|", gap,
                        limits["loss_gap"], gap <= limits["loss_gap"]))
    g = worst_leaf_gap(program["grad_norm"], reference["grad_norm"])
    numbers.append(("first gradient's norm, worst leaf's relative gap", g,
                    limits["grad_norm_gap"], g <= limits["grad_norm_gap"]))
    d = worst_leaf_gap(program["change_norm"], reference["change_norm"])
    numbers.append(("parameters' change over the followed steps, worst "
                    "leaf's relative gap", d, limits["change_norm_gap"],
                    d <= limits["change_norm_gap"]))
    return numbers
