"""`check_paired.served_gap` for a model generated by diffusion over
blocks: every served token is scored on the reference's logits of the
denoising step that REVEALED it (`references/sdar.block_logits`: the
step's noised copy of the sequence behind the clean one, one forward a
step), not on a causal row.

The statistic is Keye's PAIRED mean and for Keye's reason (the 8th of
128 experts flips under any precision and moves a row by a fixed
amount: `check_paired.py`): the mean gap of a served token's logit
below the reference's best (float32, precision highest), over every
served token of the sample, LESS the same mean for the token the
reference itself puts first with `own_matmul` operands on the same
rows. The cell serves under the `sequential` rule, so the order of the
reveals is known from the tokens alone: output position `j` of a block
is revealed by step `j // (L / steps)` (`references/sdar.step_of`).
(Beside `check.py`, which a PR that adds a cell may not edit.)
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import traffic, weights
from benchmarks.lib.check import pick_sample
from benchmarks.lib.runlog import say


def revealed_logits(reference, ref_cfg: dict, matmul: str, params: dict,
                    ids, prompt_len: int, n_out: int, steps: int,
                    rows=None) -> np.ndarray:
    """Float32 `[n_out, V]`: for every output position of the sequence
    `ids`, the reference's logits at the denoising step that reveals it
    under the `sequential` rule; one doubled forward a step."""
    step = reference.step_of(prompt_len, n_out, ref_cfg["block_length"],
                             steps)
    out = None
    for s in range(steps):
        mine = np.nonzero(step == s)[0]
        if not len(mine):
            continue
        logits = np.asarray(reference.block_logits(
            ref_cfg, matmul, params, ids, prompt_len, n_out, s, steps,
            rows=rows))
        if out is None:
            out = np.zeros(logits.shape, np.float32)
        out[mine] = logits[mine]
    return out


def served_gap(reference, ref_cfg: dict, seed: int, finished: list,
               spec: dict, vocab: int, control=None, *, steps: int) -> dict:
    """As `check_paired.served_gap` (same arguments beside `steps`, the
    engine's `denoise_steps`; same result keys)."""
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

        def logits_of(matmul):
            return revealed_logits(reference, ref_cfg, matmul, params, ids,
                                   len(prompt), len(served), steps, n_rows)

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
        say(f"served tokens' gaps below the reference's best at the step "
            f"that revealed them: mean {served.mean():.5f}, widest "
            f"{served.max():.4f}, {100.0 * (served > 0).mean():.2f} % of "
            f"{served.size} tokens are not the reference's best; the "
            f"reference's own first tokens with {spec['own_matmul']} "
            f"operands: mean {own.mean():.5f}, widest {own.max():.4f}")
    limit = spec["limit"]
    out = {"numbers": [(f"mean gap of a served token's logit below the "
                        f"reference's best at its reveal step, over that of "
                        f"the reference's own {spec['own_matmul']} "
                        f"({len(sample)} requests, {served.size} tokens)",
                        over, limit, bool(sample) and over <= limit)],
           "per_request": per_request, "tokens": int(served.size)}
    if control:
        out["control"] = float(np.concatenate(gaps["control"]).mean()
                               - own.mean())
    return out
