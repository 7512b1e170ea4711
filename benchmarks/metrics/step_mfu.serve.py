"""The whole model step's share of the chip's peak: the operations the
served tokens REQUIRE of the model's matrices (`lib/costs_step.py`: 2 a
weight a token for every prompt token credited in the window and every
output token, the experts by the picks that land here, the head for one
row a prompt and every output token; the products over the context left
out, so it is a floor) over the window's seconds, the chips and the
published bf16 peak. Beside the kernels' shares of their rooflines: a
kernel taken off the path leaves its roofline silent, this stays."""
import importlib

from benchmarks.lib import costs_step, manifest
from benchmarks.lib import reduce as R


def read(obs):
    if "records" not in obs or "window" not in obs:
        return None
    family = manifest.family(obs["config"])
    shapes = importlib.import_module(family.REFERENCE).param_shapes(
        family.reference_config(obs["config"]))
    body, head = costs_step.weight_flops_per_token(shapes, obs["config"])
    lo, hi = obs["window"]
    tokens = R.credited_tokens(obs["records"], lo, hi)
    if not tokens["total"]:
        return None
    needed = body * tokens["total"] + head * tokens["output"]
    return 100.0 * needed / (hi - lo) / (
        obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
