"""Tokens/s times the operations forward and backward REQUIRE per token
(attention in, recomputation out), over chips times the published
peak."""
from benchmarks.lib import costs


def read(obs):
    lo, hi = obs["window"]
    rate = obs["steps_in_window"] * obs["tokens_per_step"] / (hi - lo)
    flops = costs.gpt2_train_flops_per_token(obs["config"], obs["seq"])
    return 100.0 * rate * flops / (obs["chips"] *
                                   obs["peaks"]["bf16_flops_per_s"])
