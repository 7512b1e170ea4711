"""The straggler: the busiest expert's assignments in a decode tick's
layer over the mean expert's, from deltas of the engine's counters
(`fstpu_moe_max_expert_tokens_total`, `fstpu_moe_assignments_total`,
`fstpu_moe_layer_ticks_total`), the mean taken over all
`n_routed_experts`."""
from benchmarks.lib import obsutil


def read(obs):
    busiest = obsutil.counter_delta(obs,
                                    "fstpu_moe_max_expert_tokens_total")
    total = obsutil.counter_delta(obs, "fstpu_moe_assignments_total")
    if not total or busiest is None:
        return None
    return busiest * obs["config"]["n_routed_experts"] / total
