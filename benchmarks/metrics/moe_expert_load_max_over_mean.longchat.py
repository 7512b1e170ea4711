"""The straggler: the busiest held expert's assignments in a decode
tick's layer over the mean held expert's, from deltas of the engine's
counters (`fstpu_moe_max_expert_tokens_total`,
`fstpu_moe_assignments_held_total`), the mean taken over the
`num_experts` held."""
from benchmarks.lib import obsutil


def read(obs):
    busiest = obsutil.counter_delta(obs,
                                    "fstpu_moe_max_expert_tokens_total")
    held = obsutil.counter_delta(obs, "fstpu_moe_assignments_held_total")
    if not held or busiest is None:
        return None
    return busiest * obs["config"]["num_experts"] / held
