"""Prefill/decode disaggregation (docs/disaggregation.md).

Submodule map — import weight matters here because the fleet router
imports this package in its NO-JAX process:

- `transfer`: the stdlib HTTP push of an exported lane (checksum,
  size cap, timeout). No jax.
- `policy`: phase-aware placement (`plan_handoff`, `topology`) the
  fleet router consults per request. No jax.
- `coordinator`: the replica-side orchestration (export → push →
  detach, adopt → collect). Imports the serving engine, so it is NOT
  imported here — the api layer imports
  `fengshen_tpu.disagg.coordinator` explicitly.
"""

from fengshen_tpu.disagg import policy, transfer
from fengshen_tpu.disagg.policy import (HandoffPlan, plan_handoff,
                                        topology, validate_phase)
from fengshen_tpu.disagg.transfer import (KvPushError, payload_checksum,
                                          push_payload, seal,
                                          verify_checksum)

__all__ = [
    "policy", "transfer", "HandoffPlan", "plan_handoff", "topology",
    "validate_phase", "KvPushError", "payload_checksum",
    "push_payload", "seal", "verify_checksum",
]
