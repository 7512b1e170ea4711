"""What the `.longchat` readers add to `lib.trace_sala`: device time of
the operations under a scope INSIDE the runs of one program. The short
convolution's scope and the experts' are in the window program and in
the decode program alike, and XLA:TPU drops the `op_name` of a
`ragged-dot` call, so a scope's text cannot say which program an
operation belongs to; the module line's intervals can. Every function
returns None where the run has no trace or the thing is not in it."""

from __future__ import annotations

import bisect

from benchmarks.lib import obsutil, scopes, xplane, xplane_attrs
from benchmarks.lib.trace_sala import DECODE, WINDOW  # noqa: F401

#: the device scopes of the two mixers (fengshen_tpu/ops)
MIXER_SCOPES = ("fstpu_gated_delta_prefill", "fstpu_gated_delta_decode",
                "fstpu_short_conv", "fstpu_gated_attention_decode",
                "fstpu_gated_attention_prefill")
#: the experts' scopes, and the grouped matmuls by their own name
MOE_SCOPES = ("fstpu_moe_route", "fstpu_moe_experts", "fstpu_moe_shared",
              "%ragged-dot")
#: what of them is the routed experts' own work
EXPERT_SCOPES = ("fstpu_moe_experts", "%ragged-dot")


def scope_seconds_in(obs: dict, names, program):
    """(device seconds of the operations under any of `names` inside
    the runs of the programs matching `program` that lie in the traced
    window, those runs' count), or None."""
    t, ops, attrs = obsutil.traced(obs), scopes.of(obs), xplane_attrs.of(obs)
    if t is None or not ops or attrs is None:
        return None
    _, lo, hi = t
    marks = (names,) if isinstance(names, str) else tuple(names)
    under = sorted((e for e in ops if any(m in e[0] for m in marks)),
                   key=lambda e: e[1])
    runs = [(s, s + d) for n, s, d in attrs["modules"]
            if program.search(n) and s >= lo and s + d <= hi]
    if not under or not runs:
        return None
    starts = [e[1] for e in under]
    total = 0.0
    for a, b in runs:
        # an operation of a run starts inside it; a `while` may have
        # started before its body's operations: look a little back
        i = max(bisect.bisect_left(starts, a) - 1, 0)
        j = bisect.bisect_right(starts, b)
        total += sum(y - x for x, y in xplane.merged(under[i:j], a, b))
    return total, len(runs)
