"""Phase-aware placement policy for prefill/decode disaggregation.

Pure stdlib, NO jax — this module runs inside the fleet router process
(`fleet/router.py` consults it per placement), and the fleet package's
no-jax contract (pinned by subprocess test) extends to everything the
router imports.

Replicas advertise a `phase` in `/stats` (`prefill` | `decode` |
`both`, from the server config's `--phases` spawn flag):

- ``prefill`` tiers take admissions, prime the lane, and push the KV
  prefix to a decode peer;
- ``decode`` tiers adopt pushed lanes and run the long decode tail;
- ``both`` (the default) is the homogeneous mode — a fleet with no
  phase split routes exactly as before this module existed.

`plan_handoff` returns a (prefill, decode) pair only when the fleet
actually has BOTH tiers healthy; every degenerate topology (all-both,
prefill-only, decode-only) returns None and the router falls back to
plain least-occupancy placement — disaggregation is an optimization,
never a new way to fail a request.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence

#: the valid replica phase labels, in docs order
PHASES = ("prefill", "decode", "both")


def validate_phase(phase: str) -> str:
    """Normalize + reject unknown phase labels (config-load guard)."""
    p = str(phase or "both").strip().lower()
    if p not in PHASES:
        raise ValueError(
            f"unknown replica phase {phase!r}; expected one of {PHASES}")
    return p


@dataclasses.dataclass(frozen=True)
class HandoffPlan:
    """One placement decision: prime on `prefill`, decode on `decode`.
    The fields are the router's replica records (duck-typed: anything
    with `phase` and `occupancy()`)."""
    prefill: Any
    decode: Any


def _least_occupied(replicas: Sequence[Any]) -> Optional[Any]:
    best = None
    best_occ = None
    for rep in replicas:
        occ = rep.occupancy()
        if best is None or occ < best_occ:
            best, best_occ = rep, occ
    return best


def plan_handoff(candidates: Sequence[Any]) -> Optional[HandoffPlan]:
    """Pick the least-occupied prefill and decode replicas from the
    router's HEALTHY candidate list (ties by iteration order, which
    the router keeps index-sorted — deterministic placement).

    Returns None unless at least one healthy replica of EACH dedicated
    phase exists: a fleet mid-rollout (decode tier down, prefill tier
    up) must keep serving through the homogeneous path rather than
    pushing lanes nowhere.
    """
    prefills = [r for r in candidates if r.phase == "prefill"]
    decodes = [r for r in candidates if r.phase == "decode"]
    if not prefills or not decodes:
        return None
    return HandoffPlan(prefill=_least_occupied(prefills),
                       decode=_least_occupied(decodes))


def plan_evacuation(peers: Sequence[dict]) -> List[str]:
    """Rank evacuation targets for drain-time lane rescue
    (docs/fault_tolerance.md "Preemption runbook"). `peers` are probed
    `/stats` snapshots as plain dicts — at least ``url``, plus
    ``draining`` / ``phase`` / ``slots_active`` / ``num_slots`` /
    ``queue_depth`` when the probe answered (missing fields default
    safe). Returns peer urls best-first; the coordinator pushes each
    lane down the list until one adopts.

    Ordering: draining peers are excluded entirely (they are leaving
    too — an evacuated lane must not need a SECOND rescue seconds
    later); dedicated prefill tiers rank after decode/both replicas
    (an evacuated lane is mid-decode work); within a tier, least
    occupancy first with input order breaking ties — the same
    determinism contract as `plan_handoff`. An empty result means
    every lane finishes locally, never an error."""
    ranked = []
    for i, peer in enumerate(peers):
        if peer.get("draining"):
            continue
        phase = str(peer.get("phase") or "both")
        denom = max(int(peer.get("num_slots") or 0), 1)
        occ = (int(peer.get("slots_active") or 0)
               + int(peer.get("queue_depth") or 0)) / denom
        ranked.append((1 if phase == "prefill" else 0, occ, i,
                       str(peer["url"])))
    ranked.sort(key=lambda t: t[:3])
    return [url for _, _, _, url in ranked]


def topology(phases: Sequence[str]) -> str:
    """Canonical topology label `/fleet` shows: ``"homogeneous"``
    when no replica declares a dedicated phase, else
    ``"prefill=P,decode=D"`` (with ``,both=B`` appended when mixed).
    """
    counts = {p: 0 for p in PHASES}
    for p in phases:
        counts[validate_phase(p)] += 1
    if counts["prefill"] == 0 and counts["decode"] == 0:
        return "homogeneous"
    label = f"prefill={counts['prefill']},decode={counts['decode']}"
    if counts["both"]:
        label += f",both={counts['both']}"
    return label
