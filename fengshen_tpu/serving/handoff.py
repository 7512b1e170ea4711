"""KV-lane handoff: serialize a primed lane out of one engine and
resume it on another (prefill/decode disaggregation, docs/
disaggregation.md).

`export_lane` snapshots a RUNNING request's committed KV prefix,
history row and scheduler cursors into a versioned wire payload;
`adopt_lane` validates the header against the receiving engine and
scatters the lane into a free slot (or block run) so the next decode
tick resumes from the exact committed position. `detach_lane` retires
the source lane once the receiver has acknowledged adoption.

Wire-format invariants (version 1):

- KV travels int8-quantized with per-(token, head) fp32 absmax scales
  (`ops/int8_matmul.quantize_kv`) even when both tiers run fp32 — the
  4x payload shrink is the point of the int8 KV work (PR 6). An int8
  SOURCE pool exports its stored bits verbatim (no re-quantization),
  so an int8→int8 handoff is bit-identical end to end; an fp32 source
  pays exactly one quantization of the prefix (accuracy note in
  docs/disaggregation.md).
- The exported prefix covers physical positions ``[0, phys)`` only.
  The engine's decode tick writes ``_last_tok`` at ``phys`` BEFORE its
  forward, so the pending token rides in the payload header
  (``last_tok``) and the receiver's first tick re-commits it — the
  cache never carries a position the scheduler hasn't.
- The export is of COMMITTED state: the engine keeps one decode tick
  in flight, so `export_lane` first fetches and commits it
  (`_drain_locked`); the host cursors then stand at the committed
  position and the pending token is the request's last one.
- Everything here is EAGER jnp gather/scatter on the scheduler lock —
  no new jitted programs, so the engine's pinned compile counts
  (one decode program, one assign program, one prefill per bucket)
  are untouched by handoffs.

Layout/dtype are free to differ between the tiers: the receiver
re-bases the lane on its own pool (slot or paged, fp32 or int8); only
the model fingerprint and the generation controls must match exactly.
"""

from __future__ import annotations

import base64
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fengshen_tpu.disagg import transfer
from fengshen_tpu.ops.int8_matmul import dequantize_kv, quantize_kv
from fengshen_tpu.serving.engine import RUNNING, Request
from fengshen_tpu.serving.paged_cache import (_map_attn_dicts,
                                              blocks_for_tokens, row_leaves,
                                              state_leaves)

#: wire header constants — adopt declines any mismatch with "version"
WIRE_KIND = "fstpu-kv-handoff"
WIRE_VERSION = 1

#: terminal state of a lane that left this engine via `detach_lane`
HANDED_OFF = "handed_off"

#: terminal state of a lane that left via live evacuation during drain
#: (docs/fault_tolerance.md "Preemption runbook") — same mechanics as
#: handed_off, but the API layer answers the blocked POST with a
#: disagg-style redirect so the router re-collects from the adopter
EVACUATED = "evacuated"

#: EngineConfig fields that must match exactly across a handoff: the
#: receiver resumes mid-generation, so any divergence here would
#: silently change the sampled distribution or the stop condition
CONTROL_FIELDS = ("eos_token_id", "pad_token_id", "do_sample",
                  "temperature", "top_k", "top_p", "repetition_penalty",
                  "no_repeat_ngram_size", "min_length", "seed")


class HandoffError(Exception):
    """Export-side failure (request not exportable from this engine)."""


class AdoptDecline(Exception):
    """Adopt-side refusal; `reason` is the wire/metric label."""

    def __init__(self, reason: str, message: Optional[str] = None):
        super().__init__(message or reason)
        self.reason = reason


def _b64(arr: np.ndarray) -> dict:
    return {"dtype": str(arr.dtype), "shape": list(arr.shape),
            "data": base64.b64encode(
                np.ascontiguousarray(arr).tobytes()).decode("ascii")}


def _unb64(d: dict) -> np.ndarray:
    return np.frombuffer(
        base64.b64decode(d["data"]),
        dtype=np.dtype(d["dtype"])).reshape(d["shape"])


def _gather_lane(leaf, axis: int, phys: int, slot: Optional[int],
                 blocks: Optional[List[int]]):
    """The committed prefix ``[0, phys)`` of one lane as a host array.

    `axis` is the pool's slot axis (vals: ndim-4, scales: ndim-3);
    leading layer axes pass through untouched. Paged pools gather the
    lane's blocks and merge the (block, offset) axes back into one
    contiguous token axis — the inverse of `assign_paged`'s scatter.
    """
    if blocks is None:
        lane = jnp.take(leaf, slot, axis=axis)
    else:
        g = jnp.take(leaf, jnp.asarray(blocks, jnp.int32), axis=axis)
        shp = g.shape
        lane = g.reshape(shp[:axis] + (shp[axis] * shp[axis + 1],) +
                         shp[axis + 2:])
    return np.asarray(jax.lax.slice_in_dim(lane, 0, phys, axis=axis))


def _scatter_lane(leaf, axis: int, val, slot: Optional[int],
                  positions: Optional[np.ndarray]):
    """Write a `[..., phys, ...]` lane prefix into the pool at `slot`
    (slot layout) or at flat token `positions` (paged layout)."""
    val = jnp.asarray(val)
    if positions is None:
        idx = (slice(None),) * axis + (slot,
                                       slice(0, val.shape[axis]))
        return leaf.at[idx].set(val)
    nb, bs = leaf.shape[axis], leaf.shape[axis + 1]
    flat = leaf.reshape(leaf.shape[:axis] + (nb * bs,) +
                        leaf.shape[axis + 2:])
    idx = (slice(None),) * axis + (positions,)
    return flat.at[idx].set(val).reshape(leaf.shape)


#: the row leaves the wire format carries (its "k" and "v" entries)
WIRE_LEAVES = ["cached_key", "cached_value"]


def _undeclared_on_wire(engine) -> Optional[str]:
    """None when the engine's cache declares exactly the K/V pair the
    wire carries; else the one loud sentence both sides refuse with.
    A latent cache (one `cached_latent` row a token) has no wire
    format yet, nor has a per-lane state (a `state_*` leaf: a snapshot
    of it is what a wire and a preemption would both need) or a row
    leaf of another rate (ROADMAP D4, M6)."""
    if engine.block_length:
        return ("the handoff wire carries a lane at a committed cursor of "
                "one token a tick; this engine's lanes are inside a "
                f"generation block ({engine.block_length} tokens and their "
                "masked flags on the device, a cursor that moves a block "
                "at a time), which has no wire format yet")
    found: List[list] = []
    _map_attn_dicts(engine._cache, lambda d: found.append(
        row_leaves(d) + state_leaves(d)) or d)
    odd = [names for names in found if names != WIRE_LEAVES]
    if not odd:
        return None
    return (f"the handoff wire carries {WIRE_LEAVES} lanes; this "
            f"engine's cache declares {odd[0]}, which has no wire "
            "format yet")


def export_lane(engine, request_id: str) -> dict:
    """Serialize the RUNNING request `request_id` into a sealed wire
    payload. The engine keeps decoding the lane afterwards — export is
    a SNAPSHOT; call `detach_lane` only once the receiver has adopted.

    Raises `HandoffError` when the request isn't currently running in
    a lane (still queued, already finished, unknown) or the engine is
    speculative (a mid-verify draft window has no committed cursor to
    cut at).
    """
    with engine._cv:
        if engine.spec:
            raise HandoffError(
                "speculative engines do not export lanes "
                "(no committed cursor inside a verify window)")
        refusal = _undeclared_on_wire(engine)
        if refusal:
            raise HandoffError(refusal)
        engine._drain_locked()
        req = None
        for r in engine._slot_req:
            if r is not None and r.request_id == request_id:
                req = r
                break
        if req is None or req.state != RUNNING:
            raise HandoffError(
                f"request {request_id!r} is not running in a lane")
        slot = req.slot
        phys = int(engine._phys[slot])
        pos = int(engine._pos[slot])
        last_tok = req.tokens[-1]
        bucket = phys - (len(req.tokens) - 1)
        blocks = engine._slot_blocks[slot] if engine.paged else None
        int8_src = engine.config.kv_dtype == "int8"
        layers: List[dict] = []

        def grab(d):
            entry = {}
            for name, leaf_key, scale_key in (
                    ("k", "cached_key", "cached_key_scale"),
                    ("v", "cached_value", "cached_value_scale")):
                if int8_src:
                    q = _gather_lane(d[leaf_key], d[leaf_key].ndim - 4,
                                     phys, slot, blocks)
                    s = _gather_lane(d[scale_key],
                                     d[scale_key].ndim - 3, phys, slot,
                                     blocks)
                else:
                    lane = _gather_lane(d[leaf_key],
                                        d[leaf_key].ndim - 4, phys,
                                        slot, blocks)
                    qj, sj = quantize_kv(jnp.asarray(lane))
                    q, s = np.asarray(qj), np.asarray(
                        sj, dtype=np.float32)
                entry[name] = _b64(np.asarray(q))
                entry[name + "_scale"] = _b64(
                    np.asarray(s, dtype=np.float32))
            layers.append(entry)
            return d

        _map_attn_dicts(engine._cache, grab)
        now = engine._clock()
        deadline_remaining = None if req.deadline is None else \
            max(float(req.deadline - now), 0.0)
        payload = {
            "kind": WIRE_KIND,
            "version": WIRE_VERSION,
            "model_fingerprint": repr(engine.model.config),
            "request_id": req.request_id,
            "source": {"kv_layout": engine.config.kv_layout,
                       "kv_dtype": engine.config.kv_dtype},
            "wire_dtype": "int8",
            "bucket": int(bucket),
            "phys": phys,
            "pos": pos,
            "last_tok": last_tok,
            "prompt": [int(t) for t in req.prompt],
            "tokens": [int(t) for t in req.tokens],
            "max_new_tokens": int(req.max_new_tokens),
            "deadline_remaining_s": deadline_remaining,
            "ttft_s": req.ttft_s,
            "controls": {f: getattr(engine.config, f)
                         for f in CONTROL_FIELDS},
            "trace": {"trace_id": req.timeline.trace_id,
                      "parent_span_id": req.timeline.parent_span_id},
            "layers": layers,
        }
        req.timeline.add(now, "handoff_export", phys=phys,
                         layers=len(layers))
    return transfer.seal(payload)


def _validate_header(engine, payload: dict) -> None:
    if payload.get("kind") != WIRE_KIND or \
            payload.get("version") != WIRE_VERSION:
        raise AdoptDecline("version",
                           f"unsupported wire header "
                           f"{payload.get('kind')!r} "
                           f"v{payload.get('version')!r}")
    if not transfer.verify_checksum(payload):
        raise AdoptDecline("checksum", "payload checksum mismatch")
    if payload.get("model_fingerprint") != repr(engine.model.config):
        raise AdoptDecline("model_fingerprint",
                           "model config differs between tiers")
    controls = payload.get("controls") or {}
    for f in CONTROL_FIELDS:
        if f not in controls or controls[f] != getattr(engine.config, f):
            raise AdoptDecline(
                "controls", f"generation control {f!r} differs "
                f"({controls.get(f)!r} != "
                f"{getattr(engine.config, f)!r})")


def adopt_lane(engine, payload: dict) -> Request:
    """Resume an exported lane on this engine. Returns the registered
    RUNNING `Request` (its `wait()` unblocks when decode finishes
    here). Raises `AdoptDecline` — and leaves the engine untouched —
    on every refusal path; the decline reason travels back in the
    adopt-ack so the source can count its fallback precisely.
    """
    _validate_header(engine, payload)
    prompt = np.asarray(payload["prompt"], np.int32).reshape(-1)
    tokens = [int(t) for t in payload["tokens"]]
    bucket = int(payload["bucket"])
    phys = int(payload["phys"])
    pos = int(payload["pos"])
    max_new = int(payload["max_new_tokens"])
    remaining = max_new - len(tokens)
    if (len(tokens) < 1 or remaining < 1 or len(prompt) < 1 or
            bucket < len(prompt) or
            phys != bucket + len(tokens) - 1 or
            pos != len(prompt) + len(tokens) - 1):
        raise AdoptDecline("payload_invalid",
                           "inconsistent lane cursors in payload")
    with engine._cv:
        if engine.spec:
            raise AdoptDecline("spec_engine",
                              "speculative engines do not adopt lanes")
        if engine._draining:
            raise AdoptDecline("draining", "engine is draining")
        if bucket + max_new > engine.seq_capacity:
            raise AdoptDecline(
                "capacity", f"lane needs {bucket + max_new} positions; "
                f"this engine's KV capacity is {engine.seq_capacity}")
        for live in list(engine._queue) + [
                r for r in engine._slot_req if r is not None]:
            if live.request_id == payload["request_id"]:
                raise AdoptDecline("duplicate_request_id",
                                   f"{payload['request_id']!r} is "
                                   f"already {live.state} here")
        slot = None
        for i in range(engine.config.num_slots):
            if not engine._active[i]:
                slot = i
                break
        if slot is None:
            raise AdoptDecline("no_free_slot", "all lanes busy")
        blocks = None
        positions = None
        table_row = None
        if engine.paged:
            need = blocks_for_tokens(bucket + max_new,
                                     engine.block_size)
            blocks = engine._allocator.alloc(need)
            if blocks is None:
                raise AdoptDecline("kv_blocks_exhausted",
                                   f"need {need} free KV blocks")
        try:
            if engine.paged:
                table_row = np.zeros((engine.max_blocks_per_slot,),
                                     np.int32)
                table_row[:len(blocks)] = blocks
                positions = np.concatenate(
                    [np.arange(engine.block_size) + b * engine.block_size
                     for b in blocks]).astype(np.int32)[:phys]
            new_cache = _scatter_payload(engine, payload, slot, phys,
                                         positions, table_row)
        except BaseException:  # noqa: BLE001 — release + re-raise
            # any failure before the commit — a decline or an
            # unexpected error — must return the blocks to the pool
            if blocks is not None:
                engine._allocator.free(blocks)
            raise
        # lane accepted: commit pool + rows + scheduler state together
        engine._cache = new_cache
        L = engine.seq_capacity
        row, mask_row = engine.ladder.pad_prompt(
            prompt, bucket, engine.config.pad_token_id)
        hist_row = np.zeros((L,), np.int32)
        hist_row[:bucket] = row
        hist_row[bucket:phys] = np.asarray(tokens[:-1], np.int32)
        full_mask = np.ones((L,), np.int32)
        full_mask[:bucket] = mask_row
        engine._history = engine._history.at[slot].set(
            jnp.asarray(hist_row))
        engine._mask = engine._mask.at[slot].set(jnp.asarray(full_mask))
        if engine.paged:
            engine._slot_blocks[slot] = blocks
        now = engine._clock()
        deadline = payload.get("deadline_remaining_s")
        req = Request(prompt, max_new, str(payload["request_id"]),
                      None if deadline is None else now + float(deadline),
                      now, epoch=engine._wall())
        req.tokens = tokens
        req.ttft_s = payload.get("ttft_s")
        trace = payload.get("trace") or {}
        req.timeline.trace_id = trace.get("trace_id")
        req.timeline.parent_span_id = trace.get("parent_span_id")
        req.timeline.add(now, "adopted", slot=slot, bucket=bucket,
                         generated=len(tokens),
                         source_layout=payload["source"]["kv_layout"],
                         source_dtype=payload["source"]["kv_dtype"])
        req.state = RUNNING
        req.slot = slot
        engine._slot_req[slot] = req
        engine._active[slot] = True
        engine._last_tok = engine._last_tok.at[slot].set(
            int(payload["last_tok"]))
        engine._pos[slot] = pos
        engine._phys[slot] = phys
        engine._ticks_left[slot] = remaining
        # the adopter journals the lane too: after a hard kill of the
        # source, this replica's `GET /partial/<id>` carries the
        # committed prefix the router resumes from
        engine._journal_add_locked(req)
        engine.metrics.count("admitted")
        engine._log({"event": "serving_adopt",
                     "request_id": req.request_id, "slot": slot,
                     "phys": phys, "generated": len(tokens),
                     "source": payload["source"]})
        engine._cv.notify_all()
    return req


def _scatter_payload(engine, payload: dict, slot: int, phys: int,
                     positions: Optional[np.ndarray],
                     table_row: Optional[np.ndarray]):
    """Rebuild the engine's KV pool with the wire lane written into
    `slot`. int8 receivers take the wire bits verbatim (an int8→int8
    handoff never round-trips through float); fp32 receivers store the
    dequantized prefix. Raises AdoptDecline("shape") before touching
    anything when any layer disagrees with the local pool geometry."""
    refusal = _undeclared_on_wire(engine)
    if refusal:
        raise AdoptDecline("shape", refusal)
    int8_dst = engine.config.kv_dtype == "int8"
    layers = payload["layers"]
    n_layers = [0]

    def check(d):
        i = n_layers[0]
        n_layers[0] += 1
        if i >= len(layers):
            raise AdoptDecline("shape", "payload has too few layers")
        for name, leaf_key in (("k", "cached_key"),
                               ("v", "cached_value")):
            leaf = d[leaf_key]
            axis = leaf.ndim - 4
            want = (leaf.shape[:axis] + (phys,) + leaf.shape[axis + 2:])
            got = tuple(layers[i][name]["shape"])
            if got != want:
                raise AdoptDecline(
                    "shape", f"layer {i} {name} lane shape {got} does "
                    f"not fit local pool geometry {want}")
        return d

    _map_attn_dicts(engine._cache, check)
    if n_layers[0] != len(layers):
        raise AdoptDecline("shape", "payload has too many layers")
    it = iter(layers)

    def put(d):
        entry = next(it)
        out = dict(d)
        for name, leaf_key, scale_key in (
                ("k", "cached_key", "cached_key_scale"),
                ("v", "cached_value", "cached_value_scale")):
            q = _unb64(entry[name])
            s = _unb64(entry[name + "_scale"])
            leaf = d[leaf_key]
            axis = leaf.ndim - 4
            if int8_dst:
                out[leaf_key] = _scatter_lane(leaf, axis, q, slot,
                                              positions)
                sleaf = d[scale_key]
                out[scale_key] = _scatter_lane(sleaf, sleaf.ndim - 3,
                                               s, slot, positions)
            else:
                val = dequantize_kv(jnp.asarray(q), jnp.asarray(s),
                                    leaf.dtype)
                out[leaf_key] = _scatter_lane(leaf, axis, val, slot,
                                              positions)
        out["cache_index"] = d["cache_index"].at[..., slot].set(
            jnp.int32(phys))
        if table_row is not None:
            out["block_table"] = d["block_table"].at[..., slot, :].set(
                jnp.asarray(table_row))
        return out

    return _map_attn_dicts(engine._cache, put)


def detach_lane(engine, request_id: str,
                target: Optional[str] = None,
                evacuated: bool = False) -> bool:
    """Retire a lane whose payload a decode peer has ADOPTED: free the
    slot/blocks, mark the request `handed_off` (its `wait()` unblocks;
    the coordinator returns the redirect instead of local tokens) and
    park its timeline in the debug ring. Returns False — and changes
    nothing — when the request already finished locally (the race
    where decode outran the push; the source result stands and the
    adopted twin gets cancelled).

    `evacuated=True` is the live-evacuation flavor (drain-time lane
    rescue, docs/fault_tolerance.md "Preemption runbook"): the state is
    `evacuated`, the timeline gets the terminal `evacuated` event (with
    the adopter + committed-token count), and `req.evac_target` lets
    the API layer answer the blocked POST with a redirect the router
    re-collects transparently."""
    state = EVACUATED if evacuated else HANDED_OFF
    with engine._cv:
        req = None
        for r in engine._slot_req:
            if r is not None and r.request_id == request_id:
                req = r
                break
        if req is None or req.state != RUNNING:
            return False
        slot = req.slot
        engine._slot_req[slot] = None
        engine._active[slot] = False
        engine._phys[slot] = 0
        engine._pos[slot] = 0
        if engine.paged and engine._slot_blocks[slot]:
            engine._allocator.free(engine._slot_blocks[slot])
            engine._slot_blocks[slot] = []
        req.state = state
        req.finish_reason = state
        req.slot = None
        if evacuated:
            req.evac_target = target
        end_t = engine._clock()
        req.timeline.add(end_t, state,
                         **dict(({"target": target} if target else {}),
                                **({"tokens": len(req.tokens)}
                                   if evacuated else {})))
        engine._recent.append(engine._request_dict(
            req, phases=req.timeline.phases(end_t)))
        engine._log({"event": "serving_evacuate" if evacuated
                     else "serving_handoff",
                     "request_id": req.request_id,
                     "tokens": len(req.tokens), "target": target})
        # terminal stream sync AFTER evac_target is stamped: a live
        # SSE reader gets any tail tokens plus the `evacuated` event
        # pointing at the adopter (docs/streaming.md "Reconnect")
        engine._sync_stream(req)
        req._done.set()
        return True
