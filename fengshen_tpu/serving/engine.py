"""Continuous-batching inference engine: ONE jitted decode for all
in-flight requests.

`api/main.py`'s legacy path runs one pipeline call per POST — a decode
batch of 1, so concurrent users serialize behind each other and the
chip idles between dispatches. This engine multiplexes many requests
onto a fixed pool of `num_slots` KV-cache lanes:

- admission: queued prompts are LEFT-padded to a bucket
  (`buckets.BucketLadder`), prefilled batch-1 through the model's own
  cache machinery (`utils.generate._prefill_cache` — reused, not
  forked), and scattered into a free lane (`cache.assign_slot`);
- long prompts: a prompt past the largest bucket is prefilled as
  WINDOWS of that bucket's width from position 0, the last padded on
  the right, each one the model's cached call onto the batch-1 cache
  the windows before it filled (rows so far, state so far); no
  program's temporaries grow with the prompt. A cache that declares
  leaves defined on token positions (`paged_cache.positional_leaves`:
  a recurrent state, pooled keys, a learned indexer's keys) takes
  every prompt that way;
- decode: every tick runs ONE jitted step over all `num_slots` lanes —
  per-lane `cache_index` vectors (modeling_llama's vector-index path)
  let lanes sit at different write positions, so the step never
  recompiles as requests come and go;
- one tick ahead: the serve loop enqueues tick k+1 from the token
  array tick k left on the device and from cursors that advance by
  one per live lane, and only then fetches and commits tick k — the
  host's part of a tick runs under the device's (docs/serving.md
  "Threading"); `step()` runs the same two calls back to back;
- reclaim: a finished/cancelled/expired lane is immediately handed to
  the next queued request — no drain barrier, no recompilation;
- backpressure: a bounded admission queue; `submit` raises `QueueFull`
  (HTTP 429 at the API layer) / `PromptTooLong` when the ladder can't
  hold the prompt;
- KV physicals: `kv_layout="paged"` swaps the per-lane pool for the
  block/paged pool (`serving/paged_cache.py`) — admission then charges
  each request its ACTUAL footprint in blocks instead of a worst-case
  lane, and an exhausted pool defers the queue head until reclaim;
  `kv_dtype="int8"` stores K/V quantized with per-(token, head) absmax
  scales. Both keep this module's one-jitted-decode contract.
- speculative tick: `spec_mode="prompt_lookup"` swaps the one-token
  decode for a draft→verify tick — an in-graph n-gram drafter proposes
  `spec_gamma` continuations per lane from the lane's on-device
  committed history, ONE jitted forward verifies all `[B, gamma+1]`
  positions through the same slot/paged cache, and per-lane accept
  counts (`utils.generate._spec_round_tokens`' greedy rule) advance
  each lane's cursor independently — decode is memory-bandwidth-bound,
  so committing >1 token per weight stream is the per-request latency
  lever the pool alone cannot pull. Works over both layouts and both
  kv dtypes; still exactly one decode program per engine.
- block tick: a model that declares a generation block
  (`generation_block()`: diffusion over blocks of `L` positions,
  `models/sdar`) gets a decode program that forwards each lane's whole
  block, reveals several of its masked positions a forward and keeps
  the block's K/V only on the forward after the last reveal; admission
  prefills the prompt's whole blocks and hands its tail to the lane's
  first block; a commit delivers a block's tokens together
  (docs/serving.md "Block generation"). Still exactly one decode
  program per engine, still one tick ahead.

Greedy decode is TOKEN-IDENTICAL to sequential
`utils.generate.generate` on the bucket-padded prompt (the parity test
pins it): same prefill, same logits controls
(`utils.generate.apply_logits_controls`), same selection — only the
physical cache layout is pooled.

Debug introspection (docs/serving.md "Debug endpoints"): every request
carries a host-side `RequestTimeline` of lifecycle events (enqueued,
admitted, prefill, per-tick commits incl. spec accept counts,
terminal), rendered as a latency waterfall by `debug_request()` /
`GET /debug/requests/<id>` and fed into
`fstpu_request_phase_seconds{phase}` at finish; a bounded ring keeps
the last `debug_ring` finished timelines. With a `recorder`
(`observability.FlightRecorder`) attached, the engine's event stream
enters the recorder's ring and a serve-loop tick error dumps a
post-mortem bundle (stats + config + the ring of timelines) before the
pool is rebuilt. All of it is host-side bookkeeping between jit
boundaries — the one-decode-compile contract and greedy token identity
are untouched (the timeline parity test pins both).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import zlib
from collections import OrderedDict, deque
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fengshen_tpu.observability import (RequestTimeline,
                                        record_warmup_seconds, span,
                                        thread_times)
from fengshen_tpu.ops.pallas import log_dispatch
from fengshen_tpu.serving.buckets import DEFAULT_BUCKETS, BucketLadder
from fengshen_tpu.serving.cache import (abstract_init, assign_slot,
                                        init_slot_cache, reset_free_slots,
                                        rollback_slots)
from fengshen_tpu.serving.paged_cache import (INDEX_PREFIX, RING_PREFIX,
                                              BlockAllocator,
                                              assign_paged,
                                              assign_slot_quantized,
                                              blocks_for_tokens,
                                              init_pool_cache,
                                              positional_leaves,
                                              ring_leaves)
from fengshen_tpu.serving.metrics import EngineMetrics
from fengshen_tpu.streaming import StreamBook
from fengshen_tpu.utils.generate import (_controls_active,
                                         _ngram_propose_lanes,
                                         _prefill_cache, _rollback_cache,
                                         _select_token,
                                         _spec_round_tokens,
                                         _spec_round_tokens_lanes,
                                         apply_logits_controls,
                                         model_takes)


class QueueFull(Exception):
    """Admission queue at `max_queue` — API layer maps this to 429."""


class PromptTooLong(Exception):
    """Prompt outgrows the bucket ladder or the cache headroom."""


class Draining(Exception):
    """Engine is draining (begin_drain): in-flight work finishes, new
    submissions are refused — API layer maps this to 503 with reason
    "draining" so a fleet router re-places the request."""


class DuplicateRequest(Exception):
    """An explicit request_id matching a live (queued/running) request.
    The replica-side half of the fleet router's idempotent-safe retry
    contract (docs/fleet.md): a retried id must never execute twice
    concurrently on one replica — API layer maps this to 409."""


# request lifecycle states
QUEUED, RUNNING, FINISHED, CANCELLED, EXPIRED, REJECTED = (
    "queued", "running", "finished", "cancelled", "expired", "rejected")


@dataclasses.dataclass
class EngineConfig:
    """Tuning knobs; see docs/serving.md for sizing guidance."""

    num_slots: int = 8
    buckets: Sequence[int] = DEFAULT_BUCKETS
    max_new_tokens: int = 128
    max_queue: int = 64
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 0.0
    repetition_penalty: float = 1.0
    no_repeat_ngram_size: int = 0   # 0 or 1 (see __post_init__)
    min_length: int = 0
    seed: int = 0
    # KV pool physicals (docs/serving.md "Paged KV cache"): "paged"
    # carves the pool into kv_block_size-token blocks so admission is
    # bounded by ACTUAL footprint (bucket + max_new), not worst-case
    # max_len; "int8" stores K/V quantized with per-(token, head)
    # scales — ~3.7x more KV tokens in the same bytes
    kv_layout: str = "slot"                  # "slot" | "paged"
    kv_dtype: str = "fp32"                   # "fp32" | "int8"
    kv_block_size: int = 64                  # tokens per paged block
    kv_num_blocks: Optional[int] = None      # default: slot-parity + null
    kv_max_blocks_per_slot: Optional[int] = None  # default: max_len/bs
    # a model with window layers keeps their K/V as a RING (docs/
    # serving.md "Paged KV cache"; `paged_cache.ring_leaves`): a second
    # table a lane of `kv_ring_blocks_per_slot` blocks drawn from a
    # second free list of `kv_ring_num_blocks`. Ignored by a model that
    # declares no ring
    kv_ring_blocks_per_slot: Optional[int] = None  # default: window+bucket
    kv_ring_num_blocks: Optional[int] = None  # default: slot-parity + null
    # speculative decode (docs/serving.md "Speculative decoding"):
    # "prompt_lookup" makes every tick draft spec_gamma tokens per lane
    # by n-gram match against the lane's on-device committed history
    # and verify all of them in ONE jitted forward — >1 committed token
    # per weight stream on repetitive/extractive text, greedy output
    # token-identical to the non-spec engine
    # "self_draft" swaps the n-gram drafter for a REAL draft tower: the
    # target's own first spec_draft_layers decoder layers (shared
    # embedding/norm/head, make_self_draft) run one batched draft pass
    # per tick — pays off on non-repetitive traffic where prompt
    # lookup's acceptance collapses, and carries a true proposal
    # distribution so sampled requests get the paper-exact
    # rejection-sampling accept rule per lane (docs/streaming.md)
    spec_mode: str = "off"    # "off" | "prompt_lookup" | "self_draft"
    spec_gamma: int = 4                      # drafted tokens per tick
    spec_ngram: int = 2                      # suffix length to match
    spec_draft_layers: int = 2               # self-draft tower depth
    # block generation (docs/serving.md "Block generation"), read only
    # where the model declares a generation block: the reveal forwards
    # a block of L positions takes (default L, one position a forward;
    # must divide L) and which masked positions a forward reveals —
    # "low_confidence": those whose largest softmax probability is
    # highest; "sequential": the leftmost
    denoise_steps: Optional[int] = None
    remasking: str = "low_confidence"
    # debug introspection (docs/serving.md "Debug endpoints"): how many
    # finished-request timelines the engine retains for
    # `GET /debug/requests` and the flight-recorder bundle
    debug_ring: int = 64
    # commit journal (docs/fault_tolerance.md "Preemption runbook"):
    # how many requests keep their committed-token journal entry for
    # `GET /partial/<id>` — the resume-from-token-k source a fleet
    # router consults before regenerating a maybe-executed retry
    journal_ring: int = 256

    def __post_init__(self):
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.debug_ring < 1:
            raise ValueError("debug_ring must be >= 1")
        if self.journal_ring < 1:
            raise ValueError("journal_ring must be >= 1")
        if self.kv_layout not in ("slot", "paged"):
            raise ValueError(f"unknown kv_layout {self.kv_layout!r}; "
                             "expected 'slot' or 'paged'")
        if self.kv_dtype not in ("fp32", "int8"):
            raise ValueError(f"unknown kv_dtype {self.kv_dtype!r}; "
                             "expected 'fp32' or 'int8'")
        if self.kv_layout == "paged" and self.kv_block_size < 1:
            raise ValueError("kv_block_size must be >= 1")
        if self.max_queue < 1:
            # admission always passes through the queue, so 0 would
            # reject every request forever while all slots sit idle
            raise ValueError("max_queue must be >= 1")
        if self.no_repeat_ngram_size > 1:
            # the >1 processor slices history at a SCALAR cursor
            # (apply_logits_controls dynamic_slice); the pool decodes
            # every lane at a different cursor, so only the
            # ban-all-repeats size-1 form vectorizes
            raise ValueError(
                "the continuous engine supports no_repeat_ngram_size of "
                "0 or 1 only (per-slot cursors cannot drive the n>1 "
                "window processor)")
        if self.remasking not in ("low_confidence", "sequential"):
            raise ValueError(f"unknown remasking {self.remasking!r}; "
                             "expected 'low_confidence' or 'sequential'")
        if self.denoise_steps is not None and self.denoise_steps < 1:
            raise ValueError("denoise_steps must be >= 1")
        if self.spec_mode not in ("off", "prompt_lookup", "self_draft"):
            raise ValueError(
                f"unknown spec_mode {self.spec_mode!r}; expected 'off', "
                "'prompt_lookup' or 'self_draft'")
        if self.spec_mode != "off":
            if self.spec_gamma < 1:
                raise ValueError("spec_gamma must be >= 1")
            if self.spec_ngram < 1:
                raise ValueError("spec_ngram must be >= 1")
            if self.spec_mode == "self_draft" and \
                    self.spec_draft_layers < 1:
                raise ValueError("spec_draft_layers must be >= 1")
            if self.do_sample and self.spec_mode == "prompt_lookup":
                # the rejection-sampling scheme needs the DRAFTER's
                # proposal distribution q; prompt lookup has none (its
                # proposals are copied tokens), so only greedy
                # accept-while-argmax-agrees is sound here. self_draft
                # DOES carry q — its sampled tick routes through the
                # per-lane rejection rule (_spec_round_tokens_lanes)
                raise ValueError(
                    "spec_mode='prompt_lookup' is greedy-only "
                    "(do_sample=False): lookup proposals carry no "
                    "draft distribution for the rejection-sampling "
                    "accept rule (use spec_mode='self_draft' for "
                    "sampled speculation)")
            if (self.repetition_penalty != 1.0 or
                    self.no_repeat_ngram_size > 0 or self.min_length > 0):
                # the processors are defined at ONE committed cursor;
                # the verify window scores gamma+1 cursors at once
                raise ValueError(
                    "spec_mode cannot run logits controls "
                    "(repetition_penalty / no_repeat_ngram_size / "
                    "min_length act per committed cursor, but the "
                    "verify forward scores gamma+1 positions at once)")


class Request:
    """One in-flight generation; host-side bookkeeping only."""

    _ids = itertools.count()

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 request_id: Optional[str], deadline: Optional[float],
                 submit_time: float, epoch: Optional[float] = None):
        self.request_id = request_id if request_id is not None else \
            f"req-{next(Request._ids)}"
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.deadline = deadline            # engine-clock absolute time
        self.submit_time = submit_time
        self.state = QUEUED
        self.tokens: list[int] = []         # generated tokens (eos incl.)
        self.ttft_s: Optional[float] = None
        self.finish_reason: Optional[str] = None
        self.slot: Optional[int] = None
        #: resume-from-token-k (docs/fault_tolerance.md): tokens a
        #: previous execution already committed — prefilled as part of
        #: the prompt, never re-decoded — plus where they came from
        self.resume: list[int] = []
        self.resume_source: Optional[str] = None
        #: peer URL a live-evacuated lane moved to (handoff.py sets it)
        self.evac_target: Optional[str] = None
        #: per-request sampling seed (docs/streaming.md "Seed
        #: semantics"): folded into the engine's base key at admission
        #: to derive this lane's key ring entry; submit resolves it
        #: from the client field or the request-id hash
        self.seed: int = 0
        self._cancel = False
        self._done = threading.Event()
        #: host-side lifecycle events (docs/observability.md "Request
        #: tracing") — appended on the scheduler thread only, never
        #: inside traced code. `epoch` is the wall-clock anchor for
        #: `submit_time`'s monotonic axis (the engine's injectable
        #: wall clock) — what the fleet assembler's skew math reads.
        self.timeline = RequestTimeline(submit_time, epoch=epoch)

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the request leaves the engine (finished /
        cancelled / expired). True when it did within `timeout`."""
        return self._done.wait(timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()


@dataclasses.dataclass
class _Tick:
    """One enqueued decode tick whose outputs the host has not
    committed: what `_enqueue_tick_locked` hands to `_fetch_locked` and the
    commit. Engine state under `_cv` while it is `_inflight`."""

    out: tuple              # device arrays the commit needs on the host
    lanes: np.ndarray       # lane indices the tick ran
    reqs: list              # the request in each of them at enqueue
    kv_tokens: int          # real cached tokens its attention reads
    kv_blocks: tuple        # (live, tabled) blocks of its lanes' table rows
    t0: float               # perf_counter at enqueue
    ahead: bool             # enqueued with the previous tick unfetched
    commits: Any = None     # block tick: bool [slots], the lanes whose
    #                         forward this was kept their block's K/V
    host: tuple = ()        # `out` on the host, once fetched
    seconds: float = 0.0    # the tick's share of the wall, once fetched


def _moe_stats_shape(abstract, num_slots: int):
    """(expert layers, experts) of the routing a model sows under
    "moe_stats" on a decode tick (`ops/moe.py`), or None where it sows
    none; `abstract` is the model's `abstract_init` over `num_slots`
    lanes."""
    leaves = jax.tree_util.tree_leaves(abstract.get("moe_stats", {}))
    if not leaves:
        return None
    experts = leaves[0].shape[-1]
    return (sum(leaf.size for leaf in leaves) // (num_slots * experts),
            experts)


def _live_assignments(moe_stats, active):
    """`[expert layers, experts]` int32: the assignments of the live
    lanes' tokens. Leaves are `[tokens, experts]` a layer, or their
    `[layers, ...]` stack under a layer scan; a lane's tokens are
    adjacent."""
    lanes = active.shape[0]
    per_layer = jnp.concatenate([
        leaf.reshape((-1, lanes, leaf.shape[-2] // lanes, leaf.shape[-1]))
        for leaf in jax.tree_util.tree_leaves(moe_stats)])
    return (per_layer * active[None, :, None, None]).sum(axis=(1, 2))


class ContinuousBatchingEngine:
    """Slot-pool continuous batching over one decoder-only model.

    `model` must use the repo's preallocated flax cache contract
    (`cached_*` row leaves beside a `cache_index`: `cached_key` /
    `cached_value` in the LLaMA family, one `cached_latent` under
    latent attention; `paged_cache.row_leaves`), beside or instead of
    which it may declare a per-lane `state_*` leaf and row leaves of
    another rate (`paged_cache.state_leaves`); a model whose
    `__call__` takes `live` is handed the tick's live mask and keeps a
    dead lane's state itself. `clock`
    is injectable for deterministic deadline tests.
    """

    #: dispatch discriminator for the API layer and /stats — the
    #: multimodal engines (serving/multimodal.py) carry their own
    engine_type = "continuous"

    def __init__(self, model: Any, params: Any, config: EngineConfig,
                 log: Optional[Callable[[dict], None]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 recorder: Any = None,
                 wall: Callable[[], float] = time.time):
        self.model = model
        self.params = params
        self.config = config
        self.ladder = BucketLadder(config.buckets)
        self.metrics = EngineMetrics()
        self._log = log or (lambda entry: None)
        self._clock = clock
        # wall-clock anchor for request timelines: pairs with the
        # injectable monotonic `clock` so the fleet assembler's
        # cross-process skew math is deterministic under test
        self._wall = wall
        # debug introspection state (docs/serving.md "Debug endpoints"):
        # a bounded ring of finished-request timelines, engine start
        # time for /stats uptime, and the last serve-loop error (type +
        # age only — never a traceback payload)
        self._recent: deque = deque(maxlen=config.debug_ring)
        self._t0_clock = clock()
        self._last_error: Optional[dict] = None
        self._recorder = recorder
        if recorder is not None:
            # engine events enter the recorder's ring on their way to
            # the caller's sink; the provider contributes stats/config/
            # timelines to every post-mortem bundle
            self._log = recorder.wrap_sink(self._log)
            recorder.attach("engine", self._debug_bundle)
        # THE loud kernel line (docs/kernels.md): state the dispatch
        # decision for every registered kernel at startup and set the
        # fstpu_kernel_dispatch gauge — a fleet that is not running its
        # kernels must be visible to a scraper
        log_dispatch(self._log)
        self.max_len = int(model.config.max_position_embeddings)
        self.paged = config.kv_layout == "paged"
        self.spec = config.spec_mode != "off"
        self.self_draft = config.spec_mode == "self_draft"
        # every admission must reserve gamma EXTRA positions: the
        # verify forward scatters the full gamma+1 window before the
        # accept counts are known, so rejected tails land past the
        # cursor (masked, later overwritten) but must stay inside the
        # lane (the engine analog of _check_spec_cache_headroom)
        self._gamma = config.spec_gamma if self.spec else 0
        #: (L, mask token) where the model is generated by diffusion
        #: over blocks of L positions: the decode program is the block
        #: tick, `_block_len` its L (0: one token a lane a tick)
        declared = getattr(model, "generation_block", None)
        self._block_len, self._mask_id = \
            (declared() if declared is not None else None) or (0, 0)
        if self._block_len:
            self._check_block_config()
        S = config.num_slots
        if self.paged:
            bs = int(config.kv_block_size)
            if bs > self.max_len:
                raise ValueError(
                    f"kv_block_size {bs} exceeds "
                    f"max_position_embeddings={self.max_len}")
            mb = int(self.max_len // bs
                     if config.kv_max_blocks_per_slot is None
                     else config.kv_max_blocks_per_slot)
            if mb < 1 or mb * bs > self.max_len:
                raise ValueError(
                    f"kv_max_blocks_per_slot={mb} x kv_block_size={bs} "
                    f"must fit in 1..max_position_embeddings="
                    f"{self.max_len}")
            # explicit `is None` (not `or`): a computed kv_num_blocks of
            # 0 must fail loudly below, never silently balloon to the
            # slot-parity default pool
            nb = int(S * mb + 1 if config.kv_num_blocks is None
                     else config.kv_num_blocks)
            self.block_size, self.max_blocks_per_slot = bs, mb
            self.num_blocks = nb
            # the lane's logical extent: positions beyond it have no
            # block to land in, so it bounds prompt+decode like max_len
            # bounds the slot layout
            self.seq_capacity = mb * bs
            self._allocator = BlockAllocator(nb)
            self._slot_blocks: list[list[int]] = [[] for _ in range(S)]
            #: the ring blocks a lane holds beside them (a model whose
            #: cache declares a ring; else always empty)
            self._slot_ring: list[list[int]] = [[] for _ in range(S)]
            self._deferred_req: Optional[str] = None
        else:
            self.seq_capacity = self.max_len
        if self.ladder.buckets[0] + 1 + self._gamma > self.seq_capacity:
            raise ValueError(
                f"smallest bucket {self.ladder.buckets[0]} leaves no "
                f"decode headroom in the KV lane capacity "
                f"{self.seq_capacity}" +
                (f" (speculative window needs gamma={self._gamma} "
                 "extra positions)" if self._gamma else ""))

        #: the model's init_cache pass as shapes: the pool is built
        #: from its "cache", the routing's shape read off its "moe_stats"
        self._abstract_init = abstract_init(model, S)
        #: leaves defined on token positions from 0 (a state, pooled
        #: keys): a cache that declares any is filled by windows only
        self._positional = positional_leaves(self._abstract_init["cache"])
        #: every prompt is prefilled by windows from position 0: the
        #: cache declares a positional leaf, or the model a block grid,
        #: which is a grid of positions whatever the block's length (the
        #: model reads positions as they lie and takes no mask, so a
        #: lane is filled from 0 and padded on the right)
        self._from_zero = bool(self._positional) or declared is not None
        if self._positional and self.spec:
            raise ValueError(
                f"spec_mode={config.spec_mode!r} cannot serve a cache "
                f"that declares {self._positional}: a rejected draft "
                "cannot be rolled back out of a recurrent state or a "
                "pooled row, nor rewound in a ring; use spec_mode='off'")
        if declared is not None and self.spec:
            raise ValueError(
                f"spec_mode={config.spec_mode!r} cannot serve a model "
                "that declares a block grid: its lanes are filled from "
                "position 0 and a drafter reads a history laid out by "
                "left-padded buckets; use spec_mode='off'")
        #: the leaves a lane holds only the last tokens of
        #: (`paged_cache.ring_leaves`): under the paged layout they page
        #: behind a second table of `ring_blocks` blocks a lane, from a
        #: second free list
        self._ring = ring_leaves(self._abstract_init["cache"])
        #: host arithmetic a model with window layers offers: keys such
        #: a layer's query with so many cached tokens reads
        self._window_tokens = getattr(model, "window_tokens", None)
        self.ring_blocks = 0
        if self._ring and config.kv_dtype == "int8":
            raise ValueError(
                f"kv_dtype='int8' cannot serve a cache that declares "
                f"{self._ring}: a ring is read through a table of its "
                "live blocks as the rows lie and has no scales; use "
                "kv_dtype='fp32'")
        if self._ring and self.paged:
            window = int(self._window_tokens(self.max_len))
            rb = int(min(mb, blocks_for_tokens(
                window + self.ladder.max_bucket, bs))
                if config.kv_ring_blocks_per_slot is None
                else config.kv_ring_blocks_per_slot)
            if rb * bs < window or rb > mb:
                raise ValueError(
                    f"kv_ring_blocks_per_slot={rb} x kv_block_size={bs} "
                    f"must hold the {window} tokens a window layer reads "
                    f"and no more than the lane's {mb} blocks")
            rnb = int(S * rb + 1 if config.kv_ring_num_blocks is None
                      else config.kv_ring_num_blocks)
            if rnb - 1 < rb:
                raise ValueError(
                    f"kv_ring_num_blocks={rnb} cannot hold one lane's "
                    f"ring of {rb} blocks beside the null block")
            self.ring_blocks, self.ring_num_blocks = rb, rnb
            self._ring_allocator = BlockAllocator(rnb)

        if self.self_draft:
            # the self-draft tower (docs/streaming.md "Draft tower"):
            # the target's own first spec_draft_layers decoder layers
            # plus its shared embedding/norm/head — make_self_draft's
            # param leaves ALIAS the target's arrays, no copy. Its KV
            # pool is always a plain fp32 slot pool sized to this
            # engine's lane capacity (the tower is small, so paging or
            # quantizing it would save little and cost congruence with
            # the target cache's cursors).
            from fengshen_tpu.models.llama import make_self_draft
            draft_cfg, self._draft_params = make_self_draft(
                model.config, params, config.spec_draft_layers)
            if self.seq_capacity != self.max_len:
                draft_cfg = dataclasses.replace(
                    draft_cfg,
                    max_position_embeddings=self.seq_capacity)
            self._draft_model = model.clone(config=draft_cfg)
            self._draft_cache = init_slot_cache(self._draft_model, S)

        L = self.seq_capacity
        self._cache = self._init_pool()
        #: (expert layers, experts) where the model sows its routing
        #: ("moe_stats"); None for a model without routed experts
        self._moe_shape = _moe_stats_shape(self._abstract_init, S)
        #: (first, count) of the experts this chip holds where the
        #: model's config states a share of them (`ops/moe.py`)
        self._experts_held = getattr(getattr(model, "config", None),
                                     "experts_held", None)
        def pool_bytes(*prefixes, but=()):
            return sum(
                leaf.nbytes for path, leaf in
                jax.tree_util.tree_flatten_with_path(self._cache)[0]
                if any(getattr(k, "key", "").startswith(prefixes) and
                       not getattr(k, "key", "").startswith(but)
                       for k in path))
        self._kv_bytes = pool_bytes("cached_", but=INDEX_PREFIX)
        #: bytes the pool holds beside the rows attention reads: per-lane
        #: state (a recurrent layer's) and the rows a selection scores
        self._state_bytes = pool_bytes("state_", INDEX_PREFIX)
        #: of `_kv_bytes`, the rings' part
        self._ring_bytes = pool_bytes(RING_PREFIX)
        #: host arithmetic a sparse-attention model offers: tokens a
        #: query with so many cached tokens reads (None: all of them),
        #: `attended_tokens` where it chooses pooled blocks,
        #: `indexed_tokens` where a learned indexer chooses single tokens
        self._attended_tokens = getattr(model, "attended_tokens", None)
        self._indexed_tokens = getattr(model, "indexed_tokens", None)
        self._history = jnp.zeros((S, L), jnp.int32)
        self._mask = jnp.zeros((S, L), jnp.int32)
        # each lane's next input token stays on the device: the decode
        # program's token output (a routed-expert model's histogram
        # behind the first S entries) IS the next call's `tokens`, and
        # the assign program writes an admitted lane's first token
        # into it — the host's fetched copy is for the commit only
        self._last_tok = self._zero_tokens()
        # block tick: each lane's current block on the device, donated
        # from tick to tick as the history is — its tokens and which of
        # them are still masked — and, on the host, the forwards the
        # block still takes, its commit forward included
        self._block_tokens = jnp.full((S, self._block_len), self._mask_id,
                                      jnp.int32)
        self._block_masked = jnp.ones((S, self._block_len), bool)
        self._fwd_left = np.zeros((S,), np.int32)
        # host-side per-slot state (authoritative for scheduling): the
        # cursors of the next tick to ENQUEUE. A plain tick advances
        # them by one per lane as it is enqueued, a speculative tick by
        # its fetched accept counts. Arrays handed to the decode
        # program are never written again (the upload may still read
        # them): every update rebinds or precedes the next enqueue.
        self._pos = np.zeros((S,), np.int32)    # logical position of last_tok
        self._phys = np.zeros((S,), np.int32)   # physical cache cursor
        self._active = np.zeros((S,), bool)     # lane holds a request
        #: ticks a lane may still be enqueued in: max_new_tokens less
        #: the tokens committed or in flight. At 0 the lane sits out
        #: until the commit of its last tick releases it.
        self._ticks_left = np.zeros((S,), np.int32)
        self._slot_req: list[Optional[Request]] = [None] * S
        #: the one decode tick enqueued and not yet fetched, or None
        self._inflight: Optional[_Tick] = None
        self._fetched_at = 0.0      # perf_counter of the last fetch
        #: the scheduler thread's account since the serve loop's last
        #: flush to the `fstpu_serving_scheduler_*` counters: off-CPU
        #: seconds inside the spans that wait by design, and the wall
        #: seconds of `serving/lock_wait` among them
        self._waited = 0.0
        self._lock_waited = 0.0
        #: the serve thread's open `serving/tail` span, or None
        self._tail: Optional[span] = None

        self._queue: deque[Request] = deque()
        # commit journal: request_id -> the live Request object, a
        # bounded insertion-ordered ring beside the debug ring. Entries
        # are references, so the committed-token list grows in place at
        # zero per-tick cost; `partial()` snapshots it for
        # `GET /partial/<id>` (docs/fault_tolerance.md)
        self._journal: "OrderedDict[str, Request]" = OrderedDict()
        #: live SSE token streams (docs/streaming.md): per-request
        #: bounded token queues the scheduler thread feeds at commit
        #: time; an engine that never streams pays one dict lookup of
        #: overhead per sync call and nothing else
        self.streams = StreamBook()
        self._draining = False
        self._cv = threading.Condition()
        self._base_key = jax.random.PRNGKey(config.seed)
        self._zero_key = jax.random.PRNGKey(0)
        # per-lane PRNG key ring beside cache_index (docs/streaming.md
        # "Seed semantics"): one key per lane, installed at admission
        # from fold_in(base_key, request.seed) and split IN-GRAPH every
        # tick — a lane's draws are a pure function of its seed and its
        # tick count since admission, never of pool co-tenancy
        self._keys = jnp.zeros((S,) + self._zero_key.shape,
                               self._zero_key.dtype)
        self._thread: Optional[threading.Thread] = None
        self._stop_flag = False

        cfg = config
        control_kw = dict(repetition_penalty=cfg.repetition_penalty,
                          no_repeat_ngram_size=cfg.no_repeat_ngram_size,
                          min_length=cfg.min_length,
                          eos_token_id=cfg.eos_token_id)
        controls_on = _controls_active(cfg.repetition_penalty,
                                       cfg.no_repeat_ngram_size,
                                       cfg.min_length)

        def prefill_fn(params, ids, mask, rng):
            # identical math to generate()'s prompt phase: mask-cumsum
            # positions, _prefill_cache, controls on the last position
            position_ids = jnp.clip(mask.cumsum(-1) - 1, 0, None)
            # prompts are left-padded: the last row is the last token,
            # and the only one whose logits are kept
            logits, cache = _prefill_cache(
                model, params, ids, mask, position_ids,
                logits_row=jnp.int32(ids.shape[1] - 1))
            step_logits = logits[:, -1]
            if controls_on:
                step_logits = apply_logits_controls(
                    step_logits, ids, jnp.int32(ids.shape[1]),
                    history_mask=mask, **control_kw)
            tok = _select_token(step_logits, rng, cfg.do_sample,
                                cfg.temperature, cfg.top_k, cfg.top_p)
            return cache, tok.astype(jnp.int32)

        max_len = self.max_len

        # a model that takes `logits_row` projects the one row asked
        # for; one that does not projects every row of the window
        row_only = self._row_only = model_takes(model, "logits_row")

        def window_fn(params, cache, ids, prompt_row, start, n_valid, rng):
            """One window of a prompt onto the batch-1 cache the
            windows before it filled: tokens `start ..` of the prompt,
            the first `n_valid` of `ids` real, the rest padding on the
            right. The mask is over cache positions; the cursor ends
            at the last real token. Returns the cache and the token
            after the last real one (meant on the last window)."""
            width = ids.shape[1]
            end = start + n_valid
            mask = (jnp.arange(max_len) < end)[None].astype(jnp.int32)
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, ids,
                attention_mask=mask,
                position_ids=start + jnp.arange(width)[None],
                init_cache=True, mutable=["cache"],
                **({"logits_row": n_valid - 1} if row_only else {}))
            cache = _rollback_cache(mutated["cache"], width - n_valid)
            step_logits = logits[:, 0] if row_only else \
                jax.lax.dynamic_index_in_dim(
                    logits, n_valid - 1, axis=1, keepdims=False)
            if controls_on:
                seen = (jnp.arange(prompt_row.shape[1]) < end)[None]
                step_logits = apply_logits_controls(
                    step_logits, prompt_row, end,
                    history_mask=seen.astype(jnp.int32), **control_kw)
            tok = _select_token(step_logits, rng, cfg.do_sample,
                                cfg.temperature, cfg.top_k, cfg.top_p)
            return cache, tok.astype(jnp.int32)

        def fresh_fn():
            # the batch-1 cache a prompt's first window writes onto
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                abstract_init(model, 1)["cache"])

        block_len, mask_id = self._block_len, self._mask_id
        if block_len:
            def window_fn(params, cache, ids, start, n_valid):  # noqa: F811
                """One window of a prompt's WHOLE blocks onto the
                batch-1 cache (`n_valid` a multiple of the block): it
                writes their K/V and reads no logits, so the model runs
                no head. The prompt's tail is the lane's first block."""
                width = ids.shape[1]
                _, mutated = model.apply(
                    {"params": params, "cache": cache}, ids,
                    position_ids=start + jnp.arange(width)[None],
                    init_cache=True, mutable=["cache"], head=False)
                return _rollback_cache(mutated["cache"], width - n_valid)

        if self.self_draft:
            # the draft tower primes its OWN cache over the same
            # prompt in the same program — its cursor starts congruent
            # with the target's and stays congruent tick over tick
            # (both advance gamma+1 and roll back gamma-n_r together)
            draft_model = self._draft_model
            base_prefill = prefill_fn

            def prefill_fn(params, draft_params, ids, mask, rng):
                cache, tok = base_prefill(params, ids, mask, rng)
                position_ids = jnp.clip(mask.cumsum(-1) - 1, 0, None)
                _, d_cache = _prefill_cache(draft_model, draft_params,
                                            ids, mask, position_ids)
                return cache, d_cache, tok

        paged = self.paged
        # the assign program also writes the lane's first token into
        # the device token array (not donated: an in-flight tick's
        # record still fetches the array it derives from)
        if paged:
            def assign_fn(cache, history, mask, tokens, primed,
                          prompt_row, mask_row, table_row, *rest):
                # a cache with a ring brings its table row too
                *ring_row, slot, tok = rest
                cache = assign_paged(cache, primed, slot, table_row,
                                     *ring_row)
                history = history.at[slot].set(prompt_row)
                mask = mask.at[slot].set(mask_row)
                return cache, history, mask, tokens.at[slot].set(tok)
        elif config.kv_dtype == "int8":
            def assign_fn(cache, history, mask, tokens, primed,
                          prompt_row, mask_row, slot, tok):
                cache = assign_slot_quantized(cache, primed, slot)
                history = history.at[slot].set(prompt_row)
                mask = mask.at[slot].set(mask_row)
                return cache, history, mask, tokens.at[slot].set(tok)
        else:
            def assign_fn(cache, history, mask, tokens, primed,
                          prompt_row, mask_row, slot, tok):
                cache = assign_slot(cache, primed, slot)
                history = history.at[slot].set(prompt_row)
                mask = mask.at[slot].set(mask_row)
                return cache, history, mask, tokens.at[slot].set(tok)

        if block_len:
            def assign_fn(cache, block_tokens, block_masked,  # noqa: F811
                          primed, first, flags, *rest):
                # rest: [table_row,] slot. The lane's first block is the
                # prompt's tail and masks after it
                *table_row, slot = rest
                cache = assign_paged(cache, primed, slot, *table_row) \
                    if paged else assign_slot(cache, primed, slot)
                return (cache, block_tokens.at[slot].set(first),
                        block_masked.at[slot].set(flags))

        if self.self_draft:
            # the draft pool is a plain slot pool regardless of the
            # target layout, so its lane assignment is always the
            # unquantized scatter
            base_assign = assign_fn

            def assign_fn(cache, dpool, history, mask, tokens, primed,
                          d_primed, *rows):
                # rows: prompt_row, mask_row, [table_row,] slot, tok
                cache, *rings = base_assign(cache, history, mask, tokens,
                                            primed, *rows)
                dpool = assign_slot(dpool, d_primed, rows[-2])
                return (cache, dpool, *rings)

        gamma, ngram = cfg.spec_gamma, cfg.spec_ngram
        moe_shape = self._moe_shape
        # a model with per-lane state keeps a dead lane's itself: it
        # has no null block to park the write on
        takes_live = model_takes(model, "live")
        if self.self_draft:
            draft_model = self._draft_model

            def decode_fn(params, draft_params, cache, dpool, history,
                          mask, tokens, pos, phys, active, keys):
                """Self-draft speculative tick: gamma+1 BATCHED draft
                forwards (a lax.scan over the small tower, all lanes at
                once) → ONE target verify over [B, gamma+1] → the
                paper-exact per-lane accept rule, sampled or greedy,
                keyed from the per-lane ring. Both caches advance and
                roll back together, so their cursors stay congruent."""
                n = tokens.shape[0]
                if paged:
                    cache = reset_free_slots(cache, active)
                dpool = reset_free_slots(dpool, active)
                if cfg.do_sample:
                    # gamma+3 splits per lane: next ring entry, gamma+1
                    # draft draws (the +1 is scanned but unused — keeps
                    # the scan xs rectangular), one verify key
                    split = jax.vmap(
                        lambda k: jax.random.split(k, gamma + 3))(keys)
                    keys_out = split[:, 0]
                    d_keys = jnp.moveaxis(split[:, 1:gamma + 2], 1, 0)
                    round_keys = split[:, gamma + 2]
                else:
                    keys_out = keys
                    d_keys = jnp.zeros((gamma + 1,) + keys.shape,
                                       keys.dtype)
                    round_keys = keys
                history = history.at[jnp.arange(n), phys].set(tokens)

                def draft_step(carry, xs):
                    dcache, cur = carry
                    i, dkey = xs
                    dlogits, dmut = draft_model.apply(
                        {"params": draft_params, "cache": dcache},
                        cur[:, None], attention_mask=mask,
                        position_ids=(pos + i)[:, None],
                        init_cache=True, mutable=["cache"])
                    step = dlogits[:, -1]
                    if cfg.do_sample:
                        # each proposal is an exact draw from the q
                        # that the accept rule divides by: same
                        # _filtered_logits, same temp/top-k/top-p
                        nxt = jax.vmap(
                            lambda l, k: _select_token(
                                l, k, True, cfg.temperature,
                                cfg.top_k, cfg.top_p))(step, dkey)
                    else:
                        nxt = step.astype(jnp.float32).argmax(-1)
                    nxt = nxt.astype(jnp.int32)
                    return (dmut["cache"], nxt), (nxt, step)

                # gamma+1 steps: the first feeds last tick's committed
                # token (writing its draft-KV at phys, mirroring the
                # target verify), the rest extend the proposal chain;
                # the last proposal is never verified, but its forward
                # writes the KV the NEXT tick's first step would need
                # anyway
                (dpool, _), (props, d_steps) = jax.lax.scan(
                    draft_step, (dpool, tokens),
                    (jnp.arange(gamma + 1), d_keys))
                drafts = jnp.transpose(props[:gamma])
                d_logits = jnp.moveaxis(d_steps[:gamma], 0, 1)
                verify = jnp.concatenate([tokens[:, None], drafts],
                                         axis=1)
                v_pos = pos[:, None] + jnp.arange(gamma + 1)[None]
                logits, mutated = model.apply(
                    {"params": params, "cache": cache}, verify,
                    attention_mask=mask, position_ids=v_pos,
                    init_cache=True, mutable=["cache"])
                n_r, w = _spec_round_tokens_lanes(
                    logits, d_logits, drafts, round_keys,
                    do_sample=cfg.do_sample,
                    temperature=cfg.temperature, top_k=cfg.top_k,
                    top_p=cfg.top_p)
                n_r = jnp.where(active, n_r, 0)
                delta = jnp.where(active, gamma - n_r, 0)
                # both cursors advanced gamma+1; both roll back the
                # rejected tail together (the draft pool too — its
                # stale entries past the cursor are masked, the
                # _rollback_cache invariant)
                cache = rollback_slots(mutated["cache"], delta)
                dpool = rollback_slots(dpool, delta)
                if not paged:
                    cache = reset_free_slots(cache, active)
                c = n_r + 1     # committed this tick (1..gamma+1)
                win = jnp.where(
                    jnp.arange(gamma + 1)[None] < c[:, None], w,
                    cfg.pad_token_id)
                win = jnp.where(active[:, None], win, cfg.pad_token_id)
                history = jax.vmap(
                    lambda row, wrow, p: jax.lax.dynamic_update_slice(
                        row, wrow, (p,)))(history, win, phys + 1)
                return (cache, dpool, history, keys_out,
                        win[jnp.arange(n), n_r], n_r, win)
        elif self.spec:
            def decode_fn(params, cache, history, mask, tokens, pos,
                          phys, active, keys):
                """Speculative tick: per-lane prompt-lookup draft → ONE
                verify forward over [B, gamma+1] → per-lane greedy
                accept/commit. Entirely in-graph: the committed-history
                ring already lives on device, so the drafter costs no
                host round-trip (the fslint fixture
                spec_decode_clean.py pins this path clean)."""
                n = tokens.shape[0]
                if paged:
                    cache = reset_free_slots(cache, active)
                # the token selected last tick enters the history at
                # its physical cursor BEFORE the forward, exactly like
                # the plain tick — the drafter then matches the
                # ngram-suffix ending at phys+1
                history = history.at[jnp.arange(n), phys].set(tokens)
                drafts = _ngram_propose_lanes(history, phys + 1, ngram,
                                              gamma, tokens)
                verify = jnp.concatenate([tokens[:, None], drafts],
                                         axis=1)
                v_pos = pos[:, None] + jnp.arange(gamma + 1)[None]
                logits, mutated = model.apply(
                    {"params": params, "cache": cache}, verify,
                    attention_mask=mask, position_ids=v_pos,
                    init_cache=True, mutable=["cache"])
                # greedy accept = longest draft==argmax prefix, w = the
                # per-position corrections: EXACTLY _spec_round_tokens'
                # rule, shared with speculative_generate (prompt-lookup
                # proposals come from no distribution, so the sampled
                # accept rule does not apply — greedy only, enforced by
                # __post_init__)
                n_r, w = _spec_round_tokens(logits, None, drafts, None,
                                            do_sample=False)
                n_r = jnp.where(active, n_r, 0)
                # the verify advanced every lane's cursor by gamma+1;
                # each lane rolls back its REJECTED tail independently
                # (no KV rewind needed: entries past the index are
                # masked and overwritten — the _rollback_cache
                # invariant, per-lane via rollback_slots)
                cache = rollback_slots(
                    mutated["cache"],
                    jnp.where(active, gamma - n_r, 0))
                if not paged:
                    cache = reset_free_slots(cache, active)
                c = n_r + 1     # committed this tick (1..gamma+1)
                win = jnp.where(
                    jnp.arange(gamma + 1)[None] < c[:, None], w,
                    cfg.pad_token_id)
                win = jnp.where(active[:, None], win, cfg.pad_token_id)
                # committed window tokens join the history ring at
                # phys+1.. so the next tick's drafter can match them;
                # the slot past the new cursor holds pad, like
                # _speculative_loop's buffer
                history = jax.vmap(
                    lambda row, wrow, p: jax.lax.dynamic_update_slice(
                        row, wrow, (p,)))(history, win, phys + 1)
                # the last committed token (pad on a free lane) is the
                # next tick's input: it stays on the device
                return (cache, history, keys, win[jnp.arange(n), n_r],
                        n_r, win)
        elif block_len:
            per_step = block_len // self._steps
            by_confidence = cfg.remasking == "low_confidence"

            def decode_fn(params, cache, block_tokens, block_masked, phys,
                          active):
                """Block tick: every lane's whole block of L positions
                in ONE forward at `phys .. phys + L - 1`, the model
                writing the block's rows at the lane's cursor and every
                query reading the lane to the block's end. A lane with
                masked positions left has `per_step` of them revealed
                (the argmax of their own logits) and its cursor left
                where it was: the next forward overwrites the rows. A
                lane with none left made its COMMIT forward: the rows
                stay, the cursor moves a block on, the block's tokens
                are the tick's output and its next block starts all
                masked. A position is masked by the lane's flag, never
                by its id. The host knows each lane's phase without a
                fetch (`_fwd_left`)."""
                if paged:
                    cache = reset_free_slots(cache, active)
                logits, mutated = model.apply(
                    {"params": params, "cache": cache}, block_tokens,
                    position_ids=phys[:, None] + jnp.arange(block_len)[None],
                    masked=block_masked, init_cache=True,
                    mutable=["cache"] + (["moe_stats"] if moe_shape else []))
                with jax.named_scope("fstpu_block_reveal"):
                    z = logits.astype(jnp.float32)
                    # greedy: the selection every tick shares
                    best = _select_token(z, None, False, cfg.temperature,
                                         cfg.top_k, cfg.top_p
                                         ).astype(jnp.int32)
                    at = jnp.arange(block_len)
                    # what a masked position is picked by: its largest
                    # softmax probability, or how far left it lies
                    score = jnp.exp(z.max(-1) - jax.nn.logsumexp(z, -1)) \
                        if by_confidence else jnp.broadcast_to(
                            -at.astype(jnp.float32), block_masked.shape)
                    score = jnp.where(block_masked, score, -jnp.inf)
                    # positions that go before it: a higher score, or
                    # the same further left
                    ahead = (score[:, None, :] > score[:, :, None]) | (
                        (score[:, None, :] == score[:, :, None]) &
                        (at[None, None, :] < at[None, :, None]))
                    pick = block_masked & (ahead.sum(-1) < per_step)
                    commit = active & ~block_masked.any(-1)
                    out = jnp.where(commit[:, None], block_tokens,
                                    cfg.pad_token_id)
                    # a lane that committed, or holds no request, starts
                    # its next block all masked
                    fresh = (commit | ~active)[:, None]
                    block_tokens = jnp.where(
                        fresh, mask_id, jnp.where(pick, best, block_tokens))
                    block_masked = fresh | (block_masked & ~pick)
                # the forward moved every cursor a block on: only a
                # commit keeps that (rows past a cursor are never read
                # by another block: `rollback_slots`' invariant)
                cache = rollback_slots(mutated["cache"],
                                       jnp.where(commit, 0, block_len))
                if not paged:
                    cache = reset_free_slots(cache, active)
                out = out.reshape(-1).astype(jnp.int32)
                if moe_shape:
                    out = jnp.concatenate([out, _live_assignments(
                        mutated["moe_stats"], active).reshape(-1)])
                return cache, block_tokens, block_masked, out
        else:
            def decode_fn(params, cache, history, mask, tokens, pos,
                          phys, active, keys):
                # `tokens` is the previous tick's whole output, still
                # on the device: its first n entries are the tokens
                n = active.shape[0]
                tokens = tokens[:n]
                if paged:
                    # clamp BEFORE the forward: a reclaimed lane's
                    # blocks may already belong to another request, so
                    # its stray write must be parked on the null block
                    # first (the slot layout clamps after — each lane
                    # owns its space)
                    cache = reset_free_slots(cache, active)
                if cfg.do_sample:
                    # split IN-GRAPH: the ring entry advances once per
                    # tick whether or not this lane commits, so a
                    # lane's draw sequence depends only on (seed, tick
                    # count) — never on which other lanes are resident
                    split = jax.vmap(jax.random.split)(keys)
                    keys_out, tick_keys = split[:, 0], split[:, 1]
                else:
                    keys_out, tick_keys = keys, keys
                # the token selected last tick enters the history at
                # its physical cursor BEFORE the forward (its K/V are
                # written at the same position by the cache update)
                history = history.at[jnp.arange(n), phys].set(tokens)
                logits, mutated = model.apply(
                    {"params": params, "cache": cache}, tokens[:, None],
                    attention_mask=mask, position_ids=pos[:, None],
                    init_cache=True, mutable=["cache"] + (
                        ["moe_stats"] if moe_shape else []), **(
                        {"live": active} if takes_live else {}))
                cache = mutated["cache"] if paged else \
                    reset_free_slots(mutated["cache"], active)
                step_logits = logits[:, -1]
                if controls_on:
                    step_logits = apply_logits_controls(
                        step_logits, history, (phys + 1)[:, None],
                        history_mask=mask, **control_kw)
                if cfg.do_sample:
                    nxt = jax.vmap(
                        lambda l, k: _select_token(
                            l, k, True, cfg.temperature, cfg.top_k,
                            cfg.top_p))(step_logits, tick_keys)
                else:
                    nxt = _select_token(step_logits, None, False,
                                        cfg.temperature, cfg.top_k,
                                        cfg.top_p)
                nxt = jnp.where(active, nxt, cfg.pad_token_id)
                nxt = nxt.astype(jnp.int32)
                if moe_shape:
                    # the live lanes' routing rides the tokens' fetch:
                    # one array, one transfer a tick
                    nxt = jnp.concatenate([nxt, _live_assignments(
                        mutated["moe_stats"], active).reshape(-1)])
                return cache, history, keys_out, nxt

        # one compile per bucket width / exactly one for decode — the
        # parity + compile-count tests pin this via _cache_size().
        # The KV pool and the rings beside it are donated, and every
        # donated arg is reassigned from the outputs wherever these
        # are called. Donation alone only lends the program the
        # buffer: the pool stays in place across a tick because the
        # model also writes it in place — a scatter into each layer's
        # own leaf when the layers are unrolled, a scatter into the
        # stack that the layer loop carries under scan_layers (scanned
        # as xs/ys, the paged pool was copied six times a tick;
        # PERF.md, PR 25). tests/test_serving_paged.py reads the
        # aliasing off the compiled program.
        # self-draft programs carry two extra donated buffers (the
        # draft pool in both, plus the draft params slot shifting the
        # argnums); the key ring is donated everywhere it is threaded
        # (the token array is never donated: the in-flight tick's record
        # fetches it after the next call has taken it as an input)
        if self.self_draft:
            assign_donate = (0, 1, 2, 3)
            decode_donate = (2, 3, 4, 10)
        elif block_len:
            # the pool and the lanes' blocks (tokens, flags)
            assign_donate = (0, 1, 2)
            decode_donate = (1, 2, 3)
        else:
            assign_donate = (0, 1, 2)
            decode_donate = (1, 2, 8)
        self._prefill_jit = jax.jit(prefill_fn)
        # one program a window width; the batch-1 cache is handed from
        # window to window and updated in place
        self._window_jit = jax.jit(window_fn, donate_argnums=(1,))
        self._fresh_jit = jax.jit(fresh_fn)
        self._assign_jit = jax.jit(assign_fn, donate_argnums=assign_donate)
        self._decode_jit = jax.jit(decode_fn, donate_argnums=decode_donate)

    def _check_block_config(self) -> None:
        """What a block engine cannot serve, each with its reason, and
        the reveal forwards a block takes."""
        cfg, L = self.config, self._block_len
        if cfg.spec_mode != "off":
            raise ValueError(
                f"spec_mode={cfg.spec_mode!r} cannot serve a model "
                f"generated by diffusion over blocks of {L}: a block's "
                "forward already yields several tokens, and a draft "
                "window is causal where a block is not; use "
                "spec_mode='off'")
        if cfg.do_sample:
            raise ValueError(
                "do_sample cannot serve a model generated by diffusion "
                "over blocks: a revealed token is the argmax of its own "
                "logits (sampled reveal is not built)")
        if _controls_active(cfg.repetition_penalty,
                            cfg.no_repeat_ngram_size, cfg.min_length):
            raise ValueError(
                "logits controls (repetition_penalty / "
                "no_repeat_ngram_size / min_length) act at one committed "
                f"cursor; a block's forward scores {L} positions of which "
                "any may be revealed next")
        if cfg.kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' cannot serve a model generated by "
                "diffusion over blocks: a block's rows are rewritten "
                "every forward and read as they lie; use kv_dtype='fp32'")
        self._steps = L if cfg.denoise_steps is None else cfg.denoise_steps
        if L % self._steps:
            raise ValueError(
                f"denoise_steps={self._steps} must divide the model's "
                f"block_length {L}: every reveal forward sets the same "
                "number of positions")
        odd = [b for b in self.ladder.buckets if b % L]
        if odd:
            raise ValueError(
                f"buckets {odd} are not whole blocks of {L}: a prompt is "
                "prefilled in windows of a bucket from position 0, and a "
                "window holds whole blocks")

    def _init_pool(self):
        """Zeros KV pool in the configured (layout, dtype)."""
        cfg = self.config
        if not self.paged and cfg.kv_dtype == "fp32":
            return init_slot_cache(self.model, cfg.num_slots,
                                   self._abstract_init)
        if self.paged:
            return init_pool_cache(
                self.model, cfg.num_slots, layout="paged",
                kv_dtype=cfg.kv_dtype, num_blocks=self.num_blocks,
                block_size=self.block_size,
                max_blocks_per_slot=self.max_blocks_per_slot,
                **(dict(ring_num_blocks=self.ring_num_blocks,
                        ring_blocks_per_slot=self.ring_blocks)
                   if self.ring_blocks else {}),
                abstract=self._abstract_init)
        return init_pool_cache(self.model, cfg.num_slots, layout="slot",
                               kv_dtype=cfg.kv_dtype,
                               abstract=self._abstract_init)

    # ---- submission side -------------------------------------------

    def _journal_add_locked(self, req: Request) -> None:
        """Enter `req` into the bounded commit journal (caller holds
        self._cv). A duplicate request_id replaces the older entry —
        the LATEST execution owns the id (a resumed retry must not
        answer `GET /partial/<id>` with its predecessor's snapshot)."""
        self._journal[req.request_id] = req
        self._journal.move_to_end(req.request_id)
        while len(self._journal) > self.config.journal_ring:
            self._journal.popitem(last=False)

    def _record_rejection_locked(self, req: Request, reason: str,
                                 **attrs) -> None:
        """The ONE rejection record: mark the request, stamp the
        terminal timeline event, and put its waterfall in the debug
        ring. Caller holds self._cv."""
        req.state = REJECTED
        req.finish_reason = reason
        req.timeline.add(self._clock(), "rejected", reason=reason,
                         **attrs)
        self._recent.append(self._request_dict(req))
        # a rejected request's stream (opened at submit, then e.g.
        # flushed by begin_drain) must close, not hang its reader
        self._sync_stream(req)

    def _reject_prompt(self, ids: np.ndarray, reason: str,
                       request_id: Optional[str],
                       trace_id: Optional[str] = None,
                       parent_span_id: Optional[str] = None,
                       **attrs) -> None:
        """413-class rejections happen before a Request enters the
        queue, but their timelines still belong in the debug ring — a
        burst of 413s must be diagnosable from `GET /debug/requests`
        and the post-mortem bundle, like the 429s are."""
        req = Request(ids, 0, request_id, None, self._clock(),
                      epoch=self._wall())
        req.timeline.trace_id = trace_id
        req.timeline.parent_span_id = parent_span_id
        with self._cv:
            self._record_rejection_locked(
                req, reason, prompt_tokens=int(len(ids)), **attrs)

    def _windows(self, prefill_len: int) -> Optional[list]:
        """`[(start, width)]`: the windows a prompt of `prefill_len`
        tokens is prefilled in from position 0, or None where it takes
        one left-padded bucket. Windows serve a prompt past the largest
        bucket, and every prompt of a cache defined on positions
        or of a block grid (`_from_zero`). A speculative engine has none:
        its drafter reads a history laid out by buckets."""
        if self._block_len and not prefill_len:
            return []       # a prompt shorter than a block is all tail
        bucket = self.ladder.bucket_for(prefill_len)
        if self.spec or (bucket is not None and not self._from_zero):
            return None
        width = bucket if bucket is not None else self.ladder.max_bucket
        return [(start, width) for start in range(0, prefill_len, width)]

    def _whole_blocks(self, n: int) -> int:
        """The positions of `n` that are whole generation blocks."""
        return n // self._block_len * self._block_len

    def _decode_span(self, prompt_len: int, max_new: int,
                     resumed: int) -> int:
        """Positions a lane writes past the ones admission prefilled:
        what the paged footprint is charged for beside the bucket. A
        resumed request's committed prefix lives inside the bucket; a
        block engine writes whole blocks, the prompt's tail among
        them, to the end of the last one."""
        if self._block_len:
            return self._whole_blocks(
                prompt_len + max_new + self._block_len - 1) - \
                self._whole_blocks(prompt_len)
        return max_new - resumed + 1 if resumed else max_new

    def submit(self, input_ids, max_new_tokens: Optional[int] = None,
               request_id: Optional[str] = None,
               deadline_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               parent_span_id: Optional[str] = None,
               resume_tokens: Optional[Sequence[int]] = None,
               resume_source: Optional[str] = None,
               seed: Optional[int] = None,
               stream: bool = False) -> Request:
        """Queue a prompt. Raises QueueFull (backpressure) or
        PromptTooLong (no bucket / no cache headroom). `deadline_s` is
        seconds from now; an expired request frees its slot and
        finishes with reason "deadline". `trace_id`/`parent_span_id`
        are the distributed-trace correlation ids carried in off the
        wire (docs/observability.md "Distributed tracing") — pure
        host-side bookkeeping stamped onto the request's timeline and
        debug-ring entry, never an input to any traced program.

        `resume_tokens` is the resume-from-token-k path
        (docs/fault_tolerance.md "Preemption runbook"): tokens a
        previous execution of this request already committed (read from
        a replica's `GET /partial/<id>` journal). Admission prefills
        prompt + resume_tokens[:-1] in ONE bucketed prefill — greedy
        left-padded prefill logits are position-for-position identical
        to incremental decode, so the remainder of the generation is
        token-identical to the undisturbed run — and only the remaining
        max_new - k tokens are decoded. `max_new_tokens` keeps its
        TOTAL-generation meaning (the resumed prefix counts toward it).

        `seed` pins this request's sampling stream (docs/streaming.md
        "Seed semantics"): the same prompt + seed reproduces the same
        sampled tokens run-to-run regardless of pool co-tenancy. When
        None, the seed derives from the request id, so an explicit-id
        retry replays the same stream. `stream=True` opens a live
        token stream the scheduler feeds at commit time
        (`Engine.streams` / docs/streaming.md).
        """
        if self._draining:
            # checked again under the lock below; this early exit just
            # spares rejected requests the bucket/blocks math
            self.metrics.count("rejected_draining")
            self._log({"event": "serving_reject", "reason": "draining"})
            raise Draining("engine is draining; not admitting")
        if max_new_tokens is not None and int(max_new_tokens) < 1:
            # a bad request field, not a too-long prompt — the API
            # layer maps this to 422, not 413
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        resume = [int(t) for t in resume_tokens] if resume_tokens \
            else []
        # resume on a speculative engine is sound: the max_new clamp is
        # gamma-aware, the paged footprint charge includes the gamma
        # tail, and both drafters read only the committed history —
        # which admission restores inside the prefill bucket. (This
        # gate used to reject; streaming retries made spec+resume the
        # common path, docs/streaming.md "Retry and resume".)
        requested_new = int(max_new_tokens if max_new_tokens is not None
                            else self.config.max_new_tokens)
        if resume and requested_new <= len(resume):
            # the journal already holds the whole generation — the
            # caller should have answered from it, not resubmitted
            raise ValueError(
                f"resume_tokens carries {len(resume)} tokens but "
                f"max_new_tokens={requested_new} leaves nothing to "
                "decode")
        ids = np.asarray(input_ids, np.int32).reshape(-1)
        if resume and self._block_len:
            raise ValueError(
                "resume_tokens cannot be served by a block engine: a "
                "lane re-enters at a committed cursor of one token, and "
                "this engine's cursors move a block at a time")
        # a resumed request prefills prompt + resume[:-1] (the last
        # committed token re-enters as the decode seed, exactly where
        # an undisturbed lane would hold it); a block engine prefills
        # the prompt's whole blocks (its tail is the first block's)
        prefill_len = len(ids) + max(len(resume) - 1, 0) \
            if not self._block_len else self._whole_blocks(len(ids))
        # what the prompt occupies of the lane: its bucket where one
        # left-padded bucket takes it, the prompt itself where it is
        # filled from position 0 in windows — whose last must end
        # inside the batch-1 cache the windows fill
        windows = self._windows(prefill_len)
        if windows is None:
            bucket = self.ladder.bucket_for(prefill_len)
        else:
            bucket = prefill_len if not windows or \
                sum(windows[-1]) <= self.max_len else None
        if bucket is None:
            self.metrics.count("rejected_prompt_too_long")
            self._log({"event": "serving_reject", "reason":
                       "prompt_too_long", "prompt_tokens": len(ids)})
            self._reject_prompt(ids, "prompt_too_long", request_id,
                                trace_id=trace_id,
                                parent_span_id=parent_span_id)
            raise PromptTooLong(
                f"prompt of {len(ids)} tokens exceeds the largest "
                f"bucket {self.ladder.max_bucket}" if windows is None
                else f"prompt of {len(ids)} tokens in windows of "
                f"{windows[-1][1]} overruns the lane's "
                f"{self.max_len} positions")
        max_new = requested_new
        # the lane must hold bucket + generated tokens + the gamma-wide
        # speculative tail (seq_capacity is max_len for the slot
        # layout, blocks x block_size for paged); clamping without the
        # gamma term would let the verify window silently walk past
        # the lane end — the off-by-gamma the boundary test pins.
        # A resumed request only DECODES max_new - (k-1) of its total:
        # k-1 committed tokens ride inside the prefill bucket, so they
        # restore that much headroom to the clamp
        max_new = min(max_new, self.seq_capacity - bucket - self._gamma
                      + max(len(resume) - 1, 0)) if not self._block_len \
            else min(max_new,
                     self._whole_blocks(self.seq_capacity) - len(ids))
        if max_new < (len(resume) + 1 if resume else 1):
            self.metrics.count("rejected_prompt_too_long")
            self._log({"event": "serving_reject", "reason":
                       "prompt_too_long", "prompt_tokens": len(ids)})
            self._reject_prompt(ids, "prompt_too_long", request_id,
                                trace_id=trace_id,
                                parent_span_id=parent_span_id,
                                bucket=int(bucket))
            raise PromptTooLong(
                f"bucket {bucket} leaves no decode headroom in the "
                f"KV lane capacity {self.seq_capacity}" +
                (f" (speculative window needs gamma={self._gamma} "
                 "extra positions)" if self._gamma else ""))
        # tokens the lane actually DECODES past the prefill bucket —
        # what the paged footprint is charged for (a resumed request's
        # committed prefix lives inside the bucket)
        decode_span = self._decode_span(len(ids), max_new, len(resume))
        if self.paged:
            # a footprint the whole pool cannot hold would sit at the
            # queue head forever (nothing can free enough blocks) —
            # reject NOW instead of livelocking the FIFO
            need = blocks_for_tokens(bucket + decode_span + self._gamma,
                                     self.block_size)
            if need > self._allocator.total_blocks:
                self.metrics.count("rejected_prompt_too_long")
                self._log({"event": "serving_reject",
                           "reason": "kv_pool_too_small",
                           "prompt_tokens": len(ids),
                           "blocks_needed": need,
                           "blocks_total":
                               self._allocator.total_blocks})
                self._reject_prompt(
                    ids, "kv_pool_too_small", request_id,
                    trace_id=trace_id, parent_span_id=parent_span_id,
                    blocks_needed=int(need),
                    blocks_total=int(self._allocator.total_blocks))
                raise PromptTooLong(
                    f"request needs {need} KV blocks but the pool "
                    f"only has {self._allocator.total_blocks}")
        submitted = self._clock()
        req = Request(ids, max_new, request_id,
                      None if deadline_s is None
                      else submitted + deadline_s,
                      submitted, epoch=self._wall())
        req.timeline.trace_id = trace_id
        req.timeline.parent_span_id = parent_span_id
        # resolve the per-request sampling seed: an explicit client
        # seed wins; otherwise hash the request id, so an explicit-id
        # retry (the router's resume path) folds to the SAME lane key
        # and the resumed stream continues the same distribution
        req.seed = (int(seed) & 0x7FFFFFFF) if seed is not None \
            else zlib.crc32(req.request_id.encode()) & 0x7FFFFFFF
        if resume:
            # seed the committed prefix NOW: the journal and the debug
            # endpoints must show the true progress from the first
            # moment, and the finish check counts TOTAL generation
            req.resume = resume
            req.resume_source = resume_source
            req.tokens = list(resume)
        with span("serving/admit"):
            with span("lock_wait"):
                self._cv.acquire()
            try:
                self._enqueue_locked(req, bucket, request_id is not None,
                                     stream)
            finally:
                self._cv.release()
        return req

    def _enqueue_locked(self, req: Request, bucket: int,
                        explicit_id: bool, stream: bool) -> None:
        """submit()'s part under the scheduler's lock: refuse (draining,
        a live duplicate of an explicit id, a full queue) or queue."""
        # the clock is read AFTER the lock is held: `enqueued` minus
        # submit is the wait for the scheduler's lock, which
        # `phases()` reports as lock_wait_s
        now = self._clock()
        self.metrics.record_submit_lock_wait(now - req.submit_time)
        if self._draining:
            self.metrics.count("rejected_draining")
            self._log({"event": "serving_reject",
                       "reason": "draining"})
            raise Draining("engine is draining; not admitting")
        if explicit_id:
            # idempotent-safe retry contract (docs/fleet.md): an
            # explicit id may never run twice concurrently here —
            # a router retrying a request this replica may still
            # be executing must be REJECTED, not doubled. (No
            # debug-ring entry: the ORIGINAL request owns the id
            # there; the counter + log line carry the 409s.)
            for live in list(self._queue) + [
                    r for r in self._slot_req if r is not None]:
                if live.request_id == req.request_id:
                    self.metrics.count("rejected_duplicate")
                    self._log({"event": "serving_reject",
                               "reason": "duplicate_request_id",
                               "request_id": req.request_id,
                               "live_state": live.state})
                    raise DuplicateRequest(
                        f"request_id {req.request_id!r} is already "
                        f"{live.state} on this replica")
        if len(self._queue) >= self.config.max_queue:
            self.metrics.count("rejected_queue_full")
            self._log({"event": "serving_reject",
                       "reason": "queue_full",
                       "queue_depth": len(self._queue)})
            # rejected timelines join the debug ring: "who was 429'd
            # and when" is exactly the overload question
            self._record_rejection_locked(
                req, "queue_full", queue_depth=len(self._queue))
            raise QueueFull(
                f"admission queue at max_queue="
                f"{self.config.max_queue}")
        self._queue.append(req)
        req.timeline.add(now, "enqueued",
                         prompt_tokens=int(len(req.prompt)), bucket=bucket,
                         queue_depth=len(self._queue))
        if req.resume:
            # the initial resume mark (the `evacuated` event's
            # cross-replica counterpart): where the committed
            # prefix came from and how long it is
            req.timeline.add(now, "resumed_from",
                             tokens=len(req.resume),
                             source=req.resume_source)
        self._journal_add_locked(req)
        if stream:
            # open the live stream BEFORE any token can commit so
            # the reader never misses the head; open() replays
            # req.tokens, so a resumed stream starts at k, not 0
            self.streams.open(req)
        self.metrics.count("admitted")
        self._log({"event": "serving_admit",
                   "request_id": req.request_id, "bucket": bucket,
                   "queue_depth": len(self._queue)})
        self._cv.notify_all()

    def cancel(self, request_id: str) -> bool:
        """Cancel a queued or running request; a running one frees its
        slot at the next tick. False when the id is unknown/done."""
        with self._cv:
            for req in self._queue:
                if req.request_id == request_id:
                    self._queue.remove(req)
                    self._finish(req, CANCELLED, "cancelled")
                    return True
            for req in self._slot_req:
                if req is not None and req.request_id == request_id:
                    req._cancel = True
                    return True
        return False

    # ---- engine loop -----------------------------------------------

    def step(self) -> int:
        """One tick: reclaim → admit → one jitted decode over the pool.
        Returns the number of lanes still active after the tick; on
        return the tick's tokens are committed and nothing is in
        flight."""
        return self._tick(ahead=False)

    def _tick(self, ahead: bool) -> int:
        # the wait for the lock has a span of its own, so a trace tells
        # a scheduler starved by submitters from one at work
        with span("serving/lock_wait") as s:
            self._cv.acquire()
        self._declared_wait(s, lock=True)
        try:
            # the tick IS the critical section: the scheduler owns all
            # device state under _cv by design; admission threads wait
            # at most one tick (docs/serving.md "Threading")
            return self._tick_locked(ahead)
        finally:
            self._cv.release()

    def _tick_locked(self, ahead: bool) -> int:
        """Reclaim, admit, ENQUEUE a tick, FETCH and commit a tick.
        With `ahead` the fetched tick is the one the previous call
        enqueued, so the device runs the new tick while the host
        fetches, commits and comes round again; without it the tick
        just enqueued is fetched. A speculative tick's next cursors are
        its fetched accept counts, so it is never run ahead."""
        ahead = ahead and not self.spec
        if not ahead:
            self._drain_locked()
        with span("serving/reclaim"):
            now = self._clock()
            # a queued request whose deadline already passed will never
            # be worth prefilling — drop it while it waits, not just at
            # pop
            expired = [r for r in self._queue
                       if r.deadline is not None and now > r.deadline]
            for req in expired:
                self._queue.remove(req)
                self._finish(req, EXPIRED, "deadline")
            # a lane released here may have a token in flight: its
            # record no longer names the lane's request, so the commit
            # drops it
            for i, req in enumerate(self._slot_req):
                if req is None:
                    continue
                if req._cancel:
                    self._release(i, CANCELLED, "cancelled")
                elif req.deadline is not None and now > req.deadline:
                    self._release(i, EXPIRED, "deadline")
        # no span of its own: it would become the parent of
        # `serving/prefill` and `serving/assign` and rename them
        admitted = self._admit()
        prev = self._inflight
        run = self._active & (self._ticks_left > 0)
        lanes = np.nonzero(run)[0]
        if prev is None and not len(lanes):
            return 0
        with span("serving/decode"):
            # an admission's first-token fetch has already waited for
            # everything queued, so the tick after it is not ahead
            tick = self._enqueue_tick_locked(
                run, lanes, prev is not None and not admitted) \
                if len(lanes) else None
            if not ahead:
                prev, tick = tick, None
            self._inflight = tick
            if prev is not None:
                self._fetch_locked(prev)
        if prev is not None:
            self._commit_locked(prev)
            self._open_tail()
            if self._inflight is not None and not self._active.any():
                # every lane of the tick in flight was just released:
                # nobody waits for its tokens
                self._inflight = None
        return int(self._active.sum())

    def _declared_wait(self, s, lock: bool = False) -> None:
        """On the serve thread, credit a closed span that waits by
        design (the lock, the device, the idle condition): its off-CPU
        seconds are declared wait, flushed to the scheduler's counters
        once a serve-loop iteration. A tick driven by `step()`,
        `run_until_idle` or a drain from another thread is not the
        scheduler thread's time."""
        if threading.current_thread() is self._thread:
            # never negative: the two clocks are not read at one instant
            self._waited += max(0.0, s.seconds - s.cpu_seconds)
            if lock:
                self._lock_waited += s.seconds

    def _open_tail(self) -> None:
        """On the serve thread, open `serving/tail`: from the end of a
        tick's commit until the serve loop is back at `_tick`. The serve
        loop closes it; no other thread opens one (its span stack would
        keep it)."""
        if threading.current_thread() is self._thread:
            self._tail = span("serving/tail")
            self._tail.__enter__()

    def _close_tail(self) -> None:
        tail, self._tail = self._tail, None
        if tail is not None:
            tail.__exit__(None, None, None)

    def _zero_tokens(self):
        """The device token array before any tick: the shape of the
        decode program's token output."""
        n = self.config.num_slots * max(self._block_len, 1)
        if self._moe_shape and not self.spec:
            n += self._moe_shape[0] * self._moe_shape[1]
        return jnp.zeros((n,), jnp.int32)

    def _decode_args(self, active) -> tuple:
        """The decode program's arguments with `active` as its live
        mask (warmup lowers with the same)."""
        if self._block_len:
            return (self.params, self._cache, self._block_tokens,
                    self._block_masked, self._phys, active)
        head = (self.params, self._draft_params, self._cache,
                self._draft_cache) if self.self_draft else \
            (self.params, self._cache)
        return head + (self._history, self._mask, self._last_tok,
                       self._pos, self._phys, active, self._keys)

    def _run_decode(self, active) -> tuple:
        """Enqueue the decode program. Every donated argument and the
        token array are rebound from its outputs; returns the device
        arrays the commit needs on the host."""
        out = self._decode_jit(*self._decode_args(active))
        if self._block_len:
            (self._cache, self._block_tokens, self._block_masked,
             self._last_tok) = out
            return (self._last_tok,)
        if self.self_draft:
            self._cache, self._draft_cache, *out = out
        else:
            self._cache, *out = out
        self._history, self._keys, self._last_tok, *fetch = out
        return tuple(fetch) or (self._last_tok,)

    def _enqueue_tick_locked(self, run, lanes, ahead: bool) -> _Tick:
        """Enqueue one decode tick over `lanes` (`run`: the same as a
        fresh bool mask) and advance what the host knows without its
        tokens: a plain tick moves each of its lanes one position on."""
        # real cached tokens this tick's attention reads: the logical
        # cursor, not `_phys`, which counts the bucket's padding
        # (a block's queries all read to the block's end)
        kv_tokens = int(self._pos[lanes].sum()) + \
            max(self._block_len, 1) * len(lanes)
        if self._attended_tokens is not None:
            # a sparse-attention model: what its queries read of what
            # is cached, from the cursors alone
            self.metrics.record_sparse(
                int(self._attended_tokens(self._pos[lanes] + 1).sum()),
                kv_tokens)
        if self._indexed_tokens is not None:
            self.metrics.record_index(
                int(self._indexed_tokens(self._pos[lanes] + 1).sum()),
                kv_tokens)
        if self._window_tokens is not None:
            # a model with window layers: what such a layer's queries
            # read, and the blocks of each kind the lanes hold
            self.metrics.record_window(
                int(self._window_tokens(self._pos[lanes] + 1).sum()),
                *((sum(map(len, self._slot_blocks)),
                   sum(map(len, self._slot_ring))) if self.paged
                  else (0, 0)))
        # of the table rows the tick's attention is handed, the blocks
        # up to each lane's PHYSICAL cursor (bucket padding in, as the
        # lane's blocks hold it; a released lane's one null block; a
        # verify window reaches `spec_gamma` further): what the paged
        # decode kernel walks, of what is tabled
        kv_blocks = (0, 0)
        if self.paged:
            reach = self._phys + (self.config.spec_gamma if self.spec
                                  else max(self._block_len - 1, 0))
            kv_blocks = (int((reach // self.block_size + 1).sum()),
                         len(reach) * self.max_blocks_per_slot)
        t0 = time.perf_counter()
        # dispatch: the host cursors are uploaded and the program
        # enqueued behind whatever the device is still running
        with span("dispatch", lanes=len(lanes)) as s:
            # argument handling, the three uploads, the enqueue
            with span("call"):
                out = self._run_decode(run)
            with span("copy_back"):
                for x in out:
                    # the copy back starts when the device is done, not
                    # when the host comes to ask
                    x.copy_to_host_async()
        self.metrics.record_dispatch(s)
        self._ticks_left = self._ticks_left - run
        commits = None
        if self._block_len:
            # the lanes whose block had one forward left made their
            # commit forward: the cursor moves a block on and the next
            # block takes all its forwards
            commits = run & (self._fwd_left == 1)
            self._fwd_left = np.where(commits, self._steps + 1,
                                      self._fwd_left - run)
            self._pos = self._pos + self._block_len * commits
            self._phys = self._phys + self._block_len * commits
        elif not self.spec:
            self._pos = self._pos + run
            self._phys = self._phys + run
        return _Tick(out, lanes, [self._slot_req[i] for i in lanes],
                     kv_tokens, kv_blocks, t0, ahead, commits)

    def _fetch_locked(self, tick: _Tick) -> None:
        """Block until `tick`'s outputs are on the host (copies — the
        device views are read-only)."""
        with span("fetch") as s:
            tick.host = tuple(np.array(x) for x in tick.out)
        self._declared_wait(s)
        now = time.perf_counter()
        # its share of the wall: from its enqueue, or from the fetch
        # before it where it was enqueued behind a running tick
        tick.seconds = now - max(tick.t0, self._fetched_at)
        self._fetched_at = now

    def _drain_locked(self) -> None:
        """Fetch and commit the in-flight tick, if any: what every
        reader of committed state (lane export, drain, stop) and every
        depth-0 tick does first."""
        tick, self._inflight = self._inflight, None
        if tick is not None:
            with span("serving/decode"):
                self._fetch_locked(tick)
            self._commit_locked(tick)

    def _commit_locked(self, tick: _Tick) -> None:
        """Host side of a fetched tick: token append, timeline, stream,
        release — for the lanes that still hold the request the tick
        ran for. A lane released since (cancel, deadline, an EOS one
        tick back, a detach) has its token dropped."""
        live = [(i, req) for i, req in zip(tick.lanes, tick.reqs)
                if self._slot_req[i] is req]
        # a speculative tick's first output is its accept counts
        if self._block_len:
            # what each committing lane is delivered of its block
            deliver = self._block_deliveries(tick, live)
            tokens = sum(len(toks) for _, _, toks, _ in deliver)
        else:
            tokens = len(live) if not self.spec else \
                int(tick.host[0][tick.lanes].sum()) + len(tick.lanes)
        # the per-lane loops append to each lane's stream; the readers
        # are woken once, at the end (docs/streaming.md "Delivery")
        with span("serving/commit", lanes=len(tick.lanes),
                  tokens=tokens) as s, self.streams.one_signal():
            if self._block_len:
                self._commit_blocks(tick, deliver)
            elif self.spec:
                self._commit_spec(tick, live)
            else:
                self._commit_plain(tick, live)
        self.metrics.record_commit(s)

    def _commit_spec(self, tick: _Tick, live) -> None:
        n_r, win = tick.host
        # per-lane commit: accepted prefix + the correction token,
        # so each lane's cursor advances INDEPENDENTLY (the whole
        # point over generate's batched min-advance)
        commit = np.zeros_like(self._pos)
        commit[tick.lanes] = n_r[tick.lanes] + 1
        self._pos = self._pos + commit
        self._phys = self._phys + commit
        # metrics count DELIVERED tokens, not the raw window: a
        # lane finishing mid-window (eos, or the max_new cap)
        # discards the tail, and counting it would inflate
        # decode_tokens and the acceptance rate `/stats` reports
        delivered = 0
        accepted_delivered = 0
        t_commit, stamp = self._clock(), time.perf_counter()
        for i, req in live:
            k = 0
            fin = None
            for tok in (int(t) for t in win[i, :commit[i]]):
                req.tokens.append(tok)
                k += 1
                if self.config.eos_token_id is not None and \
                        tok == self.config.eos_token_id:
                    fin = "eos"
                    break
                if len(req.tokens) >= req.max_new_tokens:
                    fin = "length"
                    break
            # the commit event must precede a release: _finish
            # snapshots the timeline into the debug ring
            req.timeline.add(t_commit, "commit", n=k,
                             accepted=min(int(n_r[i]), k),
                             tick_s=round(tick.seconds, 6))
            self._sync_stream(req, stamp)
            if fin is not None:
                self._release(i, FINISHED, fin)
            delivered += k
            # delivered tokens at offsets < n_r are accepted
            # drafts; the one at offset n_r is the correction
            accepted_delivered += min(int(n_r[i]), k)
        self.metrics.record_tick(len(tick.lanes),
                                 self.config.num_slots,
                                 tokens=delivered,
                                 kv_tokens=tick.kv_tokens,
                                 kv_blocks=tick.kv_blocks)
        self.metrics.record_spec(
            self.config.spec_gamma * len(tick.lanes),
            accepted_delivered)

    def _block_deliveries(self, tick: _Tick, live) -> list:
        """`[(lane, request, tokens, finish)]` for the live lanes whose
        forward in `tick` was a commit forward: the block's tokens less
        the prompt's tail (a request's first block), cut at
        `max_new_tokens` and after an EOS."""
        S, L = self.config.num_slots, self._block_len
        blocks = tick.host[0][:S * L].reshape(S, L)
        eos = self.config.eos_token_id
        out = []
        for i, req in live:
            if not tick.commits[i]:
                continue
            skip = len(req.prompt) % L if not req.tokens else 0
            toks = [int(t) for t in blocks[i, skip:]]
            toks = toks[:req.max_new_tokens - len(req.tokens)]
            fin = "length" if len(req.tokens) + len(toks) >= \
                req.max_new_tokens else None
            if eos is not None and eos in toks:
                toks = toks[:toks.index(eos) + 1]
                fin = "eos"
            out.append((i, req, toks, fin))
        return out

    def _commit_blocks(self, tick: _Tick, deliver: list) -> None:
        """A block tick's commit: every live lane made a forward; the
        lanes in `deliver` also finished a block, whose tokens arrive
        together — one `commit` event, one stream publish — and are the
        request's first where it had none (`ttft` is the first block's
        commit)."""
        S = self.config.num_slots
        if self._moe_shape:
            self.metrics.record_moe(
                tick.host[0][S * self._block_len:].reshape(self._moe_shape),
                self._experts_held)
        self.metrics.record_tick(
            len(tick.lanes), S,
            tokens=sum(len(toks) for _, _, toks, _ in deliver),
            kv_tokens=tick.kv_tokens, kv_blocks=tick.kv_blocks,
            ahead=tick.ahead)
        self.metrics.record_block_forwards(
            len(tick.lanes), int(tick.commits[tick.lanes].sum()))
        t_commit, stamp = self._clock(), time.perf_counter()
        for i, req, toks, fin in deliver:
            if req.ttft_s is None:
                req.ttft_s = t_commit - req.submit_time
                self.metrics.record_ttft(req.ttft_s)
                req.timeline.add(t_commit, "first_token")
            req.tokens.extend(toks)
            req.timeline.add(t_commit, "commit", n=len(toks),
                             tick_s=round(tick.seconds, 6))
            self._sync_stream(req, stamp)
            if fin is not None:
                # an EOS is learnt a tick late, as in `_commit_plain`
                self._release(i, FINISHED, fin)

    def _commit_plain(self, tick: _Tick, live) -> None:
        nxt, S = tick.host[0], self.config.num_slots
        if self._moe_shape:
            self.metrics.record_moe(nxt[S:].reshape(self._moe_shape),
                                    self._experts_held)
        # credited at commit, and only what is delivered: the counter
        # keeps matching the clients' count
        self.metrics.record_tick(len(tick.lanes), S,
                                 tokens=len(live),
                                 kv_tokens=tick.kv_tokens,
                                 kv_blocks=tick.kv_blocks,
                                 ahead=tick.ahead)
        t_commit, stamp = self._clock(), time.perf_counter()
        for i, req in live:
            tok = int(nxt[i])
            req.tokens.append(tok)
            req.timeline.add(t_commit, "commit", n=1,
                             tick_s=round(tick.seconds, 6))
            self._sync_stream(req, stamp)
            if self.config.eos_token_id is not None and \
                    tok == self.config.eos_token_id:
                # the host learns an EOS a tick late: the lane's extra
                # tick, if one is in flight, wrote inside blocks it
                # still owned and its token is dropped above
                self._release(i, FINISHED, "eos")
            elif len(req.tokens) >= req.max_new_tokens:
                self._release(i, FINISHED, "length")

    def _admit(self) -> int:
        """Fill free lanes from the queue head; returns the prefills
        run (each one waited for its first token, and with it for
        everything the device had queued)."""
        prefills = 0
        for slot in range(self.config.num_slots):
            if self._active[slot] or not self._queue:
                continue
            req = self._queue.popleft()
            now = self._clock()
            if req._cancel:
                self._finish(req, CANCELLED, "cancelled")
                continue
            if req.deadline is not None and now > req.deadline:
                self._finish(req, EXPIRED, "deadline")
                continue
            # resume-from-token-k admission (docs/fault_tolerance.md):
            # the committed prefix minus its last token joins the
            # prompt in ONE bucketed prefill — identical left-pad
            # cumsum positions make the combined prefill's KV
            # position-for-position equal to the incremental decode
            # that produced those tokens, which is what keeps the
            # remainder greedy token-identical to the unkilled run
            resume = req.resume
            prefill_ids = req.prompt if not resume else np.concatenate(
                [req.prompt, np.asarray(resume[:-1], np.int32)])
            if self._block_len:
                prefill_ids = req.prompt[:self._whole_blocks(
                    len(req.prompt))]
            windows = self._windows(len(prefill_ids))
            bucket = self.ladder.bucket_for(len(prefill_ids)) \
                if windows is None else len(prefill_ids)
            decode_span = self._decode_span(
                len(req.prompt), req.max_new_tokens, len(resume))
            blocks = None
            if self.paged:
                # admission switches from "free slot" to "enough free
                # blocks" for the request's ACTUAL footprint; when the
                # pool can't serve it, the head of the queue waits for
                # reclaim (FIFO — later requests must not starve it),
                # the queue fills, and submit's QueueFull (429) is the
                # backpressure surface
                need = blocks_for_tokens(
                    bucket + decode_span + self._gamma,
                    self.block_size)
                with span("serving/alloc", request_id=req.request_id,
                          blocks=int(need), **(
                              {"ring_blocks": min(need, self.ring_blocks)}
                              if self.ring_blocks else {})):
                    blocks = self._alloc_blocks(need)
                    if blocks is None:
                        self._defer(req, need, now)
                        return prefills
                    self._deferred_req = None
            try:
                with span("serving/prepare", request_id=req.request_id,
                          bucket=int(bucket)):
                    if windows is None:
                        row, mask_row = self.ladder.pad_prompt(
                            prefill_ids, bucket, self.config.pad_token_id)
                    else:
                        # filled from position 0: the lane holds the prompt
                        row = np.asarray(prefill_ids, np.int32)
                        mask_row = np.ones_like(row)
                    if self.config.do_sample:
                        # per-request key derivation (docs/streaming.md
                        # "Seed semantics"): fold the request seed into the
                        # engine base key, then split once — one half seeds
                        # the prefill draw, the other becomes this lane's
                        # ring entry. No global RNG is consumed, so a
                        # request's stream is independent of admission
                        # order and pool co-tenancy.
                        base = jax.random.fold_in(self._base_key, req.seed)
                        key, lane_key = jax.random.split(base)
                    else:
                        key = lane_key = self._zero_key
                    req.timeline.add(self._clock(), "admitted", slot=slot,
                                     bucket=int(bucket))
                    req.timeline.add(self._clock(), "prefill_start",
                                     bucket=int(bucket))
                with span("serving/prefill", request_id=req.request_id,
                          bucket=int(bucket),
                          prompt_tokens=int(len(prefill_ids))) as s:
                    if self._block_len:
                        # nothing is fetched: no logits are read, and
                        # the lane's first tokens come with its first
                        # block's commit
                        primed = self._prefill_windows(req, row, windows,
                                                       key)
                    elif windows is not None:
                        primed, tok = self._prefill_windows(
                            req, row, windows, key)
                    elif self.self_draft:
                        primed, d_primed, tok = self._prefill_jit(
                            self.params, self._draft_params, row[None],
                            mask_row[None], key)
                    else:
                        primed, tok = self._prefill_jit(
                            self.params, row[None], mask_row[None], key)
                    if not self._block_len:
                        tok = int(np.asarray(tok)[0])
                if self._block_len:
                    if windows:
                        self.metrics.record_prefill_windows(
                            windows[0][1], len(windows), len(prefill_ids))
                    if self.paged:      # the lane owns them
                        self._slot_blocks[slot], self._slot_ring[slot] = \
                            blocks
                    with span("serving/assign", request_id=req.request_id,
                              slot=slot):
                        self._assign_block(req, slot, primed)
                    continue
                # its first-token fetch waits for the device
                self._declared_wait(s)
                prefills += 1
                if windows is None:
                    self.metrics.record_prefill(bucket, len(prefill_ids))
                    programs, width = 1, bucket
                else:
                    width = windows[0][1]
                    programs = len(windows)
                    self.metrics.record_prefill_windows(
                        width, programs, len(prefill_ids))
                # what the head projected: the row each program was
                # asked for, or every padded row of a model that
                # cannot be asked
                self.metrics.count(
                    "prefill_head_rows",
                    programs * (1 if self._row_only else width))
                t_first = self._clock()
                req.ttft_s = t_first - req.submit_time
                self.metrics.record_ttft(req.ttft_s)
                req.timeline.add(t_first, "first_token")
                if resume:
                    # the prefill-selected token is DISCARDED: a
                    # resumed lane's next decode seed is the
                    # already-committed resume[-1] (seeded into
                    # req.tokens at submit), not a re-selection —
                    # exactly the cursor the unkilled lane would hold
                    tok = resume[-1]
                else:
                    req.tokens.append(tok)
                self._sync_stream(req, time.perf_counter())
                if self.config.eos_token_id is not None and \
                        tok == self.config.eos_token_id:
                    if blocks is not None:
                        self._free_blocks(*blocks)
                        blocks = None
                    self._finish(req, FINISHED, "eos")
                    continue
                if len(req.tokens) >= req.max_new_tokens:
                    if blocks is not None:
                        self._free_blocks(*blocks)
                        blocks = None
                    self._finish(req, FINISHED, "length")
                    continue
            except BaseException:  # noqa: BLE001 — release + re-raise
                # a failed prefill must not strand the request's KV
                # blocks: return them to the pool before propagating
                if blocks is not None:
                    self._free_blocks(*blocks)
                raise
            if self.paged:
                # the lane owns them
                self._slot_blocks[slot], self._slot_ring[slot] = blocks
            with span("serving/assign", request_id=req.request_id,
                      slot=slot):
                self._assign(req, slot, bucket, row, mask_row, primed,
                             d_primed if self.self_draft else None, tok,
                             lane_key)
        return prefills

    def _alloc_blocks(self, need: int) -> Optional[tuple]:
        """(`need` blocks of the lane-long kind, the ring's blocks) for
        one request, or None when either free list cannot serve it. A
        lane shorter than the ring takes only the ring blocks it will
        reach; a model without a ring takes none."""
        ring_need = min(need, self.ring_blocks)
        ring = self._ring_allocator.alloc(ring_need) if ring_need else []
        if ring is None:
            return None
        blocks = self._allocator.alloc(need)
        if blocks is None:
            if ring:
                self._ring_allocator.free(ring)
            return None
        return blocks, ring

    def _free_blocks(self, blocks: list, ring: list) -> None:
        self._allocator.free(blocks)
        if ring:
            self._ring_allocator.free(ring)

    def _defer(self, req: Request, need: int, now: float) -> None:
        """The pool cannot serve `req`: back to the head of the queue."""
        self._queue.appendleft(req)
        if self._deferred_req != req.request_id:
            # count the deferral EVENT once, not once per tick the head
            # keeps waiting
            self._deferred_req = req.request_id
            self.metrics.count("deferred_admissions")
            ring = {"ring_blocks_free":
                    int(self._ring_allocator.free_blocks)} \
                if self.ring_blocks else {}
            req.timeline.add(
                now, "deferred", blocks_needed=int(need),
                blocks_free=int(self._allocator.free_blocks), **ring)
            self._log({"event": "serving_defer",
                       "reason": "kv_blocks_exhausted",
                       "request_id": req.request_id,
                       "blocks_needed": need,
                       "blocks_free": self._allocator.free_blocks, **ring})

    def _prefill_windows(self, req: Request, ids, windows, key):
        """Prefill `ids` window by window onto one batch-1 cache, each
        call the same program (one a width), the cache donated from
        call to call. Returns the primed cache and the first token,
        still on the device. No decode tick runs between windows."""
        if not self._block_len:
            prompt_row = np.zeros((1, self.seq_capacity), np.int32)
            prompt_row[0, :len(ids)] = ids
        primed = self._fresh_jit()
        for w, (start, width) in enumerate(windows):
            n_valid = min(width, len(ids) - start)
            chunk = np.full((1, width), self.config.pad_token_id, np.int32)
            chunk[0, :n_valid] = ids[start:start + n_valid]
            with span("window", request_id=req.request_id, window=w,
                      tokens=int(n_valid)):
                if self._block_len:
                    # whole blocks, no head, no token (`window_fn`)
                    primed = self._window_jit(
                        self.params, primed, chunk, np.int32(start),
                        np.int32(n_valid))
                    continue
                primed, tok = self._window_jit(
                    self.params, primed, chunk, prompt_row,
                    np.int32(start), np.int32(n_valid), key)
        return primed if self._block_len else (primed, tok)

    def _assign(self, req: Request, slot: int, bucket: int, row,
                mask_row, primed, d_primed, tok: int, lane_key) -> None:
        """Install a prefilled request in lane `slot`: the lane's
        history / mask / block-table rows, the assign program's
        dispatch (which also writes `tok`, the lane's next input, into
        the device token array), and the host cursors."""
        # history/mask lanes: padded prompt, mask open from the bucket
        # edge on (causal validity bounds the open tail)
        L = self.seq_capacity
        hist_row = np.zeros((L,), np.int32)
        hist_row[:bucket] = row
        full_mask = np.ones((L,), np.int32)
        full_mask[:bucket] = mask_row
        if self.paged:
            blocks = self._slot_blocks[slot]
            table_row = np.zeros((self.max_blocks_per_slot,), np.int32)
            table_row[:len(blocks)] = blocks
        tables = (table_row,) if self.paged else ()
        if self.ring_blocks:
            ring = self._slot_ring[slot]
            ring_row = np.zeros((self.ring_blocks,), np.int32)
            ring_row[:len(ring)] = ring
            tables += (ring_row,)
        rows = (hist_row, full_mask) + tables + \
            (np.int32(slot), np.int32(tok))
        if self.self_draft:
            (self._cache, self._draft_cache, self._history, self._mask,
             self._last_tok) = self._assign_jit(
                self._cache, self._draft_cache, self._history,
                self._mask, self._last_tok, primed, d_primed, *rows)
        else:
            self._cache, self._history, self._mask, self._last_tok = \
                self._assign_jit(self._cache, self._history, self._mask,
                                 self._last_tok, primed, *rows)
        req.state = RUNNING
        req.slot = slot
        self._slot_req[slot] = req
        self._active[slot] = True
        self._ticks_left[slot] = req.max_new_tokens - len(req.tokens)
        # logical pos of last_tok: len(prompt) for a fresh lane
        # (tokens == [tok]); a resumed lane holds k committed
        # tokens, the same invariant pos = P + len(tokens) - 1
        self._pos[slot] = len(req.prompt) + len(req.tokens) - 1
        self._phys[slot] = bucket           # physical cursor
        if self.config.do_sample:
            # install the lane's ring entry; greedy engines keep
            # the zero ring and never consume it
            self._keys = self._keys.at[slot].set(lane_key)

    def _assign_block(self, req: Request, slot: int, primed) -> None:
        """`_assign` for a block engine: the primed whole blocks into
        lane `slot`, the prompt's tail and masks after it as the lane's
        first block, and what the host knows of the lane's forwards: a
        block with `m` positions to generate takes `ceil(m / per
        forward)` reveal forwards and its commit forward."""
        L, steps = self._block_len, self._steps
        whole = self._whole_blocks(len(req.prompt))
        tail = len(req.prompt) - whole
        first = np.full((L,), self._mask_id, np.int32)
        first[:tail] = req.prompt[whole:]
        tables = ()
        if self.paged:
            blocks = self._slot_blocks[slot]
            table_row = np.zeros((self.max_blocks_per_slot,), np.int32)
            table_row[:len(blocks)] = blocks
            tables = (table_row,)
        self._cache, self._block_tokens, self._block_masked = \
            self._assign_jit(self._cache, self._block_tokens,
                             self._block_masked, primed, first,
                             np.arange(L) >= tail, *tables, np.int32(slot))
        req.state = RUNNING
        req.slot = slot
        self._slot_req[slot] = req
        self._active[slot] = True
        self._pos[slot] = self._phys[slot] = whole
        per = L // steps
        self._fwd_left[slot] = -(-(L - tail) // per) + 1
        blocks_to_go = -(-(tail + req.max_new_tokens) // L)
        self._ticks_left[slot] = self._fwd_left[slot] + \
            (blocks_to_go - 1) * (steps + 1)

    def _release(self, slot: int, state: str, reason: str) -> None:
        req = self._slot_req[slot]
        self._slot_req[slot] = None
        self._active[slot] = False
        self._phys[slot] = 0
        self._pos[slot] = 0
        if self.paged and self._slot_blocks[slot]:
            # blocks return to the free list NOW; the lane's stale
            # block-table row is parked on the null block by the next
            # decode's entry clamp before any write can land
            self._free_blocks(self._slot_blocks[slot],
                              self._slot_ring[slot])
            self._slot_blocks[slot], self._slot_ring[slot] = [], []
        self._finish(req, state, reason)

    def _finish(self, req: Request, state: str, reason: str) -> None:
        req.state = state
        req.finish_reason = reason
        req.slot = None
        if state == FINISHED:
            self.metrics.count("completed")
        elif state == CANCELLED:
            self.metrics.count("cancelled")
        elif state == EXPIRED:
            self.metrics.count("expired")
        end_t = self._clock()
        req.timeline.add(end_t, state, reason=reason)
        phases = req.timeline.phases(end_t)
        self.metrics.record_phases(phases)
        self._recent.append(self._request_dict(req, phases=phases))
        self.metrics.record_latency(end_t - req.submit_time)
        self._log({"event": "serving_finish",
                   "request_id": req.request_id, "reason": reason,
                   "tokens": len(req.tokens), "ttft_s": req.ttft_s})
        # terminal stream sync: finish_reason is set, so the stream
        # (if open) delivers any tail tokens and closes
        self._sync_stream(req)
        req._done.set()

    def _sync_stream(self, req: Request, stamp: float = 0.0) -> None:
        """Push `req`'s committed tokens to its live stream, if one is
        open. O(1) dict probe when it is not — the cost a non-streaming
        engine pays per commit. Host-side only, never traced. `stamp`:
        the commit's one `time.perf_counter()` reading, shared by every
        lane it syncs (a terminal sync brings no token and none)."""
        n = self.streams.sync(req, stamp)
        if n:
            self.metrics.record_stream_tokens(n)

    # ---- drivers ----------------------------------------------------

    def run_until_idle(self, max_ticks: int = 1_000_000) -> None:
        """Offline driver: tick one ahead, as the serve loop does, until
        queue and pool are empty and nothing is in flight."""
        for _ in range(max_ticks):
            with self._cv:
                if not self._queue and not self._active.any() \
                        and self._inflight is None:
                    return
                self._tick_locked(ahead=True)  # fslint: disable=blocking-under-lock; offline driver, same tick-owns-lock design as step()
        raise RuntimeError(f"engine still busy after {max_ticks} ticks")

    def generate_all(self, prompts,
                     max_new_tokens: Optional[int] = None) -> list:
        """Submit every prompt, drain, return per-prompt token lists."""
        reqs = [self.submit(p, max_new_tokens) for p in prompts]
        self.run_until_idle()
        return [r.tokens for r in reqs]

    def start(self) -> None:
        """Serve in a daemon thread (the API layer's mode): ticks run
        whenever work exists, sleep on the condition var otherwise."""
        if self._thread is not None:
            return
        self._stop_flag = False
        self._thread = threading.Thread(target=self._serve_loop,
                                        daemon=True)
        self._thread.start()

    def _serve_loop(self) -> None:
        wall, cpu = thread_times()
        while not self._stop_flag:
            try:
                n = self._tick(ahead=True)
            except Exception as e:  # noqa: BLE001 — a dead serve
                # thread would leave every waiter blocked for its full
                # timeout and the server accepting traffic against a
                # wedged engine; fail the in-flight work loudly and
                # keep serving (the tick may have died mid-donation,
                # so the pool is rebuilt from scratch)
                self._close_tail()
                self._log({"event": "serving_tick_error",
                           "error": str(e)[:500]})
                with self._cv:
                    # /stats surfaces type + age only — the full text
                    # already went to the log line above, and a
                    # traceback has no place in a polled JSON payload
                    self._last_error = {"type": type(e).__name__,
                                        "at": self._clock()}
                    self._reset_pool_locked()
                if self._recorder is not None:
                    # the reset above finished the in-flight requests,
                    # so their timelines are already in the debug ring
                    # the bundle snapshots; dump failures must not
                    # re-kill the loop the except arm just saved
                    try:
                        self._recorder.snapshot_metrics(
                            (self.metrics.registry,), force=True)
                        self._recorder.dump(
                            reason="engine_tick_error",
                            extra={"error_type": type(e).__name__})
                    except Exception as dump_err:  # noqa: BLE001
                        self._log({"event": "flightrec_dump_error",
                                   "error": str(dump_err)[:200]})
                n = 0
            if self._recorder is not None:
                # periodic ring snapshot (rate-limited inside): the
                # post-mortem bundle carries recent metric trajectories,
                # not just the final values
                self._recorder.snapshot_metrics((self.metrics.registry,))
            if n == 0:
                self._close_tail()
                with self._cv:
                    if not self._queue and not self._stop_flag:
                        with span("serving/idle_wait") as s:
                            self._cv.wait(timeout=0.02)
                        self._declared_wait(s)
            # the iteration's account: wall = cpu + declared wait + what
            # was taken from the thread (docs/serving.md "Threading")
            now, cpu_now = thread_times()
            self.metrics.record_scheduler(now - wall, cpu_now - cpu,
                                          self._waited, self._lock_waited)
            wall, cpu = now, cpu_now
            self._waited = self._lock_waited = 0.0
            self._close_tail()

    def _reset_pool_locked(self) -> None:
        """Fail every queued/running request, drop the tick in flight
        and rebuild the slot pool (donated buffers may be invalid after
        a mid-tick error)."""
        self._inflight = None
        for req in list(self._queue):
            self._queue.remove(req)
            self._finish(req, EXPIRED, "engine_error")
        for i, req in enumerate(self._slot_req):
            if req is not None:
                self._release(i, EXPIRED, "engine_error")
        S, L = self.config.num_slots, self.seq_capacity
        if self.paged:
            self._allocator = BlockAllocator(self.num_blocks)
            self._slot_blocks = [[] for _ in range(S)]
            self._slot_ring = [[] for _ in range(S)]
            if self.ring_blocks:
                self._ring_allocator = BlockAllocator(self.ring_num_blocks)
            self._deferred_req = None
        self._cache = self._init_pool()
        self._history = jnp.zeros((S, L), jnp.int32)
        self._mask = jnp.zeros((S, L), jnp.int32)
        self._keys = jnp.zeros((S,) + self._zero_key.shape,
                               self._zero_key.dtype)
        if self.self_draft:
            self._draft_cache = init_slot_cache(self._draft_model, S)
        self._last_tok = self._zero_tokens()
        self._block_tokens = jnp.full((S, self._block_len), self._mask_id,
                                      jnp.int32)
        self._block_masked = jnp.ones((S, self._block_len), bool)
        self._fwd_left = np.zeros((S,), np.int32)
        self._pos = np.zeros((S,), np.int32)
        self._phys = np.zeros((S,), np.int32)
        self._active = np.zeros((S,), bool)
        self._ticks_left = np.zeros((S,), np.int32)

    def stop(self) -> None:
        """Stop the serve thread. Running lanes stay where they are (a
        later `start()` resumes them); the tick in flight is committed
        first, so their cursors and tokens agree."""
        self._stop_flag = True
        with self._cv:
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        with self._cv:
            self._drain_locked()  # fslint: disable=blocking-under-lock; the serve thread is gone, one last fetch under its lock

    @property
    def block_length(self) -> int:
        """Positions of the generation block a tick forwards a lane; 0
        where a tick yields one token a lane."""
        return self._block_len

    # ---- drain (docs/fleet.md "Drain runbook") ----------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Stop admitting (submit raises `Draining`) and FLUSH the
        queued-but-unstarted requests back to their callers as orderly
        rejections (reason "draining" → 503 at the API layer, so a
        fleet router re-places them NOW instead of letting them wait
        out the drain timeout). Running lanes keep decoding — they are
        the live-evacuation candidates (docs/fault_tolerance.md
        "Preemption runbook"). `/stats` flips `draining` to true so a
        fleet router's poll routes around this replica even before the
        API layer's healthz does."""
        with self._cv:
            if self._draining:
                return
            self._draining = True
            # evacuation reads committed lanes next: nothing in flight
            self._drain_locked()  # fslint: disable=blocking-under-lock; one tick's fetch, as a tick itself holds the lock
            flushed = list(self._queue)
            self._queue.clear()
            for req in flushed:
                # the terminal "rejected" event + ring entry; _done
                # wakes the API thread blocked in request.wait() so the
                # 503 goes out immediately (no engine counter: the
                # rejected_draining count is pinned to SUBMIT refusals)
                self._record_rejection_locked(req, "draining")
                req._done.set()
            self._log({"event": "serving_drain",
                       "queued_flushed": len(flushed),
                       "active": int(self._active.sum())})
            self._cv.notify_all()

    def idle(self) -> bool:
        """True when nothing is queued or decoding (the drain handler's
        exit condition)."""
        with self._cv:
            return not self._queue and not bool(self._active.any()) \
                and self._inflight is None

    def live_lane_ids(self) -> list:
        """Request ids of every RUNNING lane — the drain handler's
        evacuation worklist (disagg.coordinator.evacuate_all)."""
        with self._cv:
            return [r.request_id for r in self._slot_req
                    if r is not None and r.state == RUNNING]

    # ---- commit journal (docs/fault_tolerance.md) -------------------

    def partial(self, request_id: str) -> Optional[dict]:
        """`GET /partial/<id>`: the committed-token journal entry a
        fleet router consults before regenerating a maybe-executed
        retry from token 0. None when the id never ran here or aged
        out of the journal ring. The token list is a SNAPSHOT under
        the engine lock — a live lane keeps committing after it."""
        with self._cv:
            req = self._journal.get(request_id)
            if req is None:
                return None
            out = {"request_id": req.request_id,
                   "state": req.state,
                   "finish_reason": req.finish_reason,
                   "prompt_tokens": int(len(req.prompt)),
                   "generated_tokens": len(req.tokens),
                   "tokens": [int(t) for t in req.tokens],
                   "max_new_tokens": int(req.max_new_tokens),
                   "ttft_s": (None if req.ttft_s is None
                              else round(req.ttft_s, 6)),
                   "trace_id": req.timeline.trace_id}
            if req.evac_target is not None:
                out["evac_target"] = req.evac_target
            if req.resume:
                out["resumed_tokens"] = len(req.resume)
                out["resume_source"] = req.resume_source
            return out

    def attach_stream(self, request_id: str):
        """(Re)open the live token stream of a journaled request — the
        `Last-Event-ID` reconnect path (docs/streaming.md "Reconnect").
        Idempotent: a stream already open is returned as-is; a request
        that already finished yields a stream that replays its tokens
        and closes immediately. None when the id never ran here or
        aged out of the journal ring."""
        with self._cv:
            req = self._journal.get(request_id)
            if req is None:
                return None
            return self.streams.open(req)

    # ---- observability ----------------------------------------------

    def warmup(self) -> float:
        """Compile every prefill bucket + the decode step before traffic
        (the first user must not pay jit). Returns seconds. The assign
        program is left to the first admission."""
        t0 = time.perf_counter()
        with self._cv:
            for bucket in self.ladder.buckets:
                if bucket + 1 > self.seq_capacity:
                    continue
                ids = np.ones((1, bucket), np.int32)
                mask = np.ones((1, bucket), np.int32)
                if self._from_zero:
                    # every prompt of this cache goes by windows: warm
                    # the window program of each width, not the
                    # left-padded one. (Elsewhere a window program
                    # compiles at the first prompt past the ladder.)
                    fresh = self._fresh_jit()  # fslint: disable=blocking-under-lock; warmup must exclude ticks
                    rest = (np.int32(0), np.int32(bucket))
                    if not self._block_len:
                        # the plain window program also takes the
                        # prompt's row (logits controls) and a key
                        rest = (np.zeros((1, self.seq_capacity), np.int32),
                                *rest, self._zero_key)
                    jax.block_until_ready(self._window_jit(  # fslint: disable=blocking-under-lock; warmup must exclude ticks
                        self.params, fresh, ids, *rest))
                    continue
                # warmup compiles under _cv on purpose: no request
                # may tick mid-warmup or it would pay (and double-
                # compile) the very programs being primed
                if self.self_draft:
                    jax.block_until_ready(self._prefill_jit(  # fslint: disable=blocking-under-lock; warmup must exclude ticks
                        self.params, self._draft_params, ids, mask,
                        self._zero_key))
                else:
                    jax.block_until_ready(self._prefill_jit(  # fslint: disable=blocking-under-lock; warmup must exclude ticks
                        self.params, ids, mask, self._zero_key))
            # cache/history/keys (and the draft pool) are donated,
            # so reassign them; with every lane free the warmup
            # tick is a no-op on pool state (free lanes write at
            # index 0 and are fully overwritten by the next
            # assignment anyway) and on the zero key ring
            self._run_decode(self._active)  # fslint: disable=blocking-under-lock; warmup must exclude ticks
            jax.block_until_ready(self._cache)  # fslint: disable=blocking-under-lock; warmup must exclude ticks
        dt = time.perf_counter() - t0
        self.metrics.warmup_compile_s = round(dt, 3)
        record_warmup_seconds("engine", dt)
        entry = {"event": "serving_warmup", "seconds": round(dt, 3),
                 "buckets": list(self.ladder.buckets),
                 "num_slots": self.config.num_slots}
        self._log(entry)
        # every program is traced now: restate the dispatch with the
        # choice each decode/prefill call site actually took
        log_dispatch(self._log)
        return dt

    def _kv_stats_locked(self) -> dict:
        """KV-pool utilization for `/stats` + the `fstpu_kv_*` gauges.
        The slot layout reports lanes as max_len-token blocks so the
        two layouts read on one scale; fragmentation is the unwritten
        fraction of ALLOCATED lane capacity (bucket padding counts as
        written — those positions hold real, masked K/V)."""
        cfg = self.config
        used_tokens = int(self._phys[self._active].sum())
        if self.paged:
            total = self._allocator.total_blocks
            used = self._allocator.used_blocks
            block_tokens = self.block_size
            alloc_tokens = sum(len(b) for b in self._slot_blocks) * \
                block_tokens
        else:
            total = cfg.num_slots
            used = int(self._active.sum())
            block_tokens = self.max_len
            alloc_tokens = used * block_tokens
        frag = round(1.0 - used_tokens / alloc_tokens, 4) \
            if alloc_tokens else 0.0
        # a ring's blocks stand beside, not among, the lane-long kind's:
        # `blocks_*` is the pool that grows with a lane's context
        ring = {"ring_blocks_total": self._ring_allocator.total_blocks,
                "ring_blocks_used": self._ring_allocator.used_blocks,
                "ring_bytes": self._ring_bytes} if self.ring_blocks else {}
        return {
            "layout": cfg.kv_layout, "dtype": cfg.kv_dtype,
            "blocks_total": total, "blocks_used": used,
            "blocks_free": total - used, "block_tokens": block_tokens,
            "bytes": self._kv_bytes, "fragmentation": frag,
            "state_bytes": self._state_bytes, **ring,
        }

    def stats(self) -> dict:
        with self._cv:
            now = self._clock()
            last_error = None
            if self._last_error is not None:
                last_error = {
                    "type": self._last_error["type"],
                    "age_s": round(now - self._last_error["at"], 3)}
            # engine_type EXTENDS the pinned payload (same precedent
            # as uptime_s/draining): the fleet router's heterogeneous
            # placement keys multimodal-vs-text on it
            return dict(self.metrics.snapshot(
                queue_depth=len(self._queue),
                slots_active=int(self._active.sum()),
                num_slots=self.config.num_slots,
                kv=self._kv_stats_locked(),
                # None keeps the non-spec payload byte-identical to
                # the pre-spec /stats shape (pinned by tests)
                spec=({"mode": self.config.spec_mode,
                       "gamma": self.config.spec_gamma}
                      if self.spec else None),
                # same pattern for streams: an engine that never
                # streamed keeps the exact pre-streaming payload shape
                streams=({"active": self.streams.active()}
                         if self.streams.ever_opened else None),
                uptime_s=now - self._t0_clock,
                last_error=last_error,
                draining=self._draining), engine_type=self.engine_type)

    # ---- debug introspection (docs/serving.md "Debug endpoints") ----

    def _request_dict(self, req: Request,
                      phases: Optional[dict] = None) -> dict:
        """Full waterfall payload for one request (live or finished).
        Callers hold self._cv (every mutation site does)."""
        if phases is None:
            phases = req.timeline.phases(self._clock())
        d = {"request_id": req.request_id,
             "state": req.state,
             "finish_reason": req.finish_reason,
             "prompt_tokens": int(len(req.prompt)),
             "generated_tokens": len(req.tokens),
             "max_new_tokens": int(req.max_new_tokens),
             "slot": req.slot,
             "ttft_s": (None if req.ttft_s is None
                        else round(req.ttft_s, 6)),
             "phases": phases}
        d.update(req.timeline.to_dict())
        return d

    @staticmethod
    def _request_summary(d: dict) -> dict:
        """The list-endpoint row: the waterfall minus its event log.
        trace_id rides along so a fleet trace can be followed from the
        list without fetching every full timeline."""
        return {k: d[k] for k in
                ("request_id", "state", "finish_reason",
                 "prompt_tokens", "generated_tokens", "slot",
                 "ttft_s", "phases", "trace_id")}

    def _live_summary_locked(self, req: Request) -> dict:
        """Summary for a LIVE request without materializing its event
        list — debug_requests holds the engine lock, so the scheduler
        must not stall behind event serialization on every scrape."""
        return {"request_id": req.request_id, "state": req.state,
                "finish_reason": req.finish_reason,
                "prompt_tokens": int(len(req.prompt)),
                "generated_tokens": len(req.tokens),
                "slot": req.slot,
                "ttft_s": (None if req.ttft_s is None
                           else round(req.ttft_s, 6)),
                "phases": req.timeline.phases(self._clock()),
                "trace_id": req.timeline.trace_id}

    def _live_requests_locked(self) -> list:
        return list(self._queue) + [r for r in self._slot_req
                                    if r is not None]

    def debug_requests(self) -> dict:
        """`GET /debug/requests`: summaries of every queued + running
        request plus the bounded ring of recently finished (or
        rejected) timelines, newest last."""
        with self._cv:
            in_flight = [self._live_summary_locked(r)
                         for r in self._live_requests_locked()]
            recent = [self._request_summary(d) for d in self._recent]
        return {"in_flight": in_flight, "recent": recent,
                "debug_ring": self.config.debug_ring}

    def debug_request(self, request_id: str) -> Optional[dict]:
        """`GET /debug/requests/<id>`: the full event timeline +
        derived waterfall; None when the id is neither live nor in the
        ring (it aged out or never existed)."""
        with self._cv:
            for req in self._live_requests_locked():
                if req.request_id == request_id:
                    return self._request_dict(req)
            for d in reversed(self._recent):
                if d["request_id"] == request_id:
                    return d
        return None

    def _debug_bundle(self) -> dict:
        """The flight-recorder provider: everything a post-mortem needs
        to answer "what was the engine doing" (docs/observability.md
        "Flight recorder"). Runs on the dumping thread with no engine
        lock held across the whole bundle — stats() and
        debug_requests() each take it briefly."""
        with self._cv:
            requests = [self._request_dict(r)
                        for r in self._live_requests_locked()]
            requests += list(self._recent)
        return {"stats": self.stats(),
                "engine_config": repr(self.config),
                "model_config": repr(self.model.config),
                "requests": requests}
