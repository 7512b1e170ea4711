"""Engine-level serving metrics: a thin adapter over the observability
registry.

Same event conventions as before (the engine takes an optional `log`
callable and emits `serving_admit`/`serving_reject`/`serving_finish`/
`serving_warmup` dicts; `snapshot()` is the `/stats` payload, its JSON
shape pinned by the serving tests) — but the STORAGE is now
`fengshen_tpu.observability` counters/gauges/histograms, so `/metrics`
renders the same numbers in Prometheus text and the percentile math is
the registry's single implementation (`registry.percentile`), not a
private copy.

Each EngineMetrics owns a fresh `MetricsRegistry` by default (exposed
as `.registry`): concurrent engines in one process — the test suite
builds dozens — never cross-contaminate their `/stats` counts. The api
layer concatenates this registry with the process-global one when
rendering `GET /metrics`.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from fengshen_tpu.observability import MetricsRegistry

#: snapshot field -> (metric name, help) for the plain counters the
#: engine bumps via `count()`
_COUNTERS = {
    "admitted": ("fstpu_serving_admitted_total",
                 "requests accepted into the admission queue"),
    "rejected_queue_full": ("fstpu_serving_rejected_queue_full_total",
                            "submissions rejected with QueueFull (429)"),
    "rejected_prompt_too_long": (
        "fstpu_serving_rejected_prompt_too_long_total",
        "submissions rejected with PromptTooLong (413)"),
    "rejected_draining": (
        "fstpu_serving_rejected_draining_total",
        "submissions refused while the engine drains (503)"),
    "rejected_duplicate": (
        "fstpu_serving_rejected_duplicate_total",
        "submissions rejected for a live duplicate request_id (409)"),
    "completed": ("fstpu_serving_completed_total",
                  "requests finished (eos or length)"),
    "cancelled": ("fstpu_serving_cancelled_total",
                  "requests cancelled by the client"),
    "expired": ("fstpu_serving_expired_total",
                "requests dropped at their deadline or by engine error"),
    "deferred_admissions": (
        "fstpu_serving_deferred_admissions_total",
        "admissions deferred waiting for free KV blocks (paged pool)"),
    "prefill_head_rows": (
        "fstpu_serving_prefill_head_rows_total",
        "rows the LM head projected in prefill programs (over "
        "prefill_padded_tokens_total: one a program where the model "
        "takes `logits_row`, every padded row where it does not)"),
}


class EngineMetrics:
    """Thread-safe serving metrics (storage: observability registry)."""

    def __init__(self, window: int = 512,
                 registry: Optional[MetricsRegistry] = None):
        r = self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._counters = {field: r.counter(name, help)
                          for field, (name, help) in _COUNTERS.items()}
        self._prefills = r.counter(
            "fstpu_serving_prefills_total",
            "prefills per bucket width", labelnames=("bucket",))
        self._decode_ticks = r.counter(
            "fstpu_serving_decode_ticks_total", "jitted decode ticks")
        self._decode_ticks_ahead = r.counter(
            "fstpu_serving_decode_ticks_ahead_total",
            "decode ticks enqueued while the previous tick's tokens "
            "were still unfetched (over decode_ticks_total: the share "
            "of ticks whose host part ran under the device's)")
        self._decode_tokens = r.counter(
            "fstpu_serving_decode_tokens_total",
            "tokens DELIVERED by decode ticks, credited at commit (one a "
            "live lane of a plain tick, the first token being the "
            "prefill's; a block engine's ticks deliver every output "
            "token, the first included, a block at a time)")
        # a block engine only (engine._commit_blocks)
        self._block_forwards = r.counter(
            "fstpu_serving_block_forwards_total",
            "block forwards: live lanes summed over block ticks (over "
            "decode_tokens_total: tokens a forward)")
        self._block_commit_forwards = r.counter(
            "fstpu_serving_block_commit_forwards_total",
            "of the block forwards, the commit forwards: a finished "
            "block's K/V written, no position revealed")
        self._prefill_tokens = r.counter(
            "fstpu_serving_prefill_tokens_total",
            "real prompt tokens prefilled (a resumed request's "
            "committed prefix in)")
        self._prefill_padded = r.counter(
            "fstpu_serving_prefill_padded_tokens_total",
            "bucket widths prefilled: what the device paid for")
        self._prefill_windows = r.counter(
            "fstpu_serving_prefill_windows_total",
            "windows prefilled onto a cache the windows before them "
            "filled (a prompt past the largest bucket, or any prompt "
            "of a cache defined on token positions)")
        self._sparse_attended = r.counter(
            "fstpu_sparse_tokens_attended_total",
            "per-tick sum over live lanes of the tokens a sparse "
            "layer's query reads (the chosen blocks; everything while "
            "the context is dense)")
        self._sparse_cached = r.counter(
            "fstpu_sparse_tokens_cached_total",
            "per-tick sum over live lanes of the tokens cached when a "
            "sparse layer's query chose (cursor + 1)")
        self._index_selected = r.counter(
            "fstpu_index_tokens_selected_total",
            "per-tick sum over live lanes of the tokens a learned "
            "indexer leaves a layer's query to read (its topk; "
            "everything while the context is within it)")
        self._index_scored = r.counter(
            "fstpu_index_tokens_scored_total",
            "per-tick sum over live lanes of the cached tokens a "
            "layer's indexer scored to choose them (cursor + 1)")
        self._window_attended = r.counter(
            "fstpu_serving_kv_window_tokens_attended_total",
            "per-tick sum over live lanes of the keys a WINDOW layer's "
            "query reads (min(cursor + 1, window); a layer's count): "
            "beside kv_tokens_attended, which a full layer reads")
        self._kv_blocks_held = r.counter(
            "fstpu_serving_kv_blocks_held_total",
            "per-tick sum over lanes of the lane-long table's blocks "
            "they hold (a model with window layers only)")
        self._kv_ring_blocks_held = r.counter(
            "fstpu_serving_kv_ring_blocks_held_total",
            "per-tick sum over lanes of the ring blocks they hold")
        self._kv_attended = r.counter(
            "fstpu_serving_kv_tokens_attended_total",
            "per-tick sum over active lanes of the real cached tokens "
            "the tick's attention reads (bucket padding out)")
        self._kv_blocks_live = r.counter(
            "fstpu_serving_kv_blocks_live_total",
            "per-tick sum over ALL lanes of the table-row blocks up to "
            "the lane's physical cursor (bucket padding in; a released "
            "lane counts its one null block): what the paged decode "
            "kernel walks")
        self._kv_blocks_tabled = r.counter(
            "fstpu_serving_kv_blocks_tabled_total",
            "per-tick lanes x table width: every block a table row "
            "names (live over tabled: the share of the table the "
            "kernel walks); paged layout only")
        # routed-expert models only (engine._commit_plain): what a
        # tick's live lanes asked of each expert layer
        self._moe_assignments = r.counter(
            "fstpu_moe_assignments_total",
            "token-to-expert assignments of live lanes, over the "
            "expert layers of every plain decode tick")
        self._moe_held = r.counter(
            "fstpu_moe_assignments_held_total",
            "those of the assignments that landed on an expert held "
            "here (all of them where the chip holds every expert)")
        self._moe_touched = r.counter(
            "fstpu_moe_experts_touched_total",
            "experts held here with at least one assignment, summed "
            "over layer-ticks")
        self._moe_layer_ticks = r.counter(
            "fstpu_moe_layer_ticks_total",
            "expert layers run by plain decode ticks")
        self._moe_max_tokens = r.counter(
            "fstpu_moe_max_expert_tokens_total",
            "assignments of the busiest expert held here, summed over "
            "layer-ticks (the straggler)")
        # the scheduler thread's own time (docs/serving.md "Threading"):
        # wall = cpu + wait + what was taken from it
        self._sched_wall = r.counter(
            "fstpu_serving_scheduler_wall_seconds_total",
            "wall seconds of serve-loop iterations")
        self._sched_cpu = r.counter(
            "fstpu_serving_scheduler_cpu_seconds_total",
            "the scheduler thread's CPU seconds over the same "
            "iterations")
        self._sched_wait = r.counter(
            "fstpu_serving_scheduler_wait_seconds_total",
            "off-CPU seconds (wall - CPU) inside the spans that wait "
            "by design: serving/lock_wait, serving/idle_wait, "
            "serving/decode/fetch, serving/prefill")
        self._sched_lock_wait = r.counter(
            "fstpu_serving_lock_wait_seconds_total",
            "wall seconds of the scheduler's serving/lock_wait spans")
        self._dispatch = (
            r.counter("fstpu_serving_dispatch_seconds_total",
                      "wall seconds of serving/decode/dispatch"),
            r.counter("fstpu_serving_dispatch_cpu_seconds_total",
                      "the thread's CPU seconds inside them"))
        self._commit = (
            r.counter("fstpu_serving_commit_seconds_total",
                      "wall seconds of serving/commit"),
            r.counter("fstpu_serving_commit_cpu_seconds_total",
                      "the thread's CPU seconds inside them"))
        self._submit_lock_wait = r.histogram(
            "fstpu_serving_submit_lock_wait_seconds",
            "submit()'s wait for the scheduler's lock", window=window)
        self._occupied_ticks = r.counter(
            "fstpu_serving_occupied_slot_ticks_total",
            "per-tick sum of active lanes")
        self._total_ticks = r.counter(
            "fstpu_serving_slot_ticks_total",
            "per-tick sum of pool lanes")
        self._ttft = r.histogram(
            "fstpu_serving_ttft_seconds", "submit-to-first-token",
            window=window)
        self._phase = r.histogram(
            "fstpu_request_phase_seconds",
            "per-request lifecycle phase wall seconds "
            "(queue_wait / prefill / decode / decode_stall)",
            labelnames=("phase",), window=window)
        self._latency = r.histogram(
            "fstpu_serving_request_seconds",
            "submit-to-finish (any reason)", window=window)
        self._g_queue = r.gauge(
            "fstpu_serving_queue_depth", "admission queue depth")
        self._g_active = r.gauge(
            "fstpu_serving_slots_active", "lanes decoding right now")
        self._g_slots = r.gauge(
            "fstpu_serving_num_slots", "pool size")
        self._g_warmup = r.gauge(
            "fstpu_serving_warmup_compile_seconds",
            "engine warmup compile wall seconds")
        self._g_peak = r.gauge(
            "fstpu_serving_slots_active_peak",
            "max lanes decoding in any one tick (engine lifetime)")
        self._g_kv_total = r.gauge(
            "fstpu_kv_blocks_total", "allocatable KV-pool blocks")
        self._g_kv_used = r.gauge(
            "fstpu_kv_blocks_used", "KV-pool blocks held by live lanes")
        self._g_kv_bytes = r.gauge(
            "fstpu_kv_cache_bytes", "device bytes of the KV pool")
        self._g_ring_total = r.gauge(
            "fstpu_kv_ring_blocks_total",
            "allocatable blocks of the window layers' ring pool")
        self._g_ring_used = r.gauge(
            "fstpu_kv_ring_blocks_used",
            "ring-pool blocks held by live lanes")
        self._g_state_bytes = r.gauge(
            "fstpu_serving_state_bytes",
            "device bytes the pool holds beside the rows attention "
            "reads: per-lane state (a recurrent layer's) and the rows "
            "a selection scores (a learned indexer's keys)")
        self._g_kv_frag = r.gauge(
            "fstpu_kv_fragmentation",
            "unwritten fraction of allocated KV lane capacity")
        self._spec_drafted = r.counter(
            "fstpu_serving_spec_drafted_total",
            "tokens proposed by the speculative drafter")
        self._spec_accepted = r.counter(
            "fstpu_serving_spec_accepted_total",
            "drafted tokens accepted by the verify forward")
        self._g_spec_ratio = r.gauge(
            "fstpu_spec_accepted_ratio",
            "accepted / drafted fraction of speculative proposals")
        self._stream_tokens = r.counter(
            "fstpu_stream_tokens_total",
            "tokens pushed to live SSE streams at commit time")
        self._stream_reconnects = r.counter(
            "fstpu_stream_reconnects_total",
            "Last-Event-ID stream reattachments served")
        self._stream_ttfb = r.histogram(
            "fstpu_stream_ttfb_seconds",
            "request-received to first SSE byte written",
            window=window)
        self._g_streams = r.gauge(
            "fstpu_streams_active", "open (unclosed) SSE token streams")
        # the handler and delivery threads' account (docs/serving.md
        # "Threading"):
        # sums over many streams mean something, one stream's reading
        # does not (the CPU clock steps by 10 ms on some hosts)
        self._handler_admit_cpu = r.counter(
            "fstpu_serving_handler_admit_cpu_seconds_total",
            "handler threads' CPU seconds from a POST's entry to the "
            "return of submit(): body read, JSON, encode, the submit "
            "itself (a refused request's too)")
        self._handler_stream_cpu = r.counter(
            "fstpu_serving_handler_stream_cpu_seconds_total",
            "the delivery thread's CPU seconds (wake-ups, framing, "
            "socket writes) plus the parked handlers' from submit() to "
            "their stream's terminal frame")
        self._stream_wakeups = r.counter(
            "fstpu_stream_wakeups_total",
            "returns of the delivery thread (or a pull reader) from "
            "its wait that delivered at least one token")
        self._stream_delivered = r.counter(
            "fstpu_stream_tokens_delivered_total",
            "tokens whose SSE frame's send() returned: the socket's "
            "side of stream_tokens_total (over stream_wakeups_total: "
            "tokens a wake-up)")
        self._stream_lag = r.counter(
            "fstpu_stream_delivery_lag_seconds_total",
            "summed over delivered tokens, send() returned less the "
            "commit that brought the token (over "
            "stream_tokens_delivered_total: the mean lag; a replayed "
            "token adds none)")
        self._process_cpu = r.counter(
            "fstpu_serving_process_cpu_seconds_total",
            "the whole process's CPU seconds (time.process_time()) "
            "since the engine's metrics were made, brought up to date "
            "at every read of the registry: the scheduler's, the "
            "handlers' and the rest (the runtime's threads; clients "
            "that share the process)")
        self._process_cpu_at = time.process_time()
        self._process_cpu_lock = threading.Lock()
        r.collectors.append(self._collect_process_cpu)
        self._peak_active = 0
        self._warmup_compile_s: Optional[float] = None

    # -- engine-facing mutators (names unchanged from PR 3) -----------
    def count(self, field: str, n: int = 1) -> None:
        self._counters[field].inc(n)

    def record_prefill(self, bucket: int, prompt_tokens: int) -> None:
        self._prefills.labels(bucket).inc()
        self._prefill_tokens.inc(prompt_tokens)
        self._prefill_padded.inc(bucket)

    def record_prefill_windows(self, width: int, windows: int,
                               prompt_tokens: int) -> None:
        """A prompt prefilled as `windows` windows of `width`."""
        self._prefills.labels(width).inc(windows)
        self._prefill_windows.inc(windows)
        self._prefill_tokens.inc(prompt_tokens)
        self._prefill_padded.inc(width * windows)

    def record_sparse(self, attended: int, cached: int) -> None:
        self._sparse_attended.inc(attended)
        self._sparse_cached.inc(cached)

    def record_window(self, attended: int, blocks: int,
                      ring_blocks: int) -> None:
        """One enqueued tick of a model with window layers: keys such
        a layer reads over the live lanes; blocks of each kind held."""
        self._window_attended.inc(attended)
        self._kv_blocks_held.inc(blocks)
        self._kv_ring_blocks_held.inc(ring_blocks)

    def record_index(self, selected: int, scored: int) -> None:
        self._index_selected.inc(selected)
        self._index_scored.inc(scored)

    def record_submit_lock_wait(self, seconds: float) -> None:
        self._submit_lock_wait.observe(seconds)

    def record_scheduler(self, wall: float, cpu: float, wait: float,
                         lock_wait: float) -> None:
        """One serve-loop iteration of the scheduler thread. What is
        left of `wall` after `cpu` and `wait` was taken from the
        thread: the GIL, preemption, or a call that blocks where no
        wait was declared."""
        self._sched_wall.inc(wall)
        self._sched_cpu.inc(cpu)
        self._sched_wait.inc(wait)
        self._sched_lock_wait.inc(lock_wait)

    def record_dispatch(self, s) -> None:
        """A closed `serving/decode/dispatch` span."""
        self._dispatch[0].inc(s.seconds)
        self._dispatch[1].inc(s.cpu_seconds)

    def record_commit(self, s) -> None:
        """A closed `serving/commit` span."""
        self._commit[0].inc(s.seconds)
        self._commit[1].inc(s.cpu_seconds)

    def record_tick(self, n_active: int, num_slots: int,
                    tokens: Optional[int] = None,
                    kv_tokens: int = 0, kv_blocks: tuple = (0, 0),
                    ahead: bool = False) -> None:
        """`tokens` defaults to `n_active` (one committed token per
        active lane — the plain decode tick); the speculative tick
        passes the real committed count, which exceeds the lane count
        whenever draft proposals were accepted. `kv_tokens`: the real
        cached tokens this tick's attention read, over all lanes;
        `kv_blocks`: (live, tabled) blocks of the table rows it was
        handed.
        `ahead`: the tick was enqueued one ahead (engine `_Tick`)."""
        self._decode_ticks.inc()
        if ahead:
            self._decode_ticks_ahead.inc()
        self._kv_attended.inc(kv_tokens)
        self._kv_blocks_live.inc(kv_blocks[0])
        self._kv_blocks_tabled.inc(kv_blocks[1])
        self._decode_tokens.inc(n_active if tokens is None else tokens)
        self._occupied_ticks.inc(n_active)
        self._total_ticks.inc(num_slots)
        if n_active > self._peak_active:
            self._peak_active = n_active
            self._g_peak.set(n_active)

    def record_block_forwards(self, forwards: int, commits: int) -> None:
        self._block_forwards.inc(forwards)
        self._block_commit_forwards.inc(commits)

    def record_moe(self, histogram, held=None) -> None:
        """`histogram`: `[expert layers, experts]` assignments of one
        tick's live lanes over ALL the router's experts (numpy, already
        on the host); `held`: (first, count) of the experts this chip
        computes, or None for all."""
        self._moe_assignments.inc(int(histogram.sum()))
        if held is not None:
            histogram = histogram[:, held[0]:held[0] + held[1]]
        self._moe_held.inc(int(histogram.sum()))
        self._moe_touched.inc(int((histogram > 0).sum()))
        self._moe_layer_ticks.inc(histogram.shape[0])
        self._moe_max_tokens.inc(int(histogram.max(axis=1).sum()))

    def record_spec(self, drafted: int, accepted: int) -> None:
        """One speculative verify's draft/accept tallies; keeps the
        `fstpu_spec_accepted_ratio` gauge scrape-fresh."""
        self._spec_drafted.inc(drafted)
        self._spec_accepted.inc(accepted)
        total = self._spec_drafted.value()
        if total > 0:
            self._g_spec_ratio.set(
                round(self._spec_accepted.value() / total, 4))

    def record_ttft(self, seconds: float) -> None:
        self._ttft.observe(seconds)

    # -- streaming (docs/streaming.md "Observability") ----------------
    def record_stream_tokens(self, n: int) -> None:
        """Tokens newly pushed to a live stream (the scheduler's sync
        path calls this per commit; 0-token syncs never reach here)."""
        if n > 0:
            self._stream_tokens.inc(n)

    def record_stream_reconnect(self) -> None:
        self._stream_reconnects.inc()

    def record_stream_ttfb(self, seconds: float) -> None:
        """Request-received to first SSE byte on the wire — the
        delivery-layer TTFT (ttft_seconds keeps its commit-time
        meaning)."""
        self._stream_ttfb.observe(seconds)

    def record_handler_admit_cpu(self, seconds: float) -> None:
        self._handler_admit_cpu.inc(seconds)

    def record_delivery(self, cpu: float, wakeups: int, tokens: int,
                        lag: float) -> None:
        """What a delivering thread did since its last credit (the
        server's delivery thread once a wake-up, a parked handler at
        its stream's end, a pull reader every 64 tokens): the wake-ups
        that delivered, the tokens the socket took, their summed
        delivery lag, and its CPU seconds since it last read that
        clock (every 512 tokens and at the end; 0.0 in between)."""
        self._handler_stream_cpu.inc(cpu)
        self._stream_wakeups.inc(wakeups)
        self._stream_delivered.inc(tokens)
        self._stream_lag.inc(lag)

    def _collect_process_cpu(self) -> None:
        """The registry's collector: `time.process_time()` sums over
        every thread of the process (microseconds with hundreds of
        them), so it is read when the registry is, never by the
        scheduler or a handler."""
        with self._process_cpu_lock:
            total = time.process_time()
            self._process_cpu.inc(total - self._process_cpu_at)
            self._process_cpu_at = total

    def record_phases(self, phases: dict) -> None:
        """One finished request's derived waterfall (timeline.phases())
        into `fstpu_request_phase_seconds{phase}` — the per-phase
        latency attribution the ROADMAP item-2 router will drill into
        per replica (docs/observability.md "Request tracing")."""
        for key in ("queue_wait_s", "prefill_s", "decode_s",
                    "decode_stall_s"):
            if key in phases:
                self._phase.labels(key[:-2]).observe(phases[key])

    def record_latency(self, seconds: float) -> None:
        self._latency.observe(seconds)

    @property
    def warmup_compile_s(self) -> Optional[float]:
        return self._warmup_compile_s

    @warmup_compile_s.setter
    def warmup_compile_s(self, seconds: Optional[float]) -> None:
        self._warmup_compile_s = seconds
        if seconds is not None:
            self._g_warmup.set(seconds)

    # -- reads --------------------------------------------------------
    def _int(self, field: str) -> int:
        return int(self._counters[field].value())

    def snapshot(self, queue_depth: int, slots_active: int,
                 num_slots: int, kv: Optional[dict] = None,
                 spec: Optional[dict] = None, uptime_s: float = 0.0,
                 last_error: Optional[dict] = None,
                 draining: bool = False,
                 streams: Optional[dict] = None) -> dict:
        """The `/stats` payload (shape pinned by the serving and
        observability tests); also refreshes the pool gauges so a
        `/metrics` scrape right after reads current depth/occupancy.
        `kv` is the engine's KV-pool utilization dict (layout, dtype,
        blocks total/used/free, block_tokens, bytes, fragmentation) —
        defaults describe an empty fp32 slot pool so a bare
        EngineMetrics still snapshots. `spec` ({"mode", "gamma"}) adds
        the speculative-decode section; None (a non-spec engine) keeps
        the payload BYTE-IDENTICAL to the pre-spec shape — dashboards
        pinned on it must not churn. `uptime_s` and `last_error`
        (ISSUE 8) only EXTEND the payload: `last_error` is None or a
        {"type", "age_s"} pair — the exception class name and how long
        ago the serve loop hit it, never a traceback payload.
        `draining` + the `rejected_draining` counter (ISSUE 10) extend
        it again: the fleet router polls `/stats` and must see a
        replica's orderly drain before placing work on it. `streams`
        ({"active": n}, ISSUE 20) follows the exact same pattern:
        None (an engine that never streamed) keeps the payload
        byte-identical to the pre-streaming shape."""
        if kv is None:
            kv = {"layout": "slot", "dtype": "fp32", "blocks_total": 0,
                  "blocks_used": 0, "blocks_free": 0, "block_tokens": 0,
                  "bytes": 0, "fragmentation": 0.0}
        self._g_queue.set(queue_depth)
        self._g_active.set(slots_active)
        self._g_slots.set(num_slots)
        self._g_kv_total.set(kv["blocks_total"])
        self._g_kv_used.set(kv["blocks_used"])
        self._g_kv_bytes.set(kv["bytes"])
        self._g_ring_total.set(kv.get("ring_blocks_total", 0))
        self._g_ring_used.set(kv.get("ring_blocks_used", 0))
        self._g_state_bytes.set(kv.get("state_bytes", 0))
        self._g_kv_frag.set(kv["fragmentation"])
        ttft = self._ttft.window_values()
        decode_tokens = int(self._decode_tokens.value())
        total_ticks = int(self._total_ticks.value())
        occupancy = (int(self._occupied_ticks.value()) / total_ticks
                     if total_ticks > 0 else 0.0)
        out = {
            "queue_depth": queue_depth,
            "slots_active": slots_active,
            "num_slots": num_slots,
            "admitted": self._int("admitted"),
            "rejected_queue_full": self._int("rejected_queue_full"),
            "rejected_prompt_too_long":
                self._int("rejected_prompt_too_long"),
            "rejected_draining": self._int("rejected_draining"),
            "rejected_duplicate": self._int("rejected_duplicate"),
            "completed": self._int("completed"),
            "cancelled": self._int("cancelled"),
            "expired": self._int("expired"),
            "deferred_admissions": self._int("deferred_admissions"),
            "slots_active_peak": self._peak_active,
            "kv_layout": kv["layout"],
            "kv_dtype": kv["dtype"],
            "kv_blocks_total": kv["blocks_total"],
            "kv_blocks_used": kv["blocks_used"],
            "kv_blocks_free": kv["blocks_free"],
            "kv_block_tokens": kv["block_tokens"],
            "kv_cache_bytes": kv["bytes"],
            "kv_fragmentation": kv["fragmentation"],
            "prefills_per_bucket": {
                int(values[0]): int(child.value)
                for values, child in self._prefills.children()},
            "decode_ticks": int(self._decode_ticks.value()),
            "decode_tokens": decode_tokens,
            "slot_occupancy": round(occupancy, 4),
            "ttft_avg_s": round(sum(ttft) / len(ttft), 4) if ttft
                          else 0.0,
            "ttft_p50_s": round(self._ttft.percentile(0.5), 4),
            "ttft_p95_s": round(self._ttft.percentile(0.95), 4),
            "warmup_compile_s": self._warmup_compile_s,
            "uptime_s": round(float(uptime_s), 3),
            "last_error": last_error,
            "draining": bool(draining),
        }
        if spec is not None:
            drafted = int(self._spec_drafted.value())
            accepted = int(self._spec_accepted.value())
            rate = round(accepted / drafted, 4) if drafted else 0.0
            self._g_spec_ratio.set(rate)
            out.update({
                "spec_mode": spec["mode"],
                "spec_gamma": spec["gamma"],
                "spec_drafted_total": drafted,
                "spec_accepted_total": accepted,
                "spec_acceptance_rate": rate,
            })
        if kv.get("state_bytes"):
            # a pool without per-lane state keeps the payload as it was
            out["state_bytes"] = int(kv["state_bytes"])
        if streams is not None:
            self._g_streams.set(streams["active"])
            out["streams_active"] = int(streams["active"])
        return out
