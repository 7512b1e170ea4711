"""REST serving: JSON config → pipeline → `POST /api/<task>`.

Port of reference: fengshen/API/main.py:12-75 + API/utils.py — a config
file names the task/model/server options; the server instantiates the
matching pipeline and exposes `POST /api/<task>`; CORS enabled; served
by one dependency-free `http.server` (`build_stdlib_server`).

    python -m fengshen_tpu.api.main --config text_classification.json

Beyond the reference: `"engine": "continuous"` in the SERVER block
routes generation tasks through the continuous-batching engine
(`fengshen_tpu/serving/`, docs/serving.md) — many concurrent requests
share ONE jitted decode step; the optional ENGINE block holds
`serving.EngineConfig` overrides (num_slots, buckets, max_queue, …,
plus the KV-pool physicals `kv_layout: "slot"|"paged"`,
`kv_dtype: "fp32"|"int8"`, `kv_block_size`, `kv_num_blocks` — the
paged/int8 pool serves ≥2x the concurrent requests per KV byte, see
docs/serving.md "Paged KV cache" — and the speculative-decode knobs
`spec_mode: "off"|"prompt_lookup"`, `spec_gamma`, `spec_ngram` — the
draft/verify tick commits >1 token per weight stream on repetitive
text, docs/serving.md "Speculative decoding"). `GET /stats` includes
the KV-pool utilization (blocks total/used/free, bytes, fragmentation,
layout/dtype) alongside the
engine metrics, plus — on a spec engine only, so the non-spec payload
shape never churns — `spec_mode`/`spec_gamma`/`spec_drafted_total`/
`spec_accepted_total`/`spec_acceptance_rate`.

Both engines get warmed at startup so the first user never pays jit
compilation — warmup runs in a BACKGROUND thread while the server is
already listening, and `GET /healthz` answers 503 until it completes
(load balancers must not route to a still-compiling replica) and 200
after. `GET /stats` exposes the engine metrics as JSON (now incl.
`uptime_s` and `last_error` — type + age, never a traceback) and
`GET /metrics` renders the same registry (plus the process-global one —
HTTP counters, `fstpu_http_request_seconds{route}` latency histograms,
span timings, `fstpu_warmup_seconds{phase}`, `fstpu_build_info`) as
Prometheus text exposition (docs/observability.md).

Debug introspection (docs/serving.md "Debug endpoints"):
`GET /debug/requests` lists in-flight + recently finished
request summaries, `GET /debug/requests/<id>` returns one request's
full lifecycle timeline and latency waterfall (queue wait / prefill /
decode phases), and `POST /debug/dump` writes the flight recorder's
post-mortem bundle on demand (docs/observability.md "Flight
recorder"). `main()` wires a `FlightRecorder` through the engine and
chains it onto SIGTERM, so a drained/killed replica leaves a bundle
behind.

Fleet composition (ISSUE 10, docs/fleet.md): N replicas of this server
compose behind `python -m fengshen_tpu.fleet`. The replica-side
contract lives here — `/healthz` 503 bodies carry `{"ready": false,
"reason": "warmup"|"draining"}` so the router can tell the way IN from
the way OUT; SIGTERM triggers a graceful drain (`install_drain_handler`:
admission stops, in-flight requests finish, then the process exits)
instead of immediate death; and a request body may carry a
`request_id`, which the engine DEDUPES (409 on a live duplicate) so
the router's retry-on-another-replica is idempotent-safe.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import threading
import time
from typing import NamedTuple, Optional


@dataclasses.dataclass
class ServerConfig:
    """Reference: fengshen/API/utils.py config dataclasses, plus the
    serving-engine selection ("simple" = one pipeline call per POST;
    "continuous" = slot-pool continuous batching; "batch_image" /
    "embedding" = micro-batched multimodal engines,
    docs/serving.md "Multimodal engines")."""

    host: str = "0.0.0.0"
    port: int = 8000
    engine: str = "simple"
    warmup: bool = True
    request_timeout_s: float = 120.0
    # prefill/decode disaggregation role (docs/disaggregation.md):
    # "prefill" | "decode" | "both". Surfaced in /stats so the fleet
    # router's phase-aware placement can split the two tiers; "both"
    # keeps the replica in the homogeneous rotation.
    phase: str = "both"
    # SIGTERM drain (docs/fleet.md "Drain runbook"): how long the
    # stdlib server waits for in-flight requests before shutting down
    drain_timeout_s: float = 30.0
    # live-evacuation peers (docs/fault_tolerance.md "Preemption
    # runbook"): base urls of sibling replicas this replica may push
    # its in-flight lanes to when a drain begins; empty = every lane
    # finishes locally (the pre-evacuation drain behavior)
    peers: tuple = ()
    # flight-recorder post-mortem bundles (POST /debug/dump, engine
    # tick errors, SIGTERM) land here (docs/observability.md)
    dump_dir: str = "fstpu_dumps"
    engine_args: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.engine not in ("simple", "continuous", "batch_image",
                               "embedding"):
            # a typo must fail at startup, not silently serve the
            # batch-1 legacy path under a continuous-looking config
            raise ValueError(f"unknown engine {self.engine!r}; expected "
                             "'simple', 'continuous', 'batch_image' or "
                             "'embedding'")
        from fengshen_tpu.disagg.policy import validate_phase
        self.phase = validate_phase(self.phase)
        self.peers = tuple(str(p).rstrip("/")
                           for p in (self.peers or ()) if str(p).strip())


@dataclasses.dataclass
class PipelineConfig:
    task: str = "text_classification"
    model: Optional[str] = None
    pipeline_args: dict = dataclasses.field(default_factory=dict)


def load_config(path: str) -> tuple[ServerConfig, PipelineConfig]:
    with open(path) as f:
        raw = json.load(f)
    server = ServerConfig(**raw.get("SERVER", {}))
    if "AOT" in raw:
        raise ValueError(
            f"{path}: the \"AOT\" block was removed with the AOT "
            "executable cache; the one compile cache is jax's own "
            "persistent cache (fengshen_tpu/compile_cache.py): delete "
            "the block and set JAX_COMPILATION_CACHE_DIR to place it")
    server.engine_args = dict(raw.get("ENGINE", {}))
    pipeline = PipelineConfig(
        task=raw.get("PIPELINE", {}).get("task", "text_classification"),
        model=raw.get("PIPELINE", {}).get("model"),
        pipeline_args={k: v for k, v in raw.get("PIPELINE", {}).items()
                       if k not in ("task", "model")})
    return server, pipeline


class Readiness(threading.Event):
    """The warmup gate `/healthz` reads: set once the replica is warm
    and its serve loop runs. A warmup that raised never sets it — a
    program that did not compile must not turn `/healthz` green — and
    leaves the exception text in `error`, which every 503 body then
    carries. `settled` fires either way, so a caller can wait for the
    outcome without polling."""

    def __init__(self):
        super().__init__()
        self.error: Optional[str] = None
        self.settled = threading.Event()


def _healthz_payload(task: str, ready, draining) -> tuple[int, dict]:
    """The readiness contract `/healthz` answers (pinned by
    tests): 503 with `{"ready": false, "reason": "warmup"|"draining"|
    "warmup_failed"}` while the replica must not receive traffic, 200
    with `{"ready": true}` otherwise. The legacy `status` key stays for
    pre-fleet monitors; the fleet router keys on `reason`."""
    if draining is not None and draining.is_set():
        return 503, {"status": "draining", "task": task,
                     "ready": False, "reason": "draining"}
    if ready is not None and not ready.is_set():
        error = getattr(ready, "error", None)
        if error is not None:
            return 503, {"status": "failed", "task": task,
                         "ready": False, "reason": "warmup_failed",
                         "error": error}
        return 503, {"status": "warming", "task": task,
                     "ready": False, "reason": "warmup"}
    return 200, {"status": "ok", "task": task, "ready": True}


def _render_metrics(engine=None, disagg=None) -> str:
    """Prometheus text over the process-global registry plus (when the
    continuous engine is up) the engine's own registry and the disagg
    coordinator's (`fstpu_disagg_*`); `engine.stats()` runs first so
    the pool gauges are scrape-fresh."""
    from fengshen_tpu.observability import get_registry, render_prometheus
    registries = [get_registry()]
    if engine is not None:
        engine.stats()
        # micro-batch engines count through the global registry and
        # have no engine-local one
        if getattr(engine, "metrics", None) is not None:
            registries.append(engine.metrics.registry)
    if disagg is not None:
        registries.append(disagg.registry)
    return render_prometheus(*registries)


def _count_http(route: str, code: int) -> None:
    """`fstpu_http_requests_total{route,code}` in the global registry.
    Routes are the fixed server surface (bounded label cardinality);
    anything else counts as "other"."""
    from fengshen_tpu.observability.httpmetrics import http_requests_total
    http_requests_total().labels(route, code).inc()


def _observe_http(route: str, seconds: float) -> None:
    """`fstpu_http_request_seconds{route}` beside the counter: the
    request-latency histogram (docs/observability.md)."""
    from fengshen_tpu.observability.httpmetrics import http_request_seconds
    http_request_seconds().labels(route).observe(seconds)


def _classify_route(path: str, api_route: str) -> str:
    if path.startswith("/debug/requests/"):
        # one label for every id — request ids must not become a
        # per-request label cardinality leak
        return "/debug/requests/<id>"
    if path.startswith("/kv/"):
        # KV-handoff endpoints (docs/disaggregation.md), same
        # cardinality rule as the debug routes
        return "/kv/<id>"
    if path.startswith("/partial/"):
        # commit-journal endpoint (docs/fault_tolerance.md "Preemption
        # runbook"), same cardinality rule
        return "/partial/<id>"
    return path if path in (api_route, f"{api_route}/stream",
                            "/healthz", "/stats", "/metrics",
                            "/debug/requests", "/debug/dump") else "other"


def _dump_recorder(recorder, engine, reason: str = "on_demand") -> str:
    """POST /debug/dump: refresh a metrics snapshot into the ring, then
    write the bundle; returns its path."""
    from fengshen_tpu.observability import get_registry
    registries = [get_registry()]
    if engine is not None:
        engine.stats()      # gauges scrape-fresh, like /metrics
        if getattr(engine, "metrics", None) is not None:
            registries.append(engine.metrics.registry)
    recorder.snapshot_metrics(registries, force=True)
    return recorder.dump(reason=reason)


def _partial_payload(engine, pipeline, request_id: str) \
        -> tuple[int, dict]:
    """`GET /partial/<id>`: the commit journal's view of one request —
    the committed-token prefix the fleet router resumes a maybe-executed
    retry from after a replica death (`resume_tokens`,
    docs/fault_tolerance.md "Preemption runbook"). 404 when this
    replica never journaled the id (or runs the simple engine). A
    finished entry additionally carries the decoded `result` so the
    router can answer the client without any resubmit. Micro-batch
    engines have no commit journal — same 404 as the simple path."""
    d = engine.partial(request_id) \
        if engine is not None and hasattr(engine, "partial") else None
    if d is None:
        return 404, {"error": f"unknown request_id {request_id!r}"}
    if d.get("state") == "finished" and pipeline is not None:
        d = dict(d, result=pipeline.decode(d["tokens"]))
    return 200, d


def _debug_requests_payload(engine) -> dict:
    if engine is None or not hasattr(engine, "debug_requests"):
        # the simple path (and the micro-batch engines) have no
        # request-lifecycle ring to introspect; keep the payload shape
        # so dashboards need no engine-type branch
        return {"in_flight": [], "recent": [], "debug_ring": 0}
    return engine.debug_requests()


def _accepts_max_new_tokens(pipeline) -> bool:
    """Only generation pipelines take the per-request cap — forwarding
    it to a classification pipeline would turn a client field into a
    TypeError 500."""
    import inspect
    try:
        return "max_new_tokens" in inspect.signature(pipeline).parameters
    except (TypeError, ValueError):
        return False


def warmup_pipeline(pipeline, task: str) -> float:
    """Issue one warmup request through the legacy path so the first
    user request doesn't pay jit compilation; returns seconds. A
    pipeline that cannot answer its warmup request cannot answer a
    user's either, so the failure propagates."""
    from fengshen_tpu.observability import record_warmup_seconds
    t0 = time.perf_counter()
    pipeline("warmup")
    dt = time.perf_counter() - t0
    record_warmup_seconds("pipeline", dt)
    print(f"[serving] warmup request for '{task}' compiled+ran in "
          f"{dt:.1f}s", flush=True)
    return dt


def create_continuous_engine(pipeline, engine_args: dict, log=None,
                             recorder=None):
    """Build (but do not warm or start) the continuous-batching engine.
    `recorder` is an optional `observability.FlightRecorder` the engine
    feeds its event stream into and dumps through on tick errors."""
    from fengshen_tpu.compile_cache import ensure_compile_cache
    from fengshen_tpu.serving import (ContinuousBatchingEngine,
                                      EngineConfig)
    ensure_compile_cache()
    if not hasattr(pipeline, "engine_config_kwargs"):
        raise ValueError(
            "engine 'continuous' needs a generation pipeline exposing "
            "module/params/engine_config_kwargs (task "
            "'text_generation'), not a per-call classification "
            "pipeline")
    kwargs = {**pipeline.engine_config_kwargs(), **engine_args}
    return ContinuousBatchingEngine(
        pipeline.module, pipeline.params, EngineConfig(**kwargs),
        log=log, recorder=recorder)


def start_continuous_engine(pipeline, engine_args: dict, log=None,
                            recorder=None):
    """Build, warm up (compile all prefill buckets + the decode step,
    logging the time), and start the continuous-batching engine."""
    engine = create_continuous_engine(pipeline, engine_args, log=log,
                                      recorder=recorder)
    dt = engine.warmup()
    print(f"[serving] continuous engine warmup "
          f"(buckets={list(engine.ladder.buckets)}, "
          f"num_slots={engine.config.num_slots}) compiled in {dt:.1f}s",
          flush=True)
    engine.start()
    return engine


def _engine_generate(engine, pipeline, req: dict, timeout_s: float,
                     disagg=None,
                     cpu_start: Optional[float] = None) -> tuple[int, dict]:
    """Submit one HTTP request to the engine; returns (status, body).
    Backpressure maps to HTTP: queue full → 429, prompt too long → 413,
    engine timeout/eviction → 503, draining replica → 503 with reason,
    duplicate request_id → 409 (the fleet router's idempotent-safe
    retry contract, docs/fleet.md). A `traceparent` (body field, or the
    HTTP header lifted into the body by the server layer) flows into
    `engine.submit` so the request's timeline and debug-ring entry
    carry the fleet trace ids (docs/observability.md "Distributed
    tracing"); traced responses echo `trace_id` back.

    When the fleet router tagged the body with a `disagg_push_to`
    target and a `disagg` coordinator is wired, the primed lane is
    handed to that decode replica and the 200 body is a
    `disagg_redirect` marker the router collects from the peer
    (docs/disaggregation.md). A failed handoff falls through to the
    plain local wait below — never a client-visible error.

    `cpu_start`: the handler thread's CPU seconds (`thread_times()`)
    at the POST's entry, this call's own entry where none is given;
    from there to the return of `submit()` is credited to
    `fstpu_serving_handler_admit_cpu_seconds_total`."""
    from fengshen_tpu.observability import parse_traceparent, thread_times
    from fengshen_tpu.serving import (FINISHED, Draining,
                                      DuplicateRequest, PromptTooLong,
                                      QueueFull)
    from fengshen_tpu.serving.handoff import EVACUATED
    if cpu_start is None:
        cpu_start = thread_times()[1]
    rid = req.get("request_id")
    ctx = parse_traceparent(req.get("traceparent"))

    def _body(payload: dict) -> dict:
        # only traced requests grow the trace_id key: the untraced
        # response shape stays byte-identical to the pre-trace one
        if ctx is not None:
            payload["trace_id"] = ctx.trace_id
        return payload

    try:
        request = engine.submit(
            pipeline.encode(req["input_text"]),
            max_new_tokens=req.get("max_new_tokens"),
            request_id=None if rid is None else str(rid),
            trace_id=None if ctx is None else ctx.trace_id,
            parent_span_id=None if ctx is None else ctx.span_id,
            resume_tokens=req.get("resume_tokens"),
            resume_source=req.get("resume_source"))
    except Draining as e:
        return 503, _body({"error": str(e), "reason": "draining"})
    except DuplicateRequest as e:
        return 409, _body({"error": str(e)})
    except QueueFull as e:
        return 429, _body({"error": str(e)})
    except PromptTooLong as e:
        return 413, _body({"error": str(e)})
    except (ValueError, TypeError) as e:
        # bad request payload (unencodable input, max_new_tokens < 1)
        return 422, _body({"error": str(e)})
    finally:
        engine.metrics.record_handler_admit_cpu(
            thread_times()[1] - cpu_start)
    if disagg is not None and req.get("disagg_push_to"):
        redirect = disagg.handoff(request, str(req["disagg_push_to"]))
        if redirect is not None:
            return 200, _body(dict(redirect))
        # fallback: the lane keeps decoding locally; wait as usual
    if not request.wait(timeout=timeout_s):
        engine.cancel(request.request_id)
        # the request may have completed in the wait→cancel window; a
        # finished result must not be discarded as a timeout
        if request.state not in (FINISHED, EVACUATED):
            return 503, _body({"error":
                               f"request timed out after {timeout_s}s"})
    if request.state == EVACUATED:
        # drain-time live evacuation moved the lane to a healthy peer
        # (docs/fault_tolerance.md "Preemption runbook"): answer the
        # blocked POST with the same disagg-redirect marker a phase
        # handoff uses — the router's existing collect path long-polls
        # the adopter and the client sees one ordinary 200
        return 200, _body({"disagg_redirect": True,
                           "request_id": request.request_id,
                           "target": request.evac_target,
                           "evacuated": True})
    if request.state != FINISHED:
        body = {"error": f"request {request.state} "
                         f"({request.finish_reason})"}
        if request.finish_reason == "draining":
            # queued-but-not-slotted at begin_drain: flushed back as an
            # orderly 503 the router re-places immediately instead of
            # waiting out the drain timeout
            body["reason"] = "draining"
        return 503, _body(body)
    return 200, _body({"result": pipeline.decode(request.tokens),
                       "request_id": request.request_id,
                       "ttft_s": request.ttft_s,
                       "finish_reason": request.finish_reason})


#: delivered tokens between two credits of a PULL reader's account
#: (`_engine_stream`'s frames; the server's delivery thread credits a
#: wake-up)
_CREDIT_EVERY = 64


class _Admitted(NamedTuple):
    """A stream request past admission: what delivery starts from."""
    stream: object          # the request's `TokenStream`
    start: int              # index of the first token to deliver
    request_id: str
    arrived: float          # `time.perf_counter()` at the POST's entry
    cpu: float              # the handler's CPU clock once admitted


def _admit_stream(engine, pipeline, req: dict,
                  cpu_start: Optional[float] = None):
    """Admission of `POST /api/<task>/stream` (docs/streaming.md):
    submit, or reattach to, a request. Returns `(code, payload, None)`
    for refusals — the SAME backpressure → HTTP map as
    `_engine_generate`, answered as plain JSON before any stream byte
    is written — or `(200, None, _Admitted)`.

    A body carrying `request_id` + `last_event_id` is the reconnect
    path (`Last-Event-ID`, lifted into the body by the server layer):
    no new submission — the journaled request's stream replays from
    token `last_event_id + 1` and continues live. On `evacuated`, the
    client re-POSTs the same body to the named adopter.

    The calling thread's CPU seconds from `cpu_start` (the POST's
    entry; this call's own where none is given) to the return of
    `submit()` are credited as admission, a refused request's too."""
    from fengshen_tpu.observability import parse_traceparent, thread_times
    from fengshen_tpu.serving import (Draining, DuplicateRequest,
                                      PromptTooLong, QueueFull)
    if engine is None or not hasattr(engine, "attach_stream"):
        return 501, {"error": "streaming requires the continuous "
                              "batching engine"}, None
    t0, cpu_entry = thread_times()
    if cpu_start is None:
        cpu_start = cpu_entry
    rid = req.get("request_id")
    try:
        if rid is not None and req.get("last_event_id") is not None:
            stream = engine.attach_stream(str(rid))
            if stream is None:
                return 404, {"error": f"unknown request_id {rid!r}"}, None
            engine.metrics.record_stream_reconnect()
            start, request_id = int(req["last_event_id"]) + 1, str(rid)
        else:
            ctx = parse_traceparent(req.get("traceparent"))
            request = engine.submit(
                pipeline.encode(req["input_text"]),
                max_new_tokens=req.get("max_new_tokens"),
                request_id=None if rid is None else str(rid),
                trace_id=None if ctx is None else ctx.trace_id,
                parent_span_id=None if ctx is None else ctx.span_id,
                resume_tokens=req.get("resume_tokens"),
                resume_source=req.get("resume_source"),
                seed=req.get("seed"), stream=True)
            stream = engine.streams.get(request.request_id)
            start, request_id = 0, request.request_id
    except Draining as e:
        return 503, {"error": str(e), "reason": "draining"}, None
    except DuplicateRequest as e:
        return 409, {"error": str(e)}, None
    except QueueFull as e:
        return 429, {"error": str(e)}, None
    except PromptTooLong as e:
        return 413, {"error": str(e)}, None
    except (ValueError, TypeError) as e:
        return 422, {"error": str(e)}, None
    finally:
        cpu_admitted = thread_times()[1]
        engine.metrics.record_handler_admit_cpu(cpu_admitted - cpu_start)
    return 200, None, _Admitted(stream, start, request_id, t0,
                                cpu_admitted)


def _terminal_frame(pipeline, adm: _Admitted, kind: str, idx: int,
                    payload, timeout_s: float) -> bytes:
    """The ONE SSE frame that ends a stream, from a reader's terminal
    event (`TokenStream.terminal`, or `timeout`): the pull reader's and
    the parked handler's alike."""
    from fengshen_tpu.streaming import format_event
    data = {"request_id": adm.request_id}
    if kind == "evacuated":
        # the lane moved mid-generation: the terminal event names the
        # adopter; re-POST the same body there with last_event_id to
        # continue gaplessly
        data["target"] = payload
    elif kind == "timeout":
        data["error"] = f"no stream event within {timeout_s}s"
    else:   # done
        data["finish_reason"] = payload
        if payload in ("eos", "length"):
            data["result"] = pipeline.decode(adm.stream.tokens())
    return format_event(kind, data, event_id=idx)


def _engine_stream(engine, pipeline, req: dict, timeout_s: float,
                   cpu_start: Optional[float] = None):
    """`_admit_stream`, then the stream as a PULL reader's iterator:
    `(200, None, frames)` where `frames` yields ready-to-write SSE byte
    chunks: one `token` event per committed token (event id = token
    index), then exactly one terminal `done` / `evacuated` / `timeout`
    event. The server itself delivers through its delivery thread
    (`streaming/delivery.py`); this is the same stream, frame for
    frame, for a caller that writes it itself (the tests, a tool).

    The calling thread keeps the delivery account (docs/streaming.md
    "Observability") in locals: the batches it woke for, the tokens it
    flushed and their lag behind their commit, credited every
    `_CREDIT_EVERY` tokens; its CPU seconds since admission at the
    stream's end. `frames` resumes after each `yield` only once the
    caller has written the frame, which is where a token counts as
    delivered; a caller that stops early closes `frames`, and what was
    sent is credited then."""
    from fengshen_tpu.observability import thread_times
    from fengshen_tpu.streaming import token_frame
    code, body, adm = _admit_stream(engine, pipeline, req, cpu_start)
    if adm is None:
        return code, body, None

    def frames():
        clock, metrics = time.perf_counter, engine.metrics
        wakeups, tokens, lag = 0, 0, 0.0
        first = True
        try:
            for kind, idx, payload in adm.stream.batches(
                    adm.start, timeout=timeout_s):
                if first:
                    # delivery-layer TTFB: received-to-first-byte (the
                    # engine's ttft_seconds keeps its commit-time
                    # meaning)
                    metrics.record_stream_ttfb(clock() - adm.arrived)
                    first = False
                if kind != "tokens":
                    yield _terminal_frame(pipeline, adm, kind, idx,
                                          payload, timeout_s)
                    continue
                batch, stamps = payload
                wakeups += 1
                i = 0
                for tok in batch:
                    yield token_frame(idx + i, tok)
                    # a token committed before this reader came (a
                    # reconnect's replay, a resumed prefix) lags
                    # behind nothing the server did
                    stamp = stamps[i]
                    if stamp >= adm.arrived:
                        lag += clock() - stamp
                    i += 1
                tokens += i
                if tokens >= _CREDIT_EVERY:
                    metrics.record_delivery(0.0, wakeups, tokens, lag)
                    wakeups, tokens, lag = 0, 0, 0.0
        finally:
            metrics.record_delivery(thread_times()[1] - adm.cpu, wakeups,
                                    tokens, lag)

    return 200, None, frames()


def _multimodal_generate(engine, pipeline, req: dict,
                         timeout_s: float) -> tuple[int, dict]:
    """Submit one HTTP request to a micro-batch engine (batch_image /
    embedding); returns (status, body). Same backpressure → HTTP
    mapping as `_engine_generate` — queue full → 429, draining → 503
    with reason, duplicate request_id → 409 — so the fleet router's
    retry contract holds across engine types. The 200 body carries the
    pipeline's result dict (image payload or embedding) plus the
    `engine_type` the router's heterogeneous placement keys on."""
    from fengshen_tpu.serving import Draining, DuplicateRequest, QueueFull
    from fengshen_tpu.serving.multimodal import MM_FINISHED
    rid = req.get("request_id")
    try:
        request = engine.submit(req["input_text"],
                                request_id=None if rid is None
                                else str(rid))
    except Draining as e:
        return 503, {"error": str(e), "reason": "draining"}
    except DuplicateRequest as e:
        return 409, {"error": str(e)}
    except QueueFull as e:
        return 429, {"error": str(e)}
    except (ValueError, TypeError) as e:
        return 422, {"error": str(e)}
    if not request.wait(timeout=timeout_s):
        engine.cancel(request.request_id)
        # the batch may have landed in the wait→cancel window; a
        # finished result must not be discarded as a timeout
        if request.state != MM_FINISHED:
            return 503, {"error":
                         f"request timed out after {timeout_s}s"}
    if request.state != MM_FINISHED:
        return 503, {"error": f"request {request.state} "
                              f"({request.error})"}
    return 200, {"result": request.result,
                 "request_id": request.request_id,
                 "engine_type": engine.engine_type}


def _resolve_pipeline(pipeline_cfg: PipelineConfig):
    module = importlib.import_module(
        f"fengshen_tpu.pipelines.{pipeline_cfg.task}")
    return module.Pipeline(args=None, model=pipeline_cfg.model,
                           **pipeline_cfg.pipeline_args)


def build_stdlib_server(server_cfg: ServerConfig,
                        pipeline_cfg: PipelineConfig, pipeline=None,
                        engine=None, ready=None, recorder=None,
                        draining=None, disagg=None):
    """The server (http.server, no dependency): `POST /api/<task>` with
    `{"input_text": ...}`, `GET /healthz` (503 `{"ready": false,
    "reason": "warmup"}` until the `ready` event is set — None means
    always ready — and 503 with reason "draining" once the `draining`
    event is set, while in-flight requests finish), `GET /stats`,
    `GET /metrics`, the debug introspection routes
    (`GET /debug/requests[/<id>]`, `POST /debug/dump` when a `recorder`
    is wired) and, with a `disagg` coordinator, the KV-handoff surface
    (`PUT/GET/DELETE /kv/<id>`, docs/disaggregation.md). The returned
    server tracks its in-flight generate requests
    (`server.in_flight()`) so the SIGTERM drain handler can wait them
    out (docs/fleet.md). Over an engine that streams it also starts
    the server's one delivery thread, which `server_close()` stops
    (docs/streaming.md "Delivery")."""
    import http.server
    import threading

    from fengshen_tpu.observability import thread_times
    from fengshen_tpu.streaming import Delivery, Subscription

    if pipeline is None:
        pipeline = _resolve_pipeline(pipeline_cfg)
    route = f"/api/{pipeline_cfg.task}"
    # the ONE thread that delivers every stream of this server
    # (docs/streaming.md "Delivery"); none where nothing can stream
    delivery = Delivery(engine.streams, engine.metrics) \
        if hasattr(engine, "attach_stream") else None
    inflight_lock = threading.Lock()
    inflight = [0]

    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):  # quiet
            pass

        def _send_bytes(self, code: int, body: bytes,
                        content_type: str) -> None:
            label = _classify_route(self.path, route)
            _count_http(label, code)
            t0 = getattr(self, "_t_start", None)
            if t0 is not None:
                _observe_http(label, time.perf_counter() - t0)
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Access-Control-Allow-Origin", "*")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _send(self, code: int, payload: dict) -> None:
            self._send_bytes(
                code, json.dumps(payload, ensure_ascii=False).encode(),
                "application/json")

        def _send_stream(self, adm: _Admitted) -> None:
            """SSE response: bypasses `_send_bytes` (no Content-Length
            — the body length is unknown until the stream ends). This
            thread writes the headers, hands the connection to the
            server's delivery thread, which writes the tokens as they
            are committed, and PARKS until the stream is over; woken,
            it writes the terminal event and closes the connection
            (the `Connection: close` EOF is the stream terminator
            HTTP/1.0 clients understand without chunked framing)."""
            label = _classify_route(self.path, route)
            _count_http(label, 200)
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Access-Control-Allow-Origin", "*")
            self.send_header("Connection", "close")
            self.end_headers()
            timeout_s = server_cfg.request_timeout_s
            sub = Subscription(adm.stream, adm.start, self.connection,
                               timeout_s, adm.arrived)
            delivery.subscribe(sub)
            sub.over.wait()
            kind, idx, payload = sub.end
            if kind != "dropped":
                # a client that takes nothing holds this thread no
                # longer than it would have held its subscription
                self.connection.settimeout(timeout_s)
                try:
                    self.wfile.write(_terminal_frame(
                        pipeline, adm, kind, idx, payload, timeout_s))
                except OSError:
                    # the client went away before the last frame; its
                    # tokens stay in the journal + stream buffer for a
                    # Last-Event-ID reconnect — nothing to clean up
                    pass
            # what this thread spent beside the delivery thread's own
            engine.metrics.record_delivery(
                thread_times()[1] - adm.cpu, 0, 0, 0.0)
            t0 = getattr(self, "_t_start", None)
            if t0 is not None:
                _observe_http(label, time.perf_counter() - t0)

        def do_GET(self):
            self._t_start = time.perf_counter()
            if self.path == "/healthz":
                code, body = _healthz_payload(pipeline_cfg.task, ready,
                                              draining)
                self._send(code, body)
            elif self.path == "/stats":
                if engine is not None:
                    # phase EXTENDS the pinned engine payload (same
                    # precedent as uptime_s/draining): the fleet
                    # router's phase-aware placement polls it
                    self._send(200, dict(engine.stats(),
                                         phase=server_cfg.phase))
                else:
                    self._send(200, {"engine": "simple",
                                     "task": pipeline_cfg.task,
                                     "phase": server_cfg.phase})
            elif self.path == "/metrics":
                from fengshen_tpu.observability import \
                    CONTENT_TYPE_LATEST
                self._send_bytes(
                    200, _render_metrics(engine, disagg=disagg).encode(),
                    CONTENT_TYPE_LATEST)
            elif self.path.startswith("/kv/"):
                rid = self.path[len("/kv/"):]
                if disagg is None:
                    self._send(404,
                               {"error": "no disagg coordinator"})
                else:
                    code, body = disagg.handle_get(
                        rid, server_cfg.request_timeout_s)
                    self._send(code, body)
            elif self.path.startswith("/partial/"):
                rid = self.path[len("/partial/"):]
                code, body = _partial_payload(engine, pipeline, rid)
                self._send(code, body)
            elif self.path == "/debug/requests":
                self._send(200, _debug_requests_payload(engine))
            elif self.path.startswith("/debug/requests/"):
                rid = self.path[len("/debug/requests/"):]
                d = engine.debug_request(rid) \
                    if engine is not None and \
                    hasattr(engine, "debug_request") else None
                if d is None:
                    self._send(404, {"error":
                                     f"unknown request_id {rid!r}"})
                else:
                    self._send(200, d)
            else:
                self._send(404, {"error": "not found"})

        def do_POST(self):
            self._t_start, self._cpu_start = thread_times()
            if self.path == "/debug/dump":
                if recorder is None:
                    self._send(404, {"error":
                                     "no flight recorder configured"})
                    return
                try:
                    bundle = _dump_recorder(recorder, engine)
                except Exception as e:  # noqa: BLE001 — an unwritable
                    # dump_dir (the sick-host case) must answer, not
                    # drop the socket
                    self._send(500, {"error": str(e)[:500]})
                    return
                self._send(200, {"bundle": bundle})
                return
            if self.path == f"{route}/stream":
                self._post_stream()
                return
            if self.path != route:
                self._send(404, {"error": "not found"})
                return
            length = int(self.headers.get("Content-Length", 0))
            try:
                req = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError as e:
                self._send(422, {"error": f"invalid json: {e}"})
                return
            if "input_text" not in req:
                # validated BEFORE the pipeline runs: a KeyError inside
                # the pipeline must surface as 500, not as this 422
                self._send(422, {"error": "input_text required"})
                return
            tp = self.headers.get("traceparent")
            if tp and not req.get("traceparent"):
                # lift the header form of the trace context into the
                # body dict _engine_generate reads (body field wins)
                req["traceparent"] = tp
            if draining is not None and draining.is_set():
                # admission edge of the drain: requests already past
                # it (counted in-flight below) finish normally
                self._send(503, {"error": "replica draining",
                                 "reason": "draining"})
                return
            with inflight_lock:
                inflight[0] += 1
            try:
                if engine is not None and \
                        getattr(engine, "engine_type",
                                "continuous") == "continuous":
                    code, body = _engine_generate(
                        engine, pipeline, req,
                        server_cfg.request_timeout_s, disagg=disagg,
                        cpu_start=self._cpu_start)
                    self._send(code, body)
                elif engine is not None:
                    code, body = _multimodal_generate(
                        engine, pipeline, req,
                        server_cfg.request_timeout_s)
                    self._send(code, body)
                elif req.get("max_new_tokens") is not None and \
                        _accepts_max_new_tokens(pipeline):
                    # per-request cap on the legacy path too (only
                    # generation pipelines accept it)
                    self._send(200, {"result": pipeline(
                        req["input_text"],
                        max_new_tokens=req["max_new_tokens"])})
                else:
                    self._send(200,
                               {"result": pipeline(req["input_text"])})
            except Exception as e:  # noqa: BLE001 — surface, don't die
                self._send(500, {"error": str(e)[:500]})
            finally:
                with inflight_lock:
                    inflight[0] -= 1

        def _post_stream(self):
            """`POST /api/<task>/stream` (docs/streaming.md): same
            admission surface as the plain route, SSE delivery."""
            length = int(self.headers.get("Content-Length", 0))
            try:
                req = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError as e:
                self._send(422, {"error": f"invalid json: {e}"})
                return
            tp = self.headers.get("traceparent")
            if tp and not req.get("traceparent"):
                req["traceparent"] = tp
            lei = self.headers.get("Last-Event-ID")
            if lei is not None and req.get("last_event_id") is None:
                # the SSE-standard reconnect header, lifted into the
                # body form _engine_stream reads (body field wins)
                try:
                    req["last_event_id"] = int(lei)
                except ValueError:
                    pass
            reconnect = req.get("request_id") is not None and \
                req.get("last_event_id") is not None
            if not reconnect and "input_text" not in req:
                self._send(422, {"error": "input_text required"})
                return
            if draining is not None and draining.is_set() and \
                    not reconnect:
                # reconnects pass the drain edge: a live lane's reader
                # must still receive its `evacuated` terminal event
                self._send(503, {"error": "replica draining",
                                 "reason": "draining"})
                return
            with inflight_lock:
                inflight[0] += 1
            try:
                code, body, adm = _admit_stream(
                    engine, pipeline, req, cpu_start=self._cpu_start)
                if adm is None:
                    self._send(code, body)
                else:
                    self._send_stream(adm)
            except Exception as e:  # noqa: BLE001 — surface, don't die
                self._send(500, {"error": str(e)[:500]})
            finally:
                with inflight_lock:
                    inflight[0] -= 1

        def do_PUT(self):
            # KV-handoff adopt endpoint (docs/disaggregation.md): a
            # prefill peer pushes an exported lane; the ack tells it
            # whether to detach (200) or decode locally (decline)
            self._t_start = time.perf_counter()
            if not self.path.startswith("/kv/"):
                self._send(404, {"error": "not found"})
                return
            rid = self.path[len("/kv/"):]
            if disagg is None:
                self._send(409, {"adopted": False,
                                 "reason": "no_engine"})
                return
            length = int(self.headers.get("Content-Length", 0))
            try:
                payload = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError as e:
                self._send(422, {"adopted": False,
                                 "reason": "payload_invalid",
                                 "error": f"invalid json: {e}"})
                return
            try:
                code, body = disagg.handle_put(rid, payload)
            except Exception as e:  # noqa: BLE001 — answer, don't die
                code, body = 500, {"adopted": False,
                                   "reason": "internal",
                                   "error": str(e)[:200]}
            self._send(code, body)

        def do_DELETE(self):
            self._t_start = time.perf_counter()
            if not self.path.startswith("/kv/"):
                self._send(404, {"error": "not found"})
                return
            rid = self.path[len("/kv/"):]
            if disagg is None:
                self._send(404, {"error": "no disagg coordinator"})
                return
            code, body = disagg.handle_delete(rid)
            self._send(code, body)

    class Server(http.server.ThreadingHTTPServer):
        def server_close(self):
            super().server_close()
            if delivery is not None:
                delivery.stop()

    server = Server((server_cfg.host, server_cfg.port), Handler)
    server.in_flight = lambda: inflight[0]
    if delivery is not None:
        delivery.start()
    return server


def install_drain_handler(server, draining, engine=None, recorder=None,
                          drain_timeout_s: float = 30.0,
                          poll_s: float = 0.05, disagg=None,
                          peers=()):
    """SIGTERM → graceful replica drain (docs/fleet.md "Drain
    runbook"): set the `draining` event (healthz flips to 503
    `{"reason": "draining"}`; new generates get 503), stop engine
    admission (`begin_drain`), then — on a waiter thread — wait until
    the engine is idle and no HTTP generate is in flight (bounded by
    `drain_timeout_s`), dump the flight recorder, and shut the server
    down so `serve_forever` returns and the process exits 0.

    When a `disagg` coordinator and evacuation `peers` are wired
    (docs/fault_tolerance.md "Preemption runbook"), the waiter first
    EVACUATES every in-flight lane to a healthy peer — the blocked
    POSTs answer with disagg-style redirects the router re-collects —
    so the idle-wait below only covers lanes no peer would adopt
    (which finish locally, never as an error).

    Deliberately REPLACES (does not chain) any prior SIGTERM handler:
    the flight recorder's own handler re-delivers the default
    disposition after dumping — i.e. immediate death — which is
    exactly what a drain must prevent. Its dump still happens, here,
    after the drain. Returns the previous handler (tests restore it)
    or None when not on the main thread."""
    import signal
    import threading
    if threading.current_thread() is not threading.main_thread():
        return None
    previous = signal.getsignal(signal.SIGTERM)

    def handler(signum, frame):
        if draining.is_set():
            return          # second SIGTERM: drain already underway
        draining.set()
        if engine is not None:
            engine.begin_drain()

        def waiter():
            if disagg is not None and peers:
                try:
                    disagg.evacuate_all(list(peers))
                except Exception:  # noqa: BLE001 — evacuation is
                    # best-effort; the idle wait below still finishes
                    # every unevacuated lane locally
                    pass
            deadline = time.monotonic() + drain_timeout_s
            while time.monotonic() < deadline:
                engine_idle = engine is None or engine.idle()
                if engine_idle and server.in_flight() == 0:
                    break
                time.sleep(poll_s)
            if recorder is not None:
                try:
                    recorder.dump(reason="sigterm_drain")
                except Exception:  # noqa: BLE001 — a failed dump must
                    # not leave the server running forever
                    pass
            server.shutdown()

        threading.Thread(target=waiter, daemon=True,
                         name="fstpu-drain").start()

    signal.signal(signal.SIGTERM, handler)
    return previous


def _start_warmup_thread(server_cfg: ServerConfig,
                         pipeline_cfg: PipelineConfig, pipeline,
                         engine) -> Readiness:
    """Warm up in the background while the server is already listening:
    /healthz answers 503 until the returned gate is set, then 200 — the
    load-balancer readiness contract. With a warm compile cache the
    warmup is mostly deserialization and the 503 window shrinks.

    A warmup that raises leaves the gate shut for good: the engine's
    serve loop is not started, /healthz keeps answering 503 with the
    error, and `main()` exits non-zero. Serving on would mean every
    request re-raises the same compile failure one at a time."""
    ready = Readiness()

    def _warm():
        from fengshen_tpu.observability import record_build_info
        record_build_info()
        try:
            if engine is not None and \
                    getattr(engine, "engine_type",
                            "continuous") == "continuous":
                dt = engine.warmup()
                print(f"[serving] continuous engine warmup "
                      f"(buckets={list(engine.ladder.buckets)}, "
                      f"num_slots={engine.config.num_slots}) ready in "
                      f"{dt:.1f}s", flush=True)
            elif engine is not None:
                dt = engine.warmup()
                print(f"[serving] {engine.engine_type} engine warmup "
                      f"(max_batch={engine.max_batch}) ready in "
                      f"{dt:.1f}s", flush=True)
            elif server_cfg.warmup:
                warmup_pipeline(pipeline, pipeline_cfg.task)
            if engine is not None:
                engine.start()
            ready.set()
        except Exception as e:  # noqa: BLE001 — the thread is the
            # boundary: the failure is reported through the gate
            import traceback
            traceback.print_exc()
            ready.error = f"{type(e).__name__}: {str(e)[:500]}"
            print(f"[serving] warmup failed ({ready.error}); /healthz "
                  "stays 503", flush=True)
        finally:
            ready.settled.set()

    threading.Thread(target=_warm, daemon=True,
                     name="fstpu-warmup").start()
    return ready


def _stop_on_failed_warmup(ready: Readiness, stop) -> None:
    """Once the warmup settles, call `stop()` if it failed — what turns
    a compile failure into a process exit instead of a replica that
    answers 503 forever."""
    def _watch():
        ready.settled.wait()
        if ready.error is not None:
            stop()

    threading.Thread(target=_watch, daemon=True,
                     name="fstpu-warmup-watch").start()


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True, type=str)
    args = parser.parse_args(argv)
    server_cfg, pipeline_cfg = load_config(args.config)
    from fengshen_tpu.observability import (FlightRecorder,
                                            record_build_info)
    record_build_info()
    # post-mortem flight recorder (docs/observability.md): engine tick
    # errors and SIGTERM dump the last window of events; POST
    # /debug/dump does so on demand
    recorder = FlightRecorder(dump_dir=server_cfg.dump_dir)
    recorder.install_sigterm()
    pipeline = _resolve_pipeline(pipeline_cfg)
    engine = None
    disagg = None
    if server_cfg.engine == "continuous":
        # warmup (all prefill buckets + the decode step) runs in the
        # background thread below; construction itself is compile-free
        engine = create_continuous_engine(pipeline,
                                          server_cfg.engine_args,
                                          recorder=recorder)
        # every continuous replica can play either side of a KV
        # handoff; the router's phase-aware placement decides which
        from fengshen_tpu.disagg.coordinator import DisaggCoordinator
        disagg = DisaggCoordinator(engine, pipeline)
    elif server_cfg.engine in ("batch_image", "embedding"):
        # micro-batch engines (docs/serving.md "Multimodal engines"):
        # no slot pool, no KV handoff — warmup/start also run in the
        # background thread below
        from fengshen_tpu.serving.multimodal import \
            create_multimodal_engine
        engine = create_multimodal_engine(server_cfg.engine, pipeline,
                                          server_cfg.engine_args)
    ready = _start_warmup_thread(server_cfg, pipeline_cfg, pipeline,
                                 engine)
    import os
    draining = threading.Event()
    # FSTPU_PEERS=http://host:port,... names the sibling replicas this
    # one may evacuate live lanes to on drain (the fleet launcher sets
    # it; docs/fault_tolerance.md "Preemption runbook")
    peers_env = os.environ.get("FSTPU_PEERS")
    if peers_env:
        server_cfg.peers = tuple(
            p.strip().rstrip("/") for p in peers_env.split(",")
            if p.strip())
    server = build_stdlib_server(server_cfg, pipeline_cfg,
                                 pipeline=pipeline, engine=engine,
                                 ready=ready, recorder=recorder,
                                 draining=draining, disagg=disagg)
    # graceful drain replaces the recorder's dump-then-die SIGTERM
    # chain installed above (the dump still happens, post-drain)
    install_drain_handler(server, draining, engine=engine,
                          recorder=recorder,
                          drain_timeout_s=server_cfg.drain_timeout_s,
                          disagg=disagg, peers=server_cfg.peers)
    print(f"stdlib server on {server_cfg.host}:{server_cfg.port}",
          flush=True)
    _stop_on_failed_warmup(ready, server.shutdown)
    server.serve_forever()
    server.server_close()
    if engine is not None:
        engine.stop()
    if ready.error is not None:
        raise SystemExit(f"warmup failed: {ready.error}")


if __name__ == "__main__":
    main()
