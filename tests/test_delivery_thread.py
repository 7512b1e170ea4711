"""The server's ONE delivery thread (ISSUE 51, docs/streaming.md
"Delivery"), through the real stdlib server over a tiny engine: every
stream of a server is written by one thread that wakes once a commit;
a client that stops reading holds back nobody else and can come back;
a client that drops frees its handler; the bytes of a stream are what
`format_event` gives; the thread ends with its server."""

import contextlib
import json
import os
import signal
import socket
import struct
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.observability import render_prometheus
from fengshen_tpu.streaming import (Delivery, StreamBook, Subscription,
                                    format_event, iter_sse, token_frame)

MAX_POS = 1024
COUNTERS = {k: f"fstpu_{k}_total" for k in (
    "stream_wakeups", "stream_tokens_delivered", "stream_tokens",
    "serving_decode_ticks", "serving_admitted")}


class _IntTokenizer:
    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


@pytest.fixture(scope="module")
def engine_and_pipe():
    from fengshen_tpu.api.main import start_continuous_engine
    from fengshen_tpu.pipelines.text_generation import Pipeline

    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=MAX_POS, dtype="float32")
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    pipe = Pipeline(module=model, params=params, tokenizer=_IntTokenizer(),
                    max_new_tokens=8, eos_token_id=None, pad_token_id=0)
    engine = start_continuous_engine(
        pipe, {"num_slots": 4, "buckets": (8,), "max_queue": 8,
               "max_new_tokens": MAX_POS - 8})
    try:
        yield engine, pipe
    finally:
        engine.stop()


@contextlib.contextmanager
def _serving(engine, pipe, **server_kw):
    """A server of its own over the shared engine: `(host, port,
    server)`; closed, its delivery thread has ended."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous",
                     **server_kw),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine)
    serving = threading.Thread(target=server.serve_forever, daemon=True)
    serving.start()
    try:
        yield ("127.0.0.1", server.server_address[1], server)
    finally:
        server.shutdown()
        server.server_close()
        serving.join(timeout=10)


@pytest.fixture
def replica(engine_and_pipe):
    with _serving(*engine_and_pipe) as addr:
        yield (*addr, engine_and_pipe[0])


@pytest.fixture
def slow_ticks(engine_and_pipe, monkeypatch):
    """A tick every 10 ms (the sleep wraps `_tick`: inside it, it would
    hold the engine's lock against a submit)."""
    engine = engine_and_pipe[0]
    tick = engine._tick
    monkeypatch.setattr(
        engine, "_tick", lambda ahead: (time.sleep(0.01), tick(ahead))[1])


def _counters(engine) -> dict:
    lines = dict(line.split(" ") for line in render_prometheus(
        engine.metrics.registry).splitlines()
        if line.startswith("fstpu_") and "{" not in line)
    return {k: float(lines[name]) for k, name in COUNTERS.items()}


def _until(what, seconds=20.0):
    deadline = time.monotonic() + seconds
    while not what():
        assert time.monotonic() < deadline, "never came true"
        time.sleep(0.005)


def _delivery_threads() -> list:
    return [t for t in threading.enumerate()
            if t.name == "fstpu-delivery" and t.is_alive()]


def _open(host, port, payload, headers=(), rcvbuf=None):
    """POST the stream route over a raw socket; the socket, unread."""
    sock = socket.socket()
    if rcvbuf is not None:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.settimeout(30)
    sock.connect((host, port))
    body = json.dumps(payload).encode()
    sock.sendall(b"POST /api/text_generation/stream HTTP/1.1\r\nHost: x\r\n"
                 b"Content-Type: application/json\r\n"
                 + b"".join(h.encode() + b"\r\n" for h in headers)
                 + b"Content-Length: " + str(len(body)).encode()
                 + b"\r\n\r\n" + body)
    return sock


def _read_all(sock) -> bytes:
    """The response's body, to the server's close."""
    got = b""
    while True:
        chunk = sock.recv(65536)
        if not chunk:
            break
        got += chunk
    sock.close()
    head, _, body = got.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.0 200"), head
    return body


def _events(body: bytes) -> list:
    return list(iter_sse(body.splitlines(keepends=True)))


def test_token_frame_is_format_events_token_event():
    for index, token in ((0, 0), (7, 96), (4095, 151935)):
        assert token_frame(index, token) == format_event(
            "token", {"token": token}, event_id=index)


def test_a_commit_signals_the_books_readers_once():
    """Inside `one_signal` the per-lane syncs append and wake nobody;
    its end wakes every reader once. A sync outside it wakes them
    itself."""
    class Req:
        def __init__(self, rid):
            self.request_id, self.tokens = rid, []
            self.finish_reason = self.evac_target = None

    book, reqs = StreamBook(), [Req(f"r{i}") for i in range(5)]
    for r in reqs:
        book.open(r)
    calls = []
    notify = book.news.notify_all
    book.news.notify_all = lambda: (calls.append(1), notify())[1]
    with book.one_signal():
        for r in reqs:
            r.tokens.append(3)
            assert book.sync(r, stamp=1.0) == 1
        assert calls == []
    assert calls == [1]
    with book.one_signal():
        pass                            # a commit that reached no stream
    assert calls == [1]
    reqs[0].finish_reason = "cancelled"
    book.sync(reqs[0])                  # outside a commit
    assert calls == [1, 1]
    assert list(book.get("r0").events(0, timeout=1.0)) == [
        ("token", 0, 3), ("done", 1, "cancelled")]


def test_no_token_is_lost_between_a_committer_and_many_sockets():
    """Stress, time-bounded: more streams than cores, a committer that
    signals once a commit, readers that drain their sockets, a switch
    interval of 10 us. Every reader gets every token once, in order,
    then its stream's end; delivered is what was pushed."""
    from fengshen_tpu.serving.metrics import EngineMetrics

    class Req:
        def __init__(self, rid):
            self.request_id, self.tokens = rid, []
            self.finish_reason = self.evac_target = None

    lanes, commits = 32, 300
    book, metrics = StreamBook(), EngineMetrics()
    delivery = Delivery(book, metrics)
    reqs = [Req(f"r{i}") for i in range(lanes)]
    pairs = [socket.socketpair() for _ in reqs]
    subs = [Subscription(book.open(r), 0, ours, 30.0, time.perf_counter())
            for r, (ours, _) in zip(reqs, pairs)]
    got = [b""] * lanes

    def read(i):
        theirs = pairs[i][1]
        theirs.settimeout(30)
        want = len(b"".join(token_frame(k, k) for k in range(commits)))
        while len(got[i]) < want:
            got[i] += theirs.recv(65536)

    def commit():
        for k in range(commits):
            with book.one_signal():
                for r in reqs:
                    r.tokens.append(k)
                    if k == commits - 1:
                        r.finish_reason = "length"
                    book.sync(r, stamp=time.perf_counter())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        delivery.start()
        for sub in subs:
            delivery.subscribe(sub)
        threads = [threading.Thread(target=read, args=(i,), daemon=True)
                   for i in range(lanes)]
        threads.append(threading.Thread(target=commit, daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        for sub in subs:
            assert sub.over.wait(timeout=30)
            assert sub.end == ("done", commits, "length")
    finally:
        sys.setswitchinterval(interval)
        delivery.stop()
        for ours, theirs in pairs:
            ours.close()
            theirs.close()
    assert not delivery.is_alive()
    want = b"".join(token_frame(k, k) for k in range(commits))
    assert all(g == want for g in got)
    assert metrics._stream_delivered.value() == lanes * commits
    # a wake-up carries every stream's news: far fewer than tokens
    assert metrics._stream_wakeups.value() <= lanes * commits / 4


def test_one_thread_serves_every_stream_and_wakes_once_a_commit(
        engine_and_pipe, slow_ticks):
    engine, pipe = engine_and_pipe
    others = len(_delivery_threads())
    with _serving(engine, pipe) as (host, port, server):
        assert len(_delivery_threads()) == others + 1
        before = _counters(engine)
        lanes, new = 4, 24
        socks = [_open(host, port, {"input_text": f"{5 + i} 7 9",
                                    "max_new_tokens": new})
                 for i in range(lanes)]
        bodies = [_read_all(s) for s in socks]
        # still one: a stream started no thread that delivers
        assert len(_delivery_threads()) == others + 1
        _until(lambda: server.in_flight() == 0)
        grew = {k: v - before[k] for k, v in _counters(engine).items()}
    for body in bodies:
        events = _events(body)
        assert [e["event"] for e in events] == ["token"] * new + ["done"]
        assert [e["id"] for e in events[:-1]] == list(range(new))
    # at idle the socket's side is the scheduler's
    assert grew["stream_tokens_delivered"] == lanes * new == \
        grew["stream_tokens"]
    # a wake-up a commit (a tick's, or an admission's first token), not
    # one a lane: four lanes' tokens share most of them
    assert grew["stream_wakeups"] <= \
        grew["serving_decode_ticks"] + grew["serving_admitted"] + 2
    assert grew["stream_tokens_delivered"] / grew["stream_wakeups"] >= 2.0


def test_a_client_that_stops_reading_holds_back_nobody_and_resumes(
        replica, monkeypatch):
    host, port, server, engine = replica
    # the server's side of a connection keeps little: the stalled
    # client's frames back up in its subscription, not in the kernel
    accept = server.get_request

    def get_request():
        conn, addr = accept()
        conn.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 2048)
        return conn, addr
    monkeypatch.setattr(server, "get_request", get_request)
    new = MAX_POS - 16
    stalled = _open(host, port, {"input_text": "3 4 5", "request_id": "slow",
                                 "max_new_tokens": new}, rcvbuf=2048)
    got = b""
    while got.count(b"event: token") < 3:
        got += stalled.recv(256)
    # ... and reads no further. Another stream, meanwhile, runs to its
    # end, and so does the stalled one's request in the engine
    fast = _events(_read_all(_open(
        host, port, {"input_text": "5 7 9", "max_new_tokens": 40})))
    assert [e["event"] for e in fast] == ["token"] * 40 + ["done"]
    _until(lambda: engine.partial("slow")["state"] == "finished", 60)
    c = _counters(engine)
    assert c["stream_tokens_delivered"] < c["stream_tokens"]
    assert server.in_flight() == 1          # its handler, parked
    # it comes back where it stopped: nothing was lost
    seen = [e["id"] for e in _events(got.partition(b"\r\n\r\n")[2])
            if e["event"] == "token"]
    again = _events(_read_all(_open(
        host, port, {"request_id": "slow"},
        headers=(f"Last-Event-ID: {seen[-1]}",))))
    assert [e["id"] for e in again[:-1]] == list(range(seen[-1] + 1, new))
    assert again[-1]["event"] == "done"
    assert again[-1]["data"]["result"].split() == \
        [str(t) for t in engine.partial("slow")["tokens"]]
    # and the stalled connection, closed, frees its handler
    stalled.close()
    _until(lambda: server.in_flight() == 0)


def test_a_client_that_drops_frees_its_handler_and_keeps_its_tokens(
        replica, slow_ticks):
    host, port, server, engine = replica
    before = _counters(engine)
    sock = _open(host, port, {"input_text": "3 4 5", "request_id": "gone",
                              "max_new_tokens": 400})
    got = b""
    while got.count(b"event: token") < 3:
        got += sock.recv(4096)
    # close with a reset: the delivery thread's next send fails
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                    struct.pack("ii", 1, 0))
    sock.close()
    _until(lambda: server.in_flight() == 0)
    assert engine.partial("gone")["state"] == "running"
    sent = _counters(engine)["stream_tokens_delivered"] - \
        before["stream_tokens_delivered"]
    assert got.count(b"event: token") <= sent < 400
    # the request runs on and a reconnect finds every token
    k = len(engine.partial("gone")["tokens"])
    engine.cancel("gone")
    again = _events(_read_all(_open(
        host, port, {"request_id": "gone", "last_event_id": -1})))
    assert [e["id"] for e in again[:-1]][:k] == list(range(k))
    assert again[-1]["data"]["finish_reason"] == "cancelled"


@pytest.mark.parametrize("ending", ["done", "evacuated", "timeout"])
def test_the_bytes_of_a_stream_are_format_events(engine_and_pipe, ending,
                                                 monkeypatch):
    from fengshen_tpu.serving.handoff import detach_lane
    engine, pipe = engine_and_pipe
    rid, new = f"bytes-{ending}", 12
    kw = {}
    if ending != "done":
        # after the first tokens the ticks slow down: the stream stays
        # open and, for a reader that waits half a second, silent
        tick, pause = engine._tick, 1.0 if ending == "timeout" else 0.1

        def throttled(ahead):
            if len((engine.partial(rid) or {}).get("tokens", ())) >= 3:
                time.sleep(pause)
            return tick(ahead)
        monkeypatch.setattr(engine, "_tick", throttled)
        new = 200
    if ending == "timeout":
        kw["request_timeout_s"] = 0.5
    with _serving(engine, pipe, **kw) as (host, port, server):
        sock = _open(host, port, {"input_text": "5 7 9", "request_id": rid,
                                  "max_new_tokens": new})
        if ending == "evacuated":
            _until(lambda: len((engine.partial(rid) or {}).get(
                "tokens", ())) >= 2)
            assert detach_lane(engine, rid, target="http://peer",
                               evacuated=True)
        body = _read_all(sock)
    tokens = engine.partial(rid)["tokens"]
    last = {
        "done": lambda: {"request_id": rid, "finish_reason": "length",
                         "result": pipe.decode(tokens)},
        "evacuated": lambda: {"request_id": rid, "target": "http://peer"},
        "timeout": lambda: {"request_id": rid,
                            "error": "no stream event within 0.5s"},
    }[ending]()
    if ending == "timeout":
        # the request itself is still running: count what was streamed
        tokens = tokens[:body.count(b"event: token")]
        engine.cancel(rid)
    assert len(tokens) >= 1
    assert body == b"".join(
        format_event("token", {"token": t}, event_id=i)
        for i, t in enumerate(tokens)) + format_event(
            ending, last, event_id=len(tokens))


def test_closing_the_server_ends_the_thread_and_every_open_stream(
        engine_and_pipe, slow_ticks):
    engine, pipe = engine_and_pipe
    others = len(_delivery_threads())
    with _serving(engine, pipe) as (host, port, server):
        sock = _open(host, port, {"input_text": "5 7 9",
                                  "request_id": "cut",
                                  "max_new_tokens": 600})
        got = b""
        while got.count(b"event: token") < 3:
            got += sock.recv(4096)
        assert server.in_flight() == 1
    # closed mid-stream: the thread is gone, the handler was let go and
    # the connection ends with no terminal event
    assert len(_delivery_threads()) == others
    _until(lambda: server.in_flight() == 0)
    rest = got + _read_all_raw(sock)
    assert b"event: done" not in rest and b"event: timeout" not in rest
    engine.cancel("cut")


def _read_all_raw(sock) -> bytes:
    got = b""
    with contextlib.suppress(ConnectionResetError):
        while chunk := sock.recv(65536):
            got += chunk
    sock.close()
    return got


def test_a_drain_lets_streams_finish_then_ends_the_thread(
        engine_and_pipe, slow_ticks):
    """SIGTERM through `install_drain_handler`: the open stream runs to
    its `done`, the waiter shuts the server down, and `server_close()`
    (what `main()` calls next) ends the delivery thread."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server,
                                       install_drain_handler)
    engine, pipe = engine_and_pipe
    others = len(_delivery_threads())
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine)
    serving = threading.Thread(target=server.serve_forever, daemon=True)
    serving.start()
    draining = threading.Event()
    # the engine is the module's: the drain stops this server only
    previous = install_drain_handler(server, draining, engine=None,
                                     drain_timeout_s=30.0)
    try:
        sock = _open("127.0.0.1", server.server_address[1],
                     {"input_text": "5 7 9", "max_new_tokens": 60})
        got = b""
        while got.count(b"event: token") < 3:
            got += sock.recv(4096)
        os.kill(os.getpid(), signal.SIGTERM)
        _until(draining.is_set)
        events = _events((got + _read_all_raw(sock)).partition(
            b"\r\n\r\n")[2])
        assert [e["event"] for e in events] == ["token"] * 60 + ["done"]
        serving.join(timeout=30)
        assert not serving.is_alive()
        assert server.in_flight() == 0
    finally:
        signal.signal(signal.SIGTERM, previous)
        server.server_close()
    assert len(_delivery_threads()) == others
