"""The delivery account (ISSUE 50; the delivery thread's since ISSUE
51; docs/streaming.md "Observability"): a stream through the real
stdlib server and a tiny engine closes it. Delivered tokens are the
socket's side of `fstpu_stream_tokens_total`, a wake-up delivers at
least a token, the lag is summed from the commit's one stamp, the
handlers' CPU is split at the return of `submit()`, and the process's
whole CPU stands beside the scheduler's. All unlabelled counters on the ENGINE's registry,
which is what the benchmark reads at a window's edges."""

import json
import math
import socket
import struct
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.observability import render_prometheus
from fengshen_tpu.streaming import TokenStream, iter_sse

N = 6
FAMILIES = {k: f"fstpu_{k}_total" for k in (
    "serving_handler_admit_cpu_seconds", "serving_handler_stream_cpu_seconds",
    "stream_wakeups", "stream_tokens_delivered",
    "stream_delivery_lag_seconds", "serving_process_cpu_seconds",
    "stream_tokens", "stream_reconnects", "serving_scheduler_cpu_seconds")}


class _IntTokenizer:
    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


@pytest.fixture(scope="module")
def replica():
    """(base url, server, engine) of one api server over a started
    continuous engine on a two-layer model."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server,
                                       start_continuous_engine)
    from fengshen_tpu.pipelines.text_generation import Pipeline

    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, dtype="float32")
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    pipe = Pipeline(module=model, params=params, tokenizer=_IntTokenizer(),
                    max_new_tokens=N, eos_token_id=None, pad_token_id=0)
    engine = start_continuous_engine(
        pipe, {"num_slots": 2, "buckets": (8,), "max_queue": 8})
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        yield (f"http://127.0.0.1:{server.server_address[1]}", server,
               engine)
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()


def _account(engine) -> dict:
    """As the benchmark reads it: the unlabelled lines of the ENGINE's
    registry as `/metrics` renders them, with no call into the engine."""
    lines = dict(line.split(" ") for line in render_prometheus(
        engine.metrics.registry).splitlines()
        if line.startswith("fstpu_") and "{" not in line)
    return {k: float(lines[name]) for k, name in FAMILIES.items()}


def _settled(server, engine) -> dict:
    """The account once no handler is inside a request: a stream's last
    credit comes AFTER its client has read the terminal event."""
    deadline = time.monotonic() + 10
    while server.in_flight():
        assert time.monotonic() < deadline, "a handler never returned"
        time.sleep(0.005)
    return _account(engine)


def _stream(base, payload, headers=None):
    req = urllib.request.Request(
        f"{base}/api/text_generation/stream",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=60) as r:
        return list(iter_sse(r))


def test_a_stream_through_the_real_server_closes_the_account(replica):
    base, server, engine = replica
    before = _settled(server, engine)
    events = _stream(base, {"input_text": "5 7 9", "request_id": "acct-1"})
    assert [e["event"] for e in events] == ["token"] * N + ["done"]
    after = _settled(server, engine)
    grew = {k: after[k] - before[k] for k in FAMILIES}
    # the socket's side of the scheduler's count: nothing was seeded
    assert grew["stream_tokens_delivered"] == N == grew["stream_tokens"]
    # a wake-up took at least a token (one for the terminal event
    # alone is not counted)
    assert 1 <= grew["stream_wakeups"] <= N
    assert math.isfinite(after["stream_delivery_lag_seconds"])
    # every token was committed after its reader came: each has a lag
    assert 0.0 < grew["stream_delivery_lag_seconds"] < 60.0 * N
    for k in ("serving_handler_admit_cpu_seconds",
              "serving_handler_stream_cpu_seconds",
              "serving_process_cpu_seconds"):
        assert grew[k] >= 0.0 and math.isfinite(after[k])
    # the process's CPU holds the scheduler's and the handlers'
    slack = 0.05            # the clocks are read in turn, in 10 ms steps
    assert after["serving_process_cpu_seconds"] + slack >= \
        after["serving_scheduler_cpu_seconds"] + \
        after["serving_handler_admit_cpu_seconds"] + \
        after["serving_handler_stream_cpu_seconds"]


def test_a_read_of_the_registry_brings_the_process_counter_up_to_date():
    """`time.process_time()` is read by the registry's collector when
    something reads the registry (a scrape, the benchmark's window edge,
    the flight recorder), never on the serve loop: an engine's metrics
    that nobody drives count the process's CPU all the same."""
    from fengshen_tpu.serving.metrics import EngineMetrics
    metrics = EngineMetrics()
    family = metrics.registry.get(FAMILIES["serving_process_cpu_seconds"])
    assert family.value() == 0.0
    metrics.registry.metrics()
    first = family.value()
    t = time.process_time()
    while time.process_time() - t < 0.05:       # burn
        sum(range(1000))
    assert family.value() == first              # no reader, no reading
    render_prometheus(metrics.registry)
    assert family.value() - first >= 0.04


def test_a_reconnect_credits_delivered_tokens_and_no_lag(replica):
    base, server, engine = replica
    _stream(base, {"input_text": "11 13", "request_id": "acct-2"})
    before = _settled(server, engine)
    events = _stream(base, {"request_id": "acct-2"},
                     headers={"Last-Event-ID": "2"})
    assert [e["id"] for e in events if e["event"] == "token"] == [3, 4, 5]
    after = _settled(server, engine)
    assert after["stream_reconnects"] - before["stream_reconnects"] == 1
    assert after["stream_tokens_delivered"] - \
        before["stream_tokens_delivered"] == 3
    # replayed: committed before this reader came
    assert after["stream_delivery_lag_seconds"] == \
        before["stream_delivery_lag_seconds"]
    assert after["stream_wakeups"] - before["stream_wakeups"] == 1
    assert after["stream_tokens"] == before["stream_tokens"]
    for k in ("serving_handler_admit_cpu_seconds",
              "serving_handler_stream_cpu_seconds"):
        assert after[k] >= before[k]


def test_a_client_that_drops_mid_stream_still_credits_what_it_was_sent(
        replica, monkeypatch):
    base, server, engine = replica
    # a tick every 20 ms: the reset is seen long before the stream ends
    # (the sleep wraps `_tick`: inside it, it would hold the engine's
    # lock against the submit)
    tick = engine._tick
    monkeypatch.setattr(
        engine, "_tick", lambda ahead: (time.sleep(0.02), tick(ahead))[1])
    before = _settled(server, engine)
    host, port = base[len("http://"):].split(":")
    body = json.dumps({"input_text": "3 4 5", "request_id": "acct-3",
                       "max_new_tokens": 40}).encode()
    sock = socket.create_connection((host, int(port)), timeout=30)
    sock.sendall(b"POST /api/text_generation/stream HTTP/1.1\r\n"
                 b"Host: x\r\nContent-Type: application/json\r\n"
                 b"Content-Length: " + str(len(body)).encode() +
                 b"\r\n\r\n" + body)
    got = b""
    while got.count(b"event: token") < 3:
        chunk = sock.recv(4096)
        assert chunk, got
        got += chunk
    seen = got.count(b"event: token")
    # close with a reset: the server's next write fails
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                    struct.pack("ii", 1, 0))
    sock.close()
    after = _settled(server, engine)
    sent = after["stream_tokens_delivered"] - \
        before["stream_tokens_delivered"]
    assert seen <= sent < 40
    # the wake-up whose frame met the reset took its batch all the same
    assert 1 <= after["stream_wakeups"] - before["stream_wakeups"] <= \
        sent + 1
    assert after["stream_delivery_lag_seconds"] > \
        before["stream_delivery_lag_seconds"]
    assert after["serving_handler_stream_cpu_seconds"] >= \
        before["serving_handler_stream_cpu_seconds"]
    # the request itself runs on: its tokens stay for a reconnect
    engine.cancel("acct-3")


def test_a_commits_one_stamp_reaches_every_token_it_brought():
    """`publish` keeps the commit's stamp beside each new token; a
    reader takes a wake-up's tokens and stamps together, from any
    index; what `open` seeds carries 0.0."""
    s = TokenStream()
    s.publish([5, 6])                              # seeded: no commit
    s.publish([5, 6, 7, 8, 9], stamp=12.5)         # a block's commit
    s.publish([5, 6, 7, 8, 9, 4], stamp=13.0, finish_reason="length")
    assert list(s.batches(0, timeout=1.0)) == [
        ("tokens", 0, ([5, 6, 7, 8, 9, 4],
                       [0.0, 0.0, 12.5, 12.5, 12.5, 13.0])),
        ("done", 6, "length")]
    assert list(s.batches(4, timeout=1.0)) == [
        ("tokens", 4, ([9, 4], [12.5, 13.0])), ("done", 6, "length")]
    # a reader at the end wakes for the terminal event alone
    assert list(s.batches(6, timeout=1.0)) == [("done", 6, "length")]
    # `events` is the same walk, a token an item
    assert list(s.events(4, timeout=1.0)) == [
        ("token", 4, 9), ("token", 5, 4), ("done", 6, "length")]
    assert list(TokenStream().batches(0, timeout=0.01)) == [
        ("timeout", 0, None)]
