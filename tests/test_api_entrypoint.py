"""The process a user and the fleet launcher start:
`python -m fengshen_tpu.api.main --config <file>` (config -> engine ->
warm-up thread -> server -> SIGTERM drain -> exit), what its server
answers where nothing stands behind a route, and the launcher that
starts N of them, one a chip.

`main()` runs in the test's own (main) thread, as it does in a replica:
the drain handler is a signal handler. A client thread talks to the
server it started and ends the run with SIGTERM.
"""

import json
import os
import signal
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.api import main as api_main


@pytest.fixture
def sigterm_restored():
    previous = signal.getsignal(signal.SIGTERM)
    yield
    signal.signal(signal.SIGTERM, previous)


def _ask(url, method="GET", raw=None):
    """(status, body, headers) of one request, whatever the status."""
    req = urllib.request.Request(
        url, data=raw, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.loads(r.read()), r.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), e.headers


def _get(url):
    return _ask(url)[:2]


def _post(url, payload):
    return _ask(url, "POST", json.dumps(payload).encode())[:2]


def _sigterm():
    signal.pthread_kill(threading.main_thread().ident, signal.SIGTERM)


def _run_main(tmp_path, monkeypatch, pipeline, server=None, engine=None,
              client=None, task="text_classification"):
    """`main()` on a config file that names `task`, with `pipeline` in
    the place of the task's own. `client(base, seen)` runs on a thread
    once the server listens and its return ends the run with SIGTERM.
    Returns what was seen: the `ServerConfig` and the server `main()`
    built, and whatever the client left under "client"."""
    cfg = tmp_path / "api.json"
    cfg.write_text(json.dumps({
        "SERVER": {"host": "127.0.0.1", "port": 0,
                   "dump_dir": str(tmp_path / "dumps"), **(server or {})},
        "ENGINE": engine or {},
        "PIPELINE": {"task": task}}))
    monkeypatch.setattr(api_main, "_resolve_pipeline",
                        lambda pipeline_cfg: pipeline)
    seen = {}
    build = api_main.build_stdlib_server

    def build_and_tell(server_cfg, *args, **kw):
        seen["server_cfg"] = server_cfg
        seen["server"] = build(server_cfg, *args, **kw)
        listening.set()
        return seen["server"]

    listening = threading.Event()
    monkeypatch.setattr(api_main, "build_stdlib_server", build_and_tell)

    def drive():
        try:
            assert listening.wait(60)
            port = seen["server"].server_address[1]
            if client is not None and not returned.is_set():
                seen["client"] = client(f"http://127.0.0.1:{port}", seen)
        except BaseException as e:  # noqa: BLE001 — shown by the test
            seen["client_error"] = e
        finally:
            # a `main()` that has left may have left the recorder's
            # handler behind, which ends the process
            if not returned.is_set():
                _sigterm()

    returned = threading.Event()
    driver = threading.Thread(target=drive, daemon=True)
    driver.start()
    try:
        api_main.main(["--config", str(cfg)])
    finally:
        returned.set()
        listening.set()
        driver.join(60)
    if "client_error" in seen:
        raise seen["client_error"]
    return seen


def test_a_config_files_blocks_reach_the_server(tmp_path):
    """`load_config`: the server listens where the SERVER block says and
    routes by the PIPELINE block's task."""
    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps({
        "SERVER": {"host": "127.0.0.1", "port": 0},
        "PIPELINE": {"task": "text_classification", "top_k": 2}}))
    server_cfg, pipeline_cfg = api_main.load_config(str(cfg))
    assert (server_cfg.host, server_cfg.port) == ("127.0.0.1", 0)
    assert pipeline_cfg.pipeline_args == {"top_k": 2}
    server = api_main.build_stdlib_server(
        server_cfg, pipeline_cfg,
        pipeline=lambda text: {"label": 1, "score": 0.9})
    base = f"http://127.0.0.1:{server.server_address[1]}"
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        assert _post(base + "/api/text_classification",
                     {"input_text": "你好"}) == \
            (200, {"result": {"label": 1, "score": 0.9}})
        assert _get(base + "/healthz")[1]["status"] == "ok"
        assert _get(base + "/api/text_generation")[0] == 404
    finally:
        server.shutdown()
        server.server_close()


@pytest.fixture(scope="module")
def tiny_pipeline():
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.pipelines.text_generation import Pipeline

    class IntTokenizer:
        eos_token_id = None
        pad_token_id = 0

        def encode(self, text):
            return [int(t) for t in text.split()]

        def decode(self, ids):
            return " ".join(str(int(t)) for t in ids)

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, dtype="float32"))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return Pipeline(module=model, params=params, tokenizer=IntTokenizer(),
                    max_new_tokens=4, eos_token_id=None, pad_token_id=0)


def test_main_serves_a_continuous_engine_from_config_to_drain(
        tmp_path, monkeypatch, capsys, sigterm_restored, tiny_pipeline):
    """The whole line: the ENGINE block builds the engine, `/healthz` is
    503 while the warm-up thread compiles and 200 after, a POST is
    served by the engine, and SIGTERM drains: `main()` returns, the
    port is closed, the engine's serve loop has stopped and the flight
    recorder left its bundle."""
    warm = threading.Event()
    create = api_main.create_continuous_engine
    engines = []

    def create_gated(*args, **kw):
        eng = create(*args, **kw)
        warmup = eng.warmup
        eng.warmup = lambda: (warm.wait(60), warmup())[1]
        engines.append(eng)
        return eng

    monkeypatch.setattr(api_main, "create_continuous_engine", create_gated)

    def client(base, seen):
        cold = _get(base + "/healthz")
        warm.set()
        for _ in range(2400):        # two minutes: a loaded host compiles
            hot = _get(base + "/healthz")
            if hot[0] == 200:
                break
            time.sleep(0.05)
        answer = _post(base + "/api/text_generation",
                       {"input_text": "5 7 9"})
        return cold, hot, answer, _get(base + "/stats")

    seen = _run_main(
        tmp_path, monkeypatch, tiny_pipeline, task="text_generation",
        server={"engine": "continuous", "phase": "decode"},
        engine={"num_slots": 2, "buckets": [8], "max_queue": 4},
        client=client)
    cold, hot, answer, stats = seen["client"]
    assert cold[0] == 503 and cold[1]["reason"] == "warmup"
    assert hot == (200, {"status": "ok", "task": "text_generation",
                         "ready": True})
    assert answer[0] == 200
    assert len(answer[1]["result"].split()) == 4
    assert answer[1]["finish_reason"] == "length"
    # the ENGINE and SERVER blocks reached the engine and the server
    assert stats[1]["num_slots"] == 2 and stats[1]["phase"] == "decode"
    assert stats[1]["completed"] == 1
    (engine,) = engines
    assert engine.draining and engine.idle()
    assert engine._thread is None or not engine._thread.is_alive()
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(
            "http://127.0.0.1:%d/healthz"
            % seen["server"].server_address[1], timeout=5)
    (bundle,) = os.listdir(tmp_path / "dumps")
    manifest = json.loads(
        (tmp_path / "dumps" / bundle / "manifest.json").read_text())
    assert manifest["reason"] == "sigterm_drain"
    assert "stdlib server on 127.0.0.1:0" in capsys.readouterr().out


def test_main_exits_when_the_warmup_fails(tmp_path, monkeypatch,
                                          sigterm_restored):
    """A pipeline that cannot answer its warm-up request ends the
    process with the error, not a replica that answers 503 for ever."""
    def broken(text):
        raise RuntimeError("no weights")

    def until_closed(base, seen):
        try:
            for _ in range(600):
                assert _get(base + "/healthz")[0] == 503
                time.sleep(0.05)
        except urllib.error.URLError:
            pass

    with pytest.raises(SystemExit,
                       match="warmup failed: RuntimeError: no weights"):
        _run_main(tmp_path, monkeypatch, broken, client=until_closed)


@pytest.mark.parametrize("peers_env, peers", [
    (None, ("http://c:3",)),
    ("http://a:1/, http://b:2,", ("http://a:1", "http://b:2"))],
    ids=["from_the_config", "FSTPU_PEERS_wins"])
def test_main_takes_its_evacuation_peers(tmp_path, monkeypatch,
                                         sigterm_restored, peers_env,
                                         peers):
    if peers_env is None:
        monkeypatch.delenv("FSTPU_PEERS", raising=False)
    else:
        monkeypatch.setenv("FSTPU_PEERS", peers_env)
    seen = _run_main(tmp_path, monkeypatch, lambda text: "ok",
                     server={"warmup": False, "peers": ["http://c:3/"]})
    assert seen["server_cfg"].peers == peers


def test_main_serves_the_same_with_a_module_named_fastapi_about(
        tmp_path, monkeypatch, capsys, sigterm_restored):
    """There is one server. A `fastapi` on the path, here one that
    cannot even be imported, is never looked at."""
    stub = tmp_path / "site" / "fastapi"
    stub.mkdir(parents=True)
    (stub / "__init__.py").write_text("raise RuntimeError('imported')\n")
    monkeypatch.syspath_prepend(str(tmp_path / "site"))
    monkeypatch.delenv("FSTPU_PEERS", raising=False)

    def client(base, seen):
        return (_get(base + "/healthz"),
                _post(base + "/api/text_classification",
                      {"input_text": "hi"}))

    seen = _run_main(tmp_path, monkeypatch, lambda text: "ok:" + text,
                     server={"warmup": False}, client=client)
    health, answer = seen["client"]
    assert health[0] == 200 and health[1]["ready"] is True
    assert answer == (200, {"result": "ok:hi"})
    assert "fastapi" not in sys.modules
    assert "stdlib server on 127.0.0.1:0" in capsys.readouterr().out


# ---- the server's contract on the simple path ----------------------------

@pytest.fixture(scope="module")
def simple_server():
    """A server with a pipeline and nothing else: no engine, no
    recorder, no KV-handoff coordinator."""
    calls = []

    def pipeline(text):
        calls.append(text)
        if text == "explode":
            raise RuntimeError("tower exploded")
        return "ok:" + text

    server = api_main.build_stdlib_server(
        api_main.ServerConfig(host="127.0.0.1", port=0, phase="prefill"),
        api_main.PipelineConfig(task="text_classification"),
        pipeline=pipeline)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{server.server_address[1]}", calls
    server.shutdown()
    server.server_close()


@pytest.mark.parametrize("method, path, raw, code, body", [
    ("POST", "/api/text_classification", b"{not json", 422, None),
    ("POST", "/api/text_classification", b'{"text": "x"}', 422,
     {"error": "input_text required"}),
    ("POST", "/api/text_classification", b'{"input_text": "explode"}',
     500, {"error": "tower exploded"}),
    ("POST", "/api/text_classification/stream", b'{"input_text": "x"}',
     501, {"error": "streaming requires the continuous batching engine"}),
    ("POST", "/api/text_generation", b'{"input_text": "x"}', 404,
     {"error": "not found"}),
    ("GET", "/api/text_classification", None, 404, {"error": "not found"}),
    ("PUT", "/api/text_classification", b"{}", 404,
     {"error": "not found"}),
    ("DELETE", "/api/text_classification", None, 404,
     {"error": "not found"}),
    ("PUT", "/kv/r1", b"{}", 409, {"adopted": False, "reason": "no_engine"}),
    ("GET", "/kv/r1", None, 404, {"error": "no disagg coordinator"}),
    ("DELETE", "/kv/r1", None, 404, {"error": "no disagg coordinator"}),
    ("GET", "/partial/r1", None, 404,
     {"error": "unknown request_id 'r1'"}),
    ("GET", "/debug/requests/r1", None, 404,
     {"error": "unknown request_id 'r1'"}),
    ("GET", "/stats", None, 200,
     {"engine": "simple", "task": "text_classification",
      "phase": "prefill"}),
], ids=["invalid_json", "no_input_text", "the_pipeline_raises",
        "stream_without_an_engine", "another_task", "get_the_post_route",
        "put_off_kv", "delete_off_kv", "kv_put_without_a_coordinator",
        "kv_get_without_a_coordinator", "kv_delete_without_a_coordinator",
        "partial_without_a_journal", "debug_request_without_a_ring",
        "stats_of_the_simple_path"])
def test_the_server_answers_every_refusal_as_json(simple_server, method,
                                                  path, raw, code, body):
    """The statuses and bodies a client, the fleet router and a
    handoff peer key on, where nothing stands behind a route: every one
    is an answer with a JSON body and the CORS header, never a dropped
    socket, and nothing but a well-formed request reaches the
    pipeline."""
    base, calls = simple_server
    before = len(calls)
    got_code, got_body, headers = _ask(base + path, method, raw)
    assert got_code == code
    if body is None:
        assert got_body["error"].startswith("invalid json")
    else:
        assert got_body == body
    assert headers["Access-Control-Allow-Origin"] == "*"
    assert len(calls) - before == (1 if code == 500 else 0)


def test_a_request_id_never_becomes_a_route_label(simple_server):
    """`fstpu_http_requests_total{route}` keeps one label for every id
    of a route and one for everything unknown."""
    base, _ = simple_server
    for path in ("/kv/a", "/kv/b", "/partial/a", "/partial/b",
                 "/debug/requests/a", "/nope/a", "/nope/b"):
        _get(base + path)
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        text = r.read().decode()
    routes = {line.split('route="')[1].split('"')[0]
              for line in text.splitlines()
              if line.startswith("fstpu_http_requests_total{")}
    assert {"/kv/<id>", "/partial/<id>", "/debug/requests/<id>",
            "other"} <= routes
    assert not [r for r in routes if r.endswith(("/a", "/b"))]


@pytest.mark.parametrize("takes_it", [True, False],
                         ids=["a_generation_pipeline", "a_classifier"])
def test_max_new_tokens_reaches_only_a_pipeline_that_takes_it(takes_it):
    """A client's cap is forwarded on the simple path where the
    pipeline's signature has it; to a classifier it would be a
    TypeError and a 500."""
    seen = []
    if takes_it:
        def pipeline(text, max_new_tokens=16):
            seen.append(max_new_tokens)
            return text
    else:
        def pipeline(text):
            seen.append(None)
            return text
    server = api_main.build_stdlib_server(
        api_main.ServerConfig(host="127.0.0.1", port=0),
        api_main.PipelineConfig(task="t"), pipeline=pipeline)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{server.server_address[1]}"
        assert _post(base + "/api/t", {"input_text": "x",
                                       "max_new_tokens": 3}) == \
            (200, {"result": "x"})
        assert _post(base + "/api/t", {"input_text": "x"})[0] == 200
    finally:
        server.shutdown()
        server.server_close()
    assert seen == ([3, 16] if takes_it else [None, None])


# ---- the launcher: one replica a chip ------------------------------------

def test_launcher_starts_each_replica_on_its_own_chip(tmp_path,
                                                      monkeypatch):
    """`spawn_replicas` derives one config a replica (its port, phase
    and dump directory) and starts `api.main` on it in an environment
    that shows it its chip and no other, and carries nothing that
    chooses a server: there is one."""
    from fengshen_tpu.fleet import launcher

    started = []

    class Process:
        def __init__(self, argv, env):
            started.append((argv, env))

    monkeypatch.setattr(launcher.subprocess, "Popen", Process)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cfg = tmp_path / "api.json"
    cfg.write_text(json.dumps({"SERVER": {"engine": "continuous"},
                               "PIPELINE": {"task": "text_generation"}}))
    targets, procs = launcher.spawn_replicas(
        str(cfg), 3, base_port=8300, workdir=str(tmp_path),
        phases=("prefill", "decode"))
    assert targets == ["127.0.0.1:8300", "127.0.0.1:8301",
                       "127.0.0.1:8302"]
    assert len(procs) == len(started) == 3
    for i, (argv, env) in enumerate(started):
        assert argv[1:4] == ["-m", "fengshen_tpu.api.main", "--config"]
        derived = json.loads(open(argv[4]).read())
        assert derived["PIPELINE"] == {"task": "text_generation"}
        assert derived["SERVER"] == {
            "engine": "continuous", "host": "127.0.0.1",
            "port": 8300 + i,
            "dump_dir": str(tmp_path / f"replica{i}_dumps"),
            **({"phase": ("prefill", "decode")[i]} if i < 2 else {})}
        assert env == launcher.replica_env(i)
        assert env["TPU_VISIBLE_CHIPS"] == str(i)
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert env["JAX_PLATFORMS"] == "cpu"
        assert not [k for k in env if k.startswith("FSTPU_")]
    with pytest.raises(ValueError, match="3 phases for 2 replicas"):
        launcher.spawn_replicas(str(cfg), 2, 8300,
                                phases=("both", "both", "both"))


def test_launcher_terminates_with_sigterm_then_kills_what_stays():
    from fengshen_tpu.fleet import launcher

    class Process:
        def __init__(self, stubborn):
            self.stubborn, self.signals, self.killed = stubborn, [], False

        def poll(self):
            return None

        def send_signal(self, sig):
            self.signals.append(sig)

        def wait(self, timeout=None):
            if self.stubborn and not self.killed:
                raise launcher.subprocess.TimeoutExpired("replica",
                                                         timeout)

        def kill(self):
            self.killed = True

    procs = [Process(False), Process(True)]
    launcher.terminate_replicas(procs, timeout_s=0.01)
    assert [p.signals for p in procs] == [[signal.SIGTERM]] * 2
    assert [p.killed for p in procs] == [False, True]
