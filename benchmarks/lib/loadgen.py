"""The load generator: clients of the streamed route, on threads of
this process. A closed loop keeps `clients` callers each waiting for
its reply; an open loop sends on the schedule the mix fixes whether or
not earlier requests have finished, and times every request from the
instant it was DUE. Every streamed token is stamped on the client's
clock as it arrives.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

from benchmarks.lib import traffic


class LoadGen:
    def __init__(self, host: str, port: int, route: str, mix: dict,
                 seed: int, vocab: int, clock=time.perf_counter):
        self.host, self.port, self.route = host, port, route
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.clock = clock
        self.records: list = []
        self.completed = 0
        self._lock = threading.Lock()
        self._source = traffic.requests(mix, seed)
        self._stop = threading.Event()
        self._abort = threading.Event()
        self._threads: list = []
        self._conns: set = set()

    # -- one request ---------------------------------------------------
    def body(self, row: dict) -> bytes:
        ids = traffic.token_ids(self.seed, row["index"], row["prompt_len"],
                                self.vocab)
        return json.dumps({"input_text": " ".join(map(str, ids)),
                           "max_new_tokens": row["output_len"]}).encode()

    def send(self, row: dict, body: bytes, due: float) -> dict:
        rec = dict(row, due=due, sent=None, token_times=[], tokens=[],
                   failed=False, status=None, finish_reason=None,
                   done=None)
        with self._lock:
            self.records.append(rec)
        conn = http.client.HTTPConnection(self.host, self.port, timeout=300)
        with self._lock:
            self._conns.add(conn)
        try:
            rec["sent"] = self.clock()
            conn.request("POST", self.route + "/stream", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["failed"] = True
                resp.read()
                return rec
            event = None
            for raw in resp:
                if raw.startswith(b"event:"):
                    event = raw[6:].strip()
                elif raw.startswith(b"data:"):
                    now = self.clock()
                    if event == b"token":
                        rec["token_times"].append(now)
                        rec["tokens"].append(json.loads(raw[5:])["token"])
                    else:
                        rec["finish_reason"] = json.loads(raw[5:]).get(
                            "finish_reason", event.decode())
                        rec["done"] = now
                if self._abort.is_set():
                    break
            if rec["done"] is None and not self._abort.is_set():
                rec["failed"] = True
        except (OSError, http.client.HTTPException, ValueError):
            if not self._abort.is_set():
                rec["failed"] = True
        finally:
            conn.close()
            with self._lock:
                self._conns.discard(conn)
                if rec["done"] is not None:
                    self.completed += 1
        return rec

    def _next_row(self) -> dict:
        with self._lock:
            return next(self._source)

    # -- loops ---------------------------------------------------------
    def _closed_client(self, start_at: float) -> None:
        time.sleep(max(0.0, start_at - self.clock()))
        while not self._stop.is_set():
            row = self._next_row()
            self.send(row, self.body(row), self.clock())

    def _open_dispatch(self, t0: float) -> None:
        due = t0
        while not self._stop.is_set():
            row = self._next_row()
            due += row["gap_s"]
            body = self.body(row)
            wait = due - self.clock()
            if wait > 0 and self._stop.wait(wait):
                return
            t = threading.Thread(target=self.send, args=(row, body, due),
                                 daemon=True)
            t.start()

    def start(self) -> float:
        """Start the loop; returns the clock instant it began."""
        t0 = self.clock()
        if self.mix["loop"] == "closed":
            stagger = self.mix["ramp"]["stagger_s"]
            for i in range(self.mix["clients"]):
                t = threading.Thread(target=self._closed_client,
                                     args=(t0 + i * stagger,), daemon=True)
                t.start()
                self._threads.append(t)
        else:
            t = threading.Thread(target=self._open_dispatch, args=(t0,),
                                 daemon=True)
            t.start()
            self._threads.append(t)
        return t0

    def stop_issuing(self) -> None:
        self._stop.set()

    def abort(self) -> None:
        """Drop every open stream: the run is over."""
        self._stop.set()
        self._abort.set()
        with self._lock:
            conns = list(self._conns)
        for conn in conns:
            sock = conn.sock
            if sock is not None:
                try:
                    sock.shutdown(2)
                except OSError:
                    pass
        for t in self._threads:
            t.join(timeout=5.0)

    def snapshot(self) -> list:
        with self._lock:
            return list(self.records)
