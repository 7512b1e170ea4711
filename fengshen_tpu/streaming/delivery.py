"""The server's ONE delivery thread (docs/streaming.md "Delivery").

A stream's handler thread parses, submits and writes the response
headers, then hands its connection over as a `Subscription` and PARKS
until the stream is over: one wake-up a request. This thread waits on
the book's `news` — signalled once a commit — and on each return takes
every subscription's news, frames it and writes it with one `send` a
connection. It frames and sends and nothing else: the terminal event
(`done`'s decode) is the parked handler's, woken with it.

No client can stall another: the sockets do not block. What a socket
did not take stays in its subscription (`unsent`) and is retried every
`_RETRY_S`; until it is gone the subscription frames no further token,
so its backlog is its cursor's distance in the stream's own list, which
`max_new_tokens` bounds. A connection that errors or takes nothing for
its `timeout_s` loses its subscription and nothing else: its tokens
stay in the stream for a `Last-Event-ID` reconnect.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from fengshen_tpu.streaming.sse import token_frame
from fengshen_tpu.streaming.stream import StreamBook, TokenStream

#: between two tries of a socket that took less than it was offered
_RETRY_S = 0.02
#: delivered tokens between two readings of the thread's CPU clock (a
#: system call that holds the GIL), which ride on the next credit
_CLOCK_EVERY = 512


class Subscription:
    """One connection's place in one stream: what a handler thread
    hands to the delivery thread, and what it parks on."""

    __slots__ = ("stream", "sock", "cursor", "timeout_s", "arrived",
                 "deadline", "unsent", "stamps", "retry_at", "served",
                 "end", "over")

    def __init__(self, stream: TokenStream, start: int, sock,
                 timeout_s: float, arrived: float) -> None:
        self.stream, self.sock = stream, sock
        #: index of the first token not yet framed
        self.cursor = max(int(start), 0)
        #: the request's arrival (`time.perf_counter()`): a token
        #: committed before it (a replay) lags behind nothing
        self.timeout_s, self.arrived = timeout_s, arrived
        #: no news and no byte taken until then: the stream times out
        self.deadline = time.perf_counter() + timeout_s
        #: what the socket has not taken of the last chunk, the stamps
        #: of that chunk's tokens, and when to offer it again
        self.unsent, self.stamps, self.retry_at = b"", (), 0.0
        self.served = False
        #: the terminal `(kind, next_index, payload)`: `TokenStream`'s
        #: `done` / `evacuated`, `timeout`, or `dropped` (the client
        #: went or the server closed: nothing more can be written)
        self.end: Optional[tuple] = None
        self.over = threading.Event()


class Delivery(threading.Thread):
    """Delivers every subscribed stream of `book`, crediting `metrics`
    (an `EngineMetrics`). `start()` it with the server and `stop()` it
    when the server closes."""

    def __init__(self, book: StreamBook, metrics) -> None:
        super().__init__(daemon=True, name="fstpu-delivery")
        self._news, self._metrics = book.news, metrics
        self._subs: list = []
        self._stopped = False
        self._cpu, self._unclocked = 0.0, 0

    def subscribe(self, sub: Subscription) -> None:
        """Hand `sub`'s connection over (its response headers are
        written); the caller then waits on `sub.over`."""
        sub.sock.setblocking(False)
        with self._news:
            if not self._stopped:
                self._subs.append(sub)
                self._news.notify_all()
                return
        self._hand_back(sub, "dropped")

    def stop(self) -> None:
        """End the thread; every stream still open is handed back as
        `dropped`."""
        with self._news:
            self._stopped = True
            self._news.notify_all()
        if self.is_alive():
            self.join(timeout=10.0)

    def run(self) -> None:
        self._cpu = time.thread_time()
        try:
            while True:
                with self._news:
                    work, wake_at = self._look()
                    while not work and not self._stopped:
                        self._news.wait(
                            None if wake_at is None else
                            max(wake_at - time.perf_counter(), 0.0))
                        work, wake_at = self._look()
                    if self._stopped:
                        return
                self._serve(work)
        finally:
            # stopped, or a fault of this loop: no handler stays parked
            with self._news:
                self._stopped = True
                left, self._subs = self._subs, []
            for sub in left:
                self._hand_back(sub, "dropped")

    def _look(self) -> tuple:
        """`[(subscription, token_ids, stamps, closed)]` of what has
        something to do now, and when the next one will without news
        (None: never). Under `news`."""
        now, work, wake_at = time.perf_counter(), [], None
        for sub in self._subs:
            if sub.unsent:
                # behind its socket: the timer's, whatever is committed
                due = sub.retry_at
                if now >= due:
                    work.append((sub, (), (), False))
            else:
                due = sub.deadline
                toks, stamps, closed = sub.stream.since(sub.cursor)
                if toks or closed or now >= due:
                    work.append((sub, toks, stamps, closed))
            if wake_at is None or due < wake_at:
                wake_at = due
        return work, wake_at

    def _serve(self, work: list) -> None:
        clock, metrics = time.perf_counter, self._metrics
        tokens, lag = 0, 0.0
        for sub, toks, stamps, closed in work:
            now = clock()
            if not sub.served:
                # delivery-layer TTFB: received-to-first-byte (the
                # engine's ttft_seconds keeps its commit-time meaning)
                sub.served = True
                metrics.record_stream_ttfb(now - sub.arrived)
            if toks:
                index = sub.cursor
                sub.unsent = b"".join(
                    [token_frame(index + i, t) for i, t in enumerate(toks)])
                sub.stamps = stamps
                sub.cursor = index + len(toks)
            if sub.unsent:
                try:
                    taken = sub.sock.send(sub.unsent)
                except BlockingIOError:
                    taken = 0
                except OSError:
                    # the client went away mid-stream; its tokens stay
                    # in the journal + stream buffer for a reconnect
                    self._hand_back(sub, "dropped")
                    continue
                if taken:
                    sub.deadline = now + sub.timeout_s
                    sub.unsent = sub.unsent[taken:]
                if sub.unsent:
                    sub.retry_at = now + _RETRY_S
                    if now >= sub.deadline:
                        self._hand_back(sub, "dropped")
                    continue
                now = clock()
                tokens += len(sub.stamps)
                for stamp in sub.stamps:
                    if stamp >= sub.arrived:
                        lag += now - stamp
                sub.stamps = ()
            if closed:
                kind, _, payload = sub.stream.terminal(sub.cursor)
                self._hand_back(sub, kind, payload)
            elif now >= sub.deadline:
                self._hand_back(sub, "timeout")
        if tokens:
            self._unclocked += tokens
            metrics.record_delivery(
                self._cpu_spent() if self._unclocked >= _CLOCK_EVERY
                or not self._subs else 0.0, 1, tokens, lag)

    def _cpu_spent(self) -> float:
        now = time.thread_time()
        spent, self._cpu, self._unclocked = now - self._cpu, now, 0
        return spent

    def _hand_back(self, sub: Subscription, kind: str,
                   payload=None) -> None:
        """Take `sub` off the list and wake its handler with the
        terminal event."""
        with self._news:
            if sub in self._subs:
                self._subs.remove(sub)
        sub.end = (kind, sub.cursor, payload)
        sub.over.set()
