"""Live token streams between the engine's scheduler thread and the
threads that deliver them (docs/streaming.md).

Design constraints, in order:

- the SCHEDULER must never block on a slow client: `publish`/`sync`
  only append to a list under the book's one condition — delivery is
  the server's delivery thread's (`streaming/delivery.py`), and a
  reader that never drains costs the engine nothing but the list's
  memory (bounded by `max_new_tokens`, which admission already caps);
- ONE signal a commit: every stream of a book shares its condition
  `news`; a commit's per-lane loop appends inside
  `StreamBook.one_signal()` and the readers are woken once at its end,
  not once a lane. A sync outside a commit (finish, cancel,
  evacuation, `open`'s seed) wakes them itself. A pull reader
  (`batches` / `events`) waits on the same condition and goes back to
  sleep when the news was another stream's;
- readers must be able to (re)enter at ANY index: a `Last-Event-ID`
  reconnect or a router resuming after a replica death replays from
  token k out of the stream's own buffer — the committed-token list IS
  the replay log, the same journal contract `partial()` serves;
- lock order is one-way: engine `_cv` → `StreamBook._lock` → `news`.
  The engine syncs streams while holding its own lock, so nothing here
  may ever call back into the engine, and a reader holds `news` only
  to look and to wait: it frames and sends outside it.
"""

from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import Iterator, Optional

# closed streams kept for late reconnects before eviction; sized like
# the engine's debug ring — enough for any realistic reconnect window,
# bounded so a long-lived server cannot leak one entry per request
_CLOSED_RING = 256


class TokenStream:
    """One request's live token feed.

    The writer (scheduler thread) calls `publish` with the request's
    full committed-token snapshot; a reader takes what is committed
    past its own cursor (`since`, or the blocking `batches` / `events`)
    and then ONE terminal event. Tokens are append-only: `publish`
    never truncates, so concurrent readers at different offsets stay
    consistent.
    """

    def __init__(self, news: Optional[threading.Condition] = None) -> None:
        #: what readers wait on: the book's `news`, one for all its
        #: streams; a stream of no book has its own
        self._cond = news if news is not None else threading.Condition()
        self._tokens: list = []
        #: beside each token, the `time.perf_counter()` of the commit
        #: that brought it (0.0: no commit did, `StreamBook.open`
        #: seeded it); bounded like the tokens
        self._stamps: list = []
        self.finish_reason: Optional[str] = None
        self.evac_target: Optional[str] = None
        self.closed = False

    def publish(self, tokens, finish_reason: Optional[str] = None,
                evac_target: Optional[str] = None,
                stamp: float = 0.0, wake: bool = True) -> int:
        """Append any tokens past the current length, record terminal
        state, wake readers unless the caller will (`wake=False`: a
        commit's loop, which signals once at its end). Returns the
        number of NEW tokens (0 when the snapshot brings nothing — the
        common non-commit sync).
        `stamp` is the committing tick's ONE `time.perf_counter()`
        reading, the same for every stream it publishes to: a reader
        measures its delivery lag from it."""
        with self._cond:
            new = len(tokens) - len(self._tokens)
            if new > 0:
                self._tokens.extend(
                    int(t) for t in tokens[len(self._tokens):])
                self._stamps.extend([stamp] * new)
            if evac_target is not None:
                self.evac_target = evac_target
            if finish_reason is not None and not self.closed:
                self.finish_reason = finish_reason
                self.closed = True
            if wake and (new > 0 or self.closed):
                self._cond.notify_all()
            return max(new, 0)

    def tokens(self) -> list:
        """Snapshot of the committed tokens so far."""
        with self._cond:
            return list(self._tokens)

    def since(self, pos: int) -> tuple:
        """`(token_ids, stamps, closed)` committed from index `pos` on,
        without waiting. The caller holds the stream's condition (the
        book's `news`)."""
        return self._tokens[pos:], self._stamps[pos:], self.closed

    def terminal(self, pos: int) -> tuple:
        """The ONE terminal event of a closed stream for a reader at
        `pos`: `("evacuated", pos, target)` — the lane moved to another
        replica mid-generation, reconnect THERE with `Last-Event-ID =
        pos - 1` — or `("done", pos, finish_reason)`."""
        if self.evac_target is not None and self.finish_reason in (
                "evacuated", "handed_off"):
            return ("evacuated", pos, self.evac_target)
        return ("done", pos, self.finish_reason)

    def batches(self, start: int = 0,
                timeout: Optional[float] = None) -> Iterator[tuple]:
        """The pull reader's side, one item a wake-up that found news:
        `("tokens", index, (token_ids, stamps))` for everything
        committed from `index` on (one token where every tick wakes
        the reader in time, a block where a commit delivers one, more
        where the reader fell behind), then exactly one terminal event:
        `terminal`'s, or `("timeout", next_index, None)` — no event
        within `timeout` seconds (the reader's keep-alive/deadline
        surface; the stream itself stays open).

        Items are yielded OUTSIDE the condition so a stalled consumer
        never holds it against the scheduler's publish.
        """
        pos = max(int(start), 0)
        while True:
            with self._cond:
                # `wait_for`: the condition is every stream's of the
                # book, and a wake-up for another stream's news must
                # not restart the clock
                news = self._cond.wait_for(
                    lambda: len(self._tokens) > pos or self.closed,
                    timeout)
                batch, stamps, closed = self.since(pos)
            if not news:
                yield ("timeout", pos, None)
                return
            if batch:
                yield ("tokens", pos, (batch, stamps))
                pos += len(batch)
            if closed:
                yield self.terminal(pos)
                return

    def events(self, start: int = 0,
               timeout: Optional[float] = None) -> Iterator[tuple]:
        """`batches`, a token an item: `("token", index, token_id)` for
        every token at index >= start, then the terminal event."""
        for kind, pos, payload in self.batches(start, timeout):
            if kind != "tokens":
                yield (kind, pos, payload)
                continue
            for tok in payload[0]:
                yield ("token", pos, tok)
                pos += 1


class StreamBook:
    """The engine's registry of live `TokenStream`s, keyed by
    request_id. `sync` is the scheduler-side hot path: when no stream
    was EVER opened it is one attribute read, and per synced request it
    is one dict probe — a non-streaming engine pays nothing. Every
    `open`, `sync` and `one_signal` runs under the engine's lock: the
    hold below has one writer at a time."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._streams: "OrderedDict[str, TokenStream]" = OrderedDict()
        #: the ONE condition every stream of the book appends under and
        #: every reader waits on (the delivery thread, a pull reader)
        self.news = threading.Condition()
        #: inside `one_signal`: syncs append and leave the wake-up to
        #: its end; whether one of them reached a stream
        self._held = self._unsignalled = False
        #: flips true at the first open() and never back — the /stats
        #: gate that keeps never-streaming payloads shape-identical
        self.ever_opened = False

    def open(self, req) -> TokenStream:
        """Get-or-create the stream for `req`, seeded with its current
        committed tokens (so a resumed request's stream starts at k and
        a finished request's stream replays-and-closes). Idempotent —
        the reconnect path lands here too."""
        with self._lock:
            self.ever_opened = True
            stream = self._streams.get(req.request_id)
            if stream is None:
                stream = TokenStream(self.news)
                self._streams[req.request_id] = stream
                self._evict_closed_locked()
        self._publish(stream, req)
        return stream

    def sync(self, req, stamp: float = 0.0) -> int:
        """Scheduler-side push: publish `req`'s committed snapshot to
        its stream if one is open, under the commit's `stamp`
        (`TokenStream.publish`). Returns new-token count (0 on the
        no-stream fast path)."""
        if not self.ever_opened:
            return 0
        with self._lock:
            stream = self._streams.get(req.request_id)
        if stream is None:
            return 0
        return self._publish(stream, req, stamp)

    @contextlib.contextmanager
    def one_signal(self):
        """Around a commit's per-lane loop: the syncs inside it append
        and wake nobody, and the readers are woken ONCE at its end —
        one signal a commit, not one a lane."""
        self._held = True
        try:
            yield
        finally:
            self._held = False
            if self._unsignalled:
                self._unsignalled = False
                with self.news:
                    self.news.notify_all()

    def _publish(self, stream: TokenStream, req, stamp: float = 0.0) -> int:
        # finish_reason doubles as the terminal marker: the engine sets
        # it exactly once per request (finish/reject/detach), and
        # detach_lane stamps evac_target first, so the terminal event
        # can point the reader at the adopter
        if self._held:
            self._unsignalled = True
        return stream.publish(req.tokens,
                              finish_reason=req.finish_reason,
                              evac_target=req.evac_target, stamp=stamp,
                              wake=not self._held)

    def get(self, request_id: str) -> Optional[TokenStream]:
        with self._lock:
            return self._streams.get(request_id)

    def active(self) -> int:
        """Count of open (not yet closed) streams — the
        `fstpu_streams_active` gauge / `/stats streams_active`."""
        with self._lock:
            return sum(1 for s in self._streams.values()
                       if not s.closed)

    def _evict_closed_locked(self) -> None:
        # bound the book: drop the OLDEST CLOSED streams once the
        # closed population outgrows the ring; live streams are never
        # evicted (they are bounded by the engine's slot + queue caps)
        closed = [rid for rid, s in self._streams.items() if s.closed]
        for rid in closed[:max(len(closed) - _CLOSED_RING, 0)]:
            del self._streams[rid]
