"""Streaming tier (docs/streaming.md): token-by-token delivery for the
continuous-batching engine.

Two halves, both host-side (nothing here is ever traced):

- `stream`: `TokenStream` / `StreamBook` — per-request bounded token
  queues the engine's scheduler thread feeds at commit time, one
  signal a commit, with replay-from-index so `Last-Event-ID`
  reconnects and resume-from-token-k retries pick up mid-stream;
- `delivery`: `Delivery` — the server's ONE thread that, woken once a
  commit, writes every subscribed stream's news to its socket without
  blocking, while the request's handler thread parks;
- `sse`: the Server-Sent-Events wire framing (event ids = token
  index) shared by both API paths and parsed back by the fleet
  router's streaming transport.
"""

from fengshen_tpu.streaming.delivery import Delivery, Subscription
from fengshen_tpu.streaming.sse import format_event, iter_sse, token_frame
from fengshen_tpu.streaming.stream import StreamBook, TokenStream

__all__ = ["Delivery", "StreamBook", "Subscription", "TokenStream",
           "format_event", "iter_sse", "token_frame"]
