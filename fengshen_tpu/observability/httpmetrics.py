"""The shared HTTP request telemetry families.

Both server surfaces — the replica api server (`api/main.py`) and the
fleet router's own endpoints
(`fleet/server.py`) — count and time their requests into the SAME
global-registry families, so router-side and replica-side latency read
on one dashboard. The family definitions (name, help, labelnames) live
here ONCE: the registry's get-or-create matches on the full signature,
so two hand-kept string copies drifting apart would split the family
at runtime. Pure stdlib (the fleet package imports no jax).
"""

from __future__ import annotations

from fengshen_tpu.observability.registry import get_registry


def http_requests_total():
    """`fstpu_http_requests_total{route,code}` counter family."""
    return get_registry().counter(
        "fstpu_http_requests_total",
        "REST requests by route and status",
        labelnames=("route", "code"))


def http_request_seconds():
    """`fstpu_http_request_seconds{route}` latency histogram family."""
    return get_registry().histogram(
        "fstpu_http_request_seconds",
        "REST request wall seconds by route",
        labelnames=("route",))
