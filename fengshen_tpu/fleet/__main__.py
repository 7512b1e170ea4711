"""CLI: run the fleet router (docs/fleet.md).

    # front replicas that are already running
    python -m fengshen_tpu.fleet --replicas 10.0.0.1:8000,10.0.0.2:8000

    # or spawn N local api replicas from one config, then front
    # them (the `make serve-fleet` path)
    python -m fengshen_tpu.fleet --spawn 3 --config api.json

SIGTERM drains gracefully: admission stops (healthz → 503 draining),
in-flight requests finish, spawned replicas are SIGTERMed (each drains
itself), then the process exits 0.
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m fengshen_tpu.fleet",
        description="health-gated fleet router over api replicas")
    p.add_argument("--replicas", type=str, default=None,
                   help="comma list of replica targets (host:port or "
                        "http://... base URLs)")
    p.add_argument("--spawn", type=int, default=None, metavar="N",
                   help="spawn N local api replicas from "
                        "--config instead of fronting existing ones")
    p.add_argument("--config", type=str, default=None,
                   help="api/main.py config json for --spawn")
    p.add_argument("--base-port", type=int, default=8100,
                   help="first spawned replica's port (default 8100)")
    p.add_argument("--phases", type=str, default=None,
                   help="comma list of per-replica serving phases for "
                        "--spawn (prefill|decode|both, e.g. "
                        "'prefill,decode,decode'); omitted replicas "
                        "default to 'both' (docs/disaggregation.md)")
    p.add_argument("--host", type=str, default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080,
                   help="the router's own port (default 8080)")
    p.add_argument("--task", type=str, default="text_generation",
                   help="the proxied /api/<task> route")
    p.add_argument("--poll-interval", type=float, default=0.5)
    p.add_argument("--request-timeout", type=float, default=120.0)
    p.add_argument("--max-retries", type=int, default=2)
    p.add_argument("--breaker-threshold", type=int, default=3)
    p.add_argument("--breaker-cooldown", type=float, default=5.0)
    p.add_argument("--recovery-probes", type=int, default=2)
    p.add_argument("--drain-timeout", type=float, default=30.0)
    p.add_argument("--dump-dir", type=str, default=None,
                   help="flight-recorder dir: post-mortem bundles "
                        "(incl. traces.json, the last-N distributed "
                        "traces) land here on drain")
    return p


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if bool(args.replicas) == bool(args.spawn):
        build_parser().error(
            "exactly one of --replicas or --spawn is required")
    if args.phases and not args.spawn:
        build_parser().error("--phases needs --spawn (already-running "
                             "replicas advertise their own phase)")
    procs = []
    if args.spawn:
        if not args.config:
            build_parser().error("--spawn needs --config")
        from fengshen_tpu.fleet.launcher import (spawn_replicas,
                                                 terminate_replicas)
        phases = [] if not args.phases else \
            [p.strip() for p in args.phases.split(",") if p.strip()]
        targets, procs = spawn_replicas(args.config, args.spawn,
                                        args.base_port, phases=phases)
        print(f"[fleet] spawned {len(procs)} replica(s): "
              f"{', '.join(targets)}", flush=True)
    else:
        targets = [t.strip() for t in args.replicas.split(",")
                   if t.strip()]

    from fengshen_tpu.fleet.router import FleetConfig, FleetRouter
    from fengshen_tpu.fleet.server import serve
    recorder = None
    if args.dump_dir:
        # router-side flight recorder: the event ring plus a
        # traces.json provider (the last-N distributed traces) in
        # every post-mortem bundle (docs/observability.md)
        from fengshen_tpu.observability import FlightRecorder
        recorder = FlightRecorder(dump_dir=args.dump_dir)
    router = FleetRouter(FleetConfig(
        replicas=targets, task=args.task,
        request_timeout_s=args.request_timeout,
        poll_interval_s=args.poll_interval,
        max_retries=args.max_retries,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown,
        recovery_probes=args.recovery_probes),
        recorder=recorder)

    def on_drained():
        if recorder is not None:
            try:
                recorder.dump(reason="router_drain")
            except Exception:  # noqa: BLE001 — a failed dump must not
                pass           # block replica teardown on the way out
        if procs:
            from fengshen_tpu.fleet.launcher import terminate_replicas
            terminate_replicas(procs)

    try:
        serve(router, args.host, args.port,
              drain_timeout_s=args.drain_timeout,
              on_drained=on_drained)
    finally:
        if procs:
            from fengshen_tpu.fleet.launcher import terminate_replicas
            terminate_replicas(procs, timeout_s=5.0)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
