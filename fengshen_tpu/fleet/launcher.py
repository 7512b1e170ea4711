"""Local fleet launcher: spawn N api replicas from one config.

`python -m fengshen_tpu.fleet --spawn N --config api.json` (the
`make serve-fleet` path) takes the SAME config file a single replica
runs with (`api/main.py`), writes N derived copies whose `SERVER.port`
is `base_port + i`, and starts each as a
`python -m fengshen_tpu.api.main --config <derived>` subprocess. The
router then fronts them; its health gating keeps traffic off each
replica until its warmup 503 window closes, and its drain handler
SIGTERMs the children (each drains gracefully, docs/fleet.md "Drain
runbook") once the router itself has drained.

One replica, one chip (docs/fleet.md "Replicas and chips"): a TPU chip
belongs to one process and a jax process claims every chip it can see,
so replica i is started seeing chip i and only that chip
(`replica_env`). Nothing here imports jax — a parent that touched it
would hold the chips its replicas need.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
from typing import List, Sequence, Tuple

from fengshen_tpu.disagg.policy import validate_phase


def replica_env(index: int) -> dict:
    """The environment replica `index` of a local fleet starts in: this
    process's, plus what shows it chip `index` of the host and no
    other. The three variables are libtpu's (verified on a four-chip
    v5e host, libtpu 0.0.34: four such processes each see one device
    and run side by side); other backends ignore them. A replica whose
    index has no chip fails at start-up ("No jellyfish device found")
    instead of hanging on a chip another replica holds."""
    return {**os.environ,
            "TPU_VISIBLE_CHIPS": str(index),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def spawn_replicas(config_path: str, n: int, base_port: int,
                   host: str = "127.0.0.1",
                   workdir: str = None,
                   phases: Sequence[str] = ()
                   ) -> Tuple[List[str], list]:
    """Write derived configs and start N replica subprocesses. Returns
    (targets, processes) where targets are "host:port" strings for
    `FleetConfig.replicas`. Replica i starts in `replica_env(i)` — this
    process's env (so `JAX_PLATFORMS` etc. flow through) narrowed to
    chip i. A replica drains gracefully on SIGTERM (`api/main.py`),
    which the fleet's rolling restarts depend on.

    `phases` assigns replica i the serving phase `phases[i]`
    (`prefill` | `decode` | `both`, docs/disaggregation.md) via its
    derived config's `SERVER.phase`; replicas past the end of the list
    stay homogeneous (`both`)."""
    if n < 1:
        raise ValueError("need at least one replica")
    phases = [validate_phase(p) for p in phases]
    if len(phases) > n:
        raise ValueError(f"{len(phases)} phases for {n} replicas")
    with open(config_path) as f:
        raw = json.load(f)
    workdir = workdir or tempfile.mkdtemp(prefix="fstpu_fleet_")
    targets, procs = [], []
    for i in range(n):
        cfg = json.loads(json.dumps(raw))    # deep copy
        server = cfg.setdefault("SERVER", {})
        port = base_port + i
        server["host"] = host
        server["port"] = port
        if i < len(phases):
            server["phase"] = phases[i]
        # per-replica dump dirs: two replicas sharing one flight-
        # recorder directory would interleave their bundle sequences
        server["dump_dir"] = os.path.join(
            workdir, f"replica{i}_dumps")
        path = os.path.join(workdir, f"replica{i}.json")
        with open(path, "w") as f:
            json.dump(cfg, f, indent=1, sort_keys=True)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "fengshen_tpu.api.main",
             "--config", path],
            env=replica_env(i)))
        targets.append(f"{host}:{port}")
    return targets, procs


def terminate_replicas(procs, timeout_s: float = 30.0) -> None:
    """SIGTERM every replica (graceful drain), then wait; SIGKILL any
    that outlive the timeout."""
    for p in procs:
        if p.poll() is None:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
    for p in procs:
        try:
            p.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
