"""Startup/warmup telemetry: the build-info gauge and the warmup-phase
gauge every entry point records into the global registry.

Before this, warmup seconds only appeared in stdout logs — a scraper
could not answer "how long did this replica take to become ready" or
"which jax build is this fleet actually running". Now:

- ``fstpu_build_info{jax_version,backend}`` is a constant ``1``
  info-gauge (the Prometheus idiom: the VALUE is meaningless, the
  labels are the payload) set by the api server and the trainer at
  startup;
- ``fstpu_warmup_seconds{phase}`` records each warmup phase's wall
  seconds: ``engine`` (serving engine compile of all prefill buckets +
  decode) and ``pipeline`` (the legacy batch-1 warmup request).

Pure-stdlib except for the lazy jax probe, which degrades to
``jax_version="none"`` so the exporter works on hosts without jax.
"""

from __future__ import annotations

from typing import Optional

from fengshen_tpu.observability.registry import (MetricsRegistry,
                                                 get_registry)

BUILD_INFO_METRIC = "fstpu_build_info"
WARMUP_METRIC = "fstpu_warmup_seconds"


def record_build_info(registry: Optional[MetricsRegistry] = None) -> None:
    """Set the constant info-gauge for this process's jax build."""
    try:
        import jax
        version, backend = jax.__version__, jax.default_backend()
    except Exception:  # noqa: BLE001 — no/broken jax: still expose
        # SOMETHING a scraper can alert on
        version, backend = "none", "none"
    reg = registry if registry is not None else get_registry()
    reg.gauge(
        BUILD_INFO_METRIC,
        "constant 1; jax build + backend as labels",
        labelnames=("jax_version", "backend"),
    ).labels(version, backend).set(1)


def record_warmup_seconds(phase: str, seconds: float,
                          registry: Optional[MetricsRegistry] = None
                          ) -> None:
    """Record one warmup phase's wall seconds (gauge: the LAST warmup
    of each phase is the replica's current cold-start cost)."""
    reg = registry if registry is not None else get_registry()
    reg.gauge(
        WARMUP_METRIC,
        "wall seconds of each startup warmup phase",
        labelnames=("phase",),
    ).labels(phase).set(float(seconds))
