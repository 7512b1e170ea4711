"""The benchmark's own tests run on the CPU at tiny sizes; they sit
outside `tests/` and so outside tier-1. Four virtual devices stand in
for the four-chip host."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# no persistent cache of CPU programs: it warns on every load
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
