"""A look by hand at what a traced run's device operations are called:
for each scope name given, how many operations carry it and their
seconds, with a few of their texts; then the longest operations with
everything the profiler wrote on them. The readers under
`benchmarks/metrics/` that match `jax.named_scope` names
(`lib/scopes.py`) rest on what this prints.

    python3 benchmarks/tools/dump_scopes.py <trace dir> [scope ...]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import scopes, xplane  # noqa: E402


def main(trace_dir: str, *names: str) -> None:
    ops = scopes.load(xplane.find_xplane(trace_dir))
    print(f"{len(ops)} operations on device 0")
    for name in names:
        under = [e for e in ops if name in e[0]]
        print(f"{name}: {len(under)} operations, "
              f"{sum(e[2] for e in under):.4f} s")
        for text in sorted({e[0][:400] for e in under})[:4]:
            print("   ", text)
    total: dict = {}
    for text, _, d in ops:
        total[text[:300]] = total.get(text[:300], 0.0) + d
    for text, s in sorted(total.items(), key=lambda kv: -kv[1])[:25]:
        print(f"{s:9.4f} s  {text}")


if __name__ == "__main__":
    main(*sys.argv[1:])
