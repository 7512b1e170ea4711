"""unguarded-shared-state: inconsistent lock discipline on one attr.

The RacerD-style heuristic: once a class protects an attribute with
one of its own locks *somewhere*, every other mutation of that
attribute is claiming the same invariant — a write outside the guard
is either a latent race (PR 11's JsonlSink interleaved-writer bug was
exactly this shape) or an undocumented threading assumption that the
next editor will break. The rule fires on attributes of a lock-owning
class that are mutated BOTH under a class lock and outside any,
counting in-place container mutation (``self.q.append``) as a write.

Escape hatches, in line with the serving stack's actual doctrine:

- ``__init__``-family writes: construction happens-before sharing
- guard inference through the call graph: a helper that every
  resolved call site enters with the lock held (``step()`` →
  ``_tick_locked()``) is guarded, as is anything honouring the
  ``*_locked`` naming convention
- thread confinement: private methods that only ever run on the
  class's own dedicated thread (``threading.Thread(target=self._loop)``
  and helpers reachable solely from it) are single-writer by
  construction
"""

from __future__ import annotations

from typing import Iterator, List, Tuple

from fengshen_tpu.analysis.registry import ProjectRule, register


@register
class UnguardedSharedState(ProjectRule):
    id = "unguarded-shared-state"
    hint = ("take the owning lock around this mutation (or move it "
            "into __init__/the owning thread, or suppress with the "
            "threading rationale)")

    def check_project(self, index) -> Iterator[Tuple[str, int, int,
                                                     str]]:
        held = index.guaranteed_held()
        confined = index.thread_confined()
        for relpath in sorted(index.files):
            fsum = index.files[relpath]
            for cname in sorted(fsum.classes):
                cs = fsum.classes[cname]
                if not cs.lock_attrs:
                    continue
                lock_ids = index.class_lock_ids(fsum.module, cs)
                # infra attributes follow their own lifecycle (locks
                # and threads are created once, never raced over)
                skip = set(cs.lock_attrs) | set(cs.waitable_attrs) \
                    | set(cs.thread_attrs) | set(cs.jit_attrs)
                guarded: dict = {}
                unguarded: dict = {}
                for q in sorted(fsum.functions):
                    fs = fsum.functions[q]
                    if fs.cls != cname:
                        continue
                    fn_id = f"{fsum.module}::{q}"
                    base = held.get(fn_id, set())
                    is_init = fs.name in ("__init__", "__post_init__",
                                          "__new__", "__del__",
                                          "__set_name__")
                    for attr, line, col, site_guards in fs.writes:
                        if attr in skip:
                            continue
                        eff = set(site_guards) | base
                        if eff & lock_ids:
                            guarded.setdefault(attr, []).append(
                                (relpath, line, col, q))
                        elif not is_init and fn_id not in confined:
                            unguarded.setdefault(attr, []).append(
                                (relpath, line, col, q))
                for attr in sorted(set(guarded) & set(unguarded)):
                    g0 = min(guarded[attr])
                    locks = " / ".join(
                        sorted(a for a in cs.lock_attrs))
                    for rel, line, col, q in sorted(unguarded[attr]):
                        yield (rel, line, col,
                               f"`self.{attr}` of {cname} is mutated "
                               f"here without the class lock "
                               f"(`{locks}`), but under it at "
                               f"{g0[0]}:{g0[1]} ({g0[3]}) — "
                               "inconsistent guarding is a data race")
