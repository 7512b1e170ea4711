"""metric-contract: every ``fstpu_*`` family is registered once,
consistently, and documented.

Two checks over the index's metric registration sites (name, kind,
label set — extracted by the dataflow tier from every
``registry.counter/gauge/histogram`` get-or-create call with a
statically constant name):

- **collision**: the same metric name registered with a different
  label set or kind anywhere in the package. Prometheus registries
  reject that at runtime — but only on the code path that registers
  second, which may be a rarely-exercised serve mode.
- **docs drift**: the code table diffed against the "Metrics
  reference" table in ``docs/observability.md``. A registered family
  missing from the docs, a documented family no longer registered,
  and a label-set/kind mismatch are all findings, so the docs can't
  rot silently.

Families whose registration is dynamic — the serving outcome counters
built in a dict comprehension — are invisible to static extraction;
they are documented but live on ``DYNAMIC_REGISTRATIONS`` below so the rule
lands with a genuinely empty baseline instead of day-one
suppressions. The docs diff only runs when the analyzed set includes
package files and the docs file exists (fixture runs in tmp roots
check collisions only); documented-but-unregistered findings anchor
at the registry module so whole-package runs surface them.
"""

from __future__ import annotations

import os
from typing import Dict, Iterator, List, Tuple

from fengshen_tpu.analysis.dataflow import parse_metric_docs
from fengshen_tpu.analysis.registry import ProjectRule, register

#: documented families whose get-or-create site has no statically
#: constant name. Keep in sync with docs/observability.md — a name
#: here must still be documented; it is only excused from the
#: "documented but never registered" direction of the diff.
DYNAMIC_REGISTRATIONS = frozenset({
    # serving/metrics.py builds its outcome counters in a dict
    # comprehension over the name list
    "fstpu_serving_admitted_total",
    "fstpu_serving_cancelled_total",
    "fstpu_serving_completed_total",
    "fstpu_serving_deferred_admissions_total",
    "fstpu_serving_expired_total",
    "fstpu_serving_prefill_head_rows_total",
    "fstpu_serving_rejected_draining_total",
    "fstpu_serving_rejected_duplicate_total",
    "fstpu_serving_rejected_prompt_too_long_total",
    "fstpu_serving_rejected_queue_full_total",
})

#: where documented-but-unregistered findings anchor (the registry
#: module is the natural owner of the metric namespace and is always
#: part of a whole-package run)
_DOCS_ANCHOR = "fengshen_tpu/observability/registry.py"
_DOCS_PATH = os.path.join("docs", "observability.md")


@register
class MetricContract(ProjectRule):
    id = "metric-contract"
    hint = ("register each fstpu_* family exactly once per "
            "(name, labelnames, kind) and mirror it in the metrics "
            "reference table of docs/observability.md; dynamic "
            "registrations belong on the rule's "
            "DYNAMIC_REGISTRATIONS allowlist")

    def check_project(self, index) -> Iterator[
            Tuple[str, int, int, str]]:
        # (name) -> list of (relpath, line, col, kind, sorted labels)
        sites: Dict[str, List[Tuple[str, int, int, str,
                                    Tuple[str, ...]]]] = {}
        package_run = False
        for rel in sorted(index.files):
            if rel.startswith("fengshen_tpu/"):
                package_run = True
            for name, kind, labels, line, col in \
                    index.files[rel].metrics:
                sites.setdefault(name, []).append(
                    (rel, line, col, kind, tuple(sorted(labels))))

        # -- collisions (always, including fixture runs) -------------
        for name in sorted(sites):
            recs = sorted(sites[name])
            first = recs[0]
            for rec in recs[1:]:
                if (rec[3], rec[4]) == (first[3], first[4]):
                    continue
                yield (rec[0], rec[1], rec[2],
                       f"metric `{name}` registered as {rec[3]}"
                       f"{{{','.join(rec[4])}}} here but as "
                       f"{first[3]}{{{','.join(first[4])}}} at "
                       f"{first[0]}:{first[1]} — same family, "
                       f"conflicting schema")

        # -- docs drift (package runs with the docs present) ---------
        docs_file = os.path.join(self.project_root, _DOCS_PATH)
        if not package_run or not os.path.isfile(docs_file):
            return
        try:
            with open(docs_file, encoding="utf-8") as f:
                documented = parse_metric_docs(f.read())
        except (OSError, UnicodeDecodeError):
            return

        code: Dict[str, Tuple[str, int, int, str,
                              Tuple[str, ...]]] = {}
        for name in sorted(sites):
            pkg = [r for r in sorted(sites[name])
                   if r[0].startswith("fengshen_tpu/")]
            if pkg:
                code[name] = pkg[0]

        for name in sorted(set(code) - set(documented)):
            rel, line, col, kind, labels = code[name]
            yield (rel, line, col,
                   f"metric `{name}` ({kind}"
                   f"{{{','.join(labels)}}}) is registered but "
                   f"missing from the metrics reference table in "
                   f"{_DOCS_PATH}")
        for name in sorted(set(documented) - set(code)):
            if name in DYNAMIC_REGISTRATIONS:
                continue
            labels, kind, doc_line = documented[name]
            yield (_DOCS_ANCHOR, 1, 0,
                   f"metric `{name}` is documented "
                   f"({_DOCS_PATH}:{doc_line}) but never "
                   f"registered in the package — remove the row or "
                   f"add it to DYNAMIC_REGISTRATIONS if the "
                   f"registration is dynamic")
        for name in sorted(set(documented) & set(code)):
            rel, line, col, kind, labels = code[name]
            doc_labels, doc_kind, doc_line = documented[name]
            if (kind, labels) != (doc_kind, doc_labels):
                yield (rel, line, col,
                       f"metric `{name}` is {kind}"
                       f"{{{','.join(labels)}}} in code but "
                       f"documented as {doc_kind}"
                       f"{{{','.join(doc_labels)}}} at "
                       f"{_DOCS_PATH}:{doc_line}")
