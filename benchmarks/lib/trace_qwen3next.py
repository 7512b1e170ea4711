"""Moved to `lib/trace_lines.py` (PR 39 folded this module and
`lib/trace_sala.py` into it; the scope lists went to
`lib/costs_qwen3next.py`). Nothing imports this file. The path stays
only because `docs/observability.md` names it, tier-1's
`tests/test_docs_refs.py` holds a document to the paths it names, and a
PR that changes the benchmark may edit no document: the PR that
re-points that line deletes this file (PERF.md section 7)."""
