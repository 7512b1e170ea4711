"""donated-buffer-use: a buffer read after being donated to a jitted
call is reading freed device memory.

``jax.jit(fn, donate_argnums=...)`` hands the listed arguments'
buffers to XLA — after the
call dispatches, the caller's reference is invalid and reading it
returns garbage or raises, depending on backend and timing. That makes
this the classic silent-corruption bug: it passes on CPU test runs
(where donation is a no-op) and corrupts state on TPU.

The dataflow tier (``analysis/dataflow.py``) binds
``donate_argnums``/``donate_argnames`` positions through the wrapping
call to the variable the callable lands in (a local, a module var, or
a ``self._step_jit`` attribute), arms the caller variables passed in
donated positions at every call through that binding, and flags any
read on any later path. Rebinding from the outputs —

    state = step(state, batch)          # clean: donate + rebind
    cache, logits = self._decode_jit(tokens, cache, positions)

disarms the variable; that is the doctrine (docs/static_analysis.md,
"Donation & lifecycle doctrine"). The findings carried by each
``FileSummary`` were computed flow-sensitively at index time, so this
rule is a cheap re-emission and warm-cache runs stay fast.
"""

from __future__ import annotations

from typing import Iterator, Tuple

from fengshen_tpu.analysis.registry import ProjectRule, register


@register
class DonatedBufferUse(ProjectRule):
    id = "donated-buffer-use"
    hint = ("rebind the variable from the call's outputs "
            "(`x = f(x, ...)`) — a donated buffer is invalidated "
            "by dispatch; if the read is intentional (e.g. CPU-only "
            "path), suppress with a rationale")

    def check_project(self, index) -> Iterator[
            Tuple[str, int, int, str]]:
        for rel in sorted(index.files):
            fsum = index.files[rel]
            for (var, callee, bind_line, call_line, read_line,
                 read_col) in fsum.donation_findings:
                yield (rel, read_line, read_col,
                       f"`{var}` is read after being donated to "
                       f"`{callee}()` — witness: donate_argnums "
                       f"bound at {rel}:{bind_line} -> donating "
                       f"call at :{call_line} -> read at "
                       f":{read_line}")
