"""The declarative rules table: logical axis → mesh axis (or None).

One table answers "how does this deployment shard?" for every model in
the package (docs/sharding.md). Models annotate parameter dimensions
with logical-axis names (``*PARAM_LOGICAL_AXES`` tables: regex on the
param path → tuple of logical names, the same path-matching contract
``parallel.partition.match_partition_rules`` already speaks) and
:func:`to_partition_rules` resolves them into the regex →
``PartitionSpec`` lists every existing consumer
(``make_shardings`` / ``create_sharded_state`` / the offload policy)
takes unchanged. Activations go through
:func:`with_logical_constraint`, optimizer state inherits the param
specs as before.
"""

from __future__ import annotations

import threading
from typing import Any, Optional, Sequence, Tuple

from jax.sharding import PartitionSpec as P

from fengshen_tpu.parallel.mesh import (BATCH_AXES, DATA_AXIS, EXPERT_AXIS,
                                        FSDP_AXIS, SEQUENCE_AXIS,
                                        TENSOR_AXIS)
from fengshen_tpu.sharding.axes import LOGICAL_AXIS_SET

#: The default deployment table — the sharding story of the whole
#: package in one place. Megatron conventions (PAPERS.md arxiv
#: 2104.04473): column-parallel projections put their OUTPUT dim
#: (heads/kv/mlp) on the tensor axis, row-parallel projections their
#: INPUT dim; the other weight dim takes fsdp (ZeRO-3-style param
#: sharding); vocab is tensor-parallel for the vocab-parallel
#: embedding + CE. ``relpos`` and ``norm`` are deliberately None —
#: see docs/sharding.md "Root cause" for why relpos must never shard.
DEFAULT_LOGICAL_AXIS_RULES: tuple = (
    ("batch", BATCH_AXES),
    ("seq", SEQUENCE_AXIS),
    ("vocab", TENSOR_AXIS),
    ("embed", FSDP_AXIS),
    ("heads", TENSOR_AXIS),
    ("kv", TENSOR_AXIS),
    ("mlp", TENSOR_AXIS),
    ("expert", EXPERT_AXIS),
    ("layers", None),
    ("conv_kernel", None),
    ("conv_in", None),
    ("conv_out", FSDP_AXIS),
    ("relpos", None),
    ("norm", None),
)

#: Mesh-axis names the table may map onto (mirrors
#: ``parallel.mesh.MESH_AXES``; kept literal so the table validates
#: without building a mesh).
_MESH_AXIS_SET = frozenset({DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS,
                            TENSOR_AXIS, EXPERT_AXIS, "pipe"})

_active = threading.local()


def validate_rules(rules: Sequence[Tuple[str, Any]]) -> None:
    """Reject a malformed table loudly at definition time — an unknown
    logical axis would otherwise KeyError deep inside resolution, and
    an unknown mesh axis would silently replicate (the exact failure
    fslint's partition-spec-axes rule exists to catch statically)."""
    seen = set()
    for entry in rules:
        if not isinstance(entry, (tuple, list)) or len(entry) != 2:
            raise ValueError(f"rules entry {entry!r} is not a "
                             "(logical_axis, mesh_axis) pair")
        logical, mesh_axis = entry
        if logical not in LOGICAL_AXIS_SET:
            raise ValueError(
                f"unknown logical axis {logical!r} — declare it in "
                "fengshen_tpu/sharding/axes.py (LOGICAL_AXES)")
        if logical in seen:
            raise ValueError(f"logical axis {logical!r} mapped twice")
        seen.add(logical)
        axes = mesh_axis if isinstance(mesh_axis, (tuple, list)) \
            else (mesh_axis,)
        for a in axes:
            if a is not None and a not in _MESH_AXIS_SET:
                raise ValueError(
                    f"rules map {logical!r} to unknown mesh axis "
                    f"{a!r} (mesh axes: "
                    f"{', '.join(sorted(_MESH_AXIS_SET))})")


def get_rules() -> tuple:
    """The active table: the default unless a `use_rules` scope or
    `set_rules` override is in effect."""
    return getattr(_active, "rules", None) or DEFAULT_LOGICAL_AXIS_RULES


def set_rules(rules: Optional[Sequence[Tuple[str, Any]]]) -> None:
    """Install `rules` as the active table (None restores the
    default). Validates eagerly."""
    if rules is not None:
        validate_rules(rules)
        rules = tuple((k, tuple(v) if isinstance(v, list) else v)
                      for k, v in rules)
    _active.rules = rules


class use_rules:
    """Scoped table override::

        with use_rules(my_table):
            shardings = make_shardings(model.partition_rules(), ...)
    """

    def __init__(self, rules: Optional[Sequence[Tuple[str, Any]]]):
        self._rules = rules

    def __enter__(self):
        self._prev = getattr(_active, "rules", None)
        set_rules(self._rules)
        return get_rules()

    def __exit__(self, *exc):
        _active.rules = self._prev
        return False


def resolve_spec(logical_axes: Sequence[Optional[str]],
                 rules: Optional[Sequence[Tuple[str, Any]]] = None) -> P:
    """One logical-axes tuple → a PartitionSpec under `rules` (default:
    the active table). None entries stay None (explicitly replicated
    dims); a logical name absent from the table resolves to None too —
    an UNKNOWN name (not in the vocabulary) raises."""
    table = dict(rules if rules is not None else get_rules())
    out = []
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        if name not in LOGICAL_AXIS_SET:
            raise ValueError(
                f"unknown logical axis {name!r} — declare it in "
                "fengshen_tpu/sharding/axes.py (LOGICAL_AXES)")
        mesh_axis = table.get(name)
        out.append(tuple(mesh_axis) if isinstance(mesh_axis, list)
                   else mesh_axis)
    return P(*out) if out else P(None)


def to_partition_rules(
        param_axes: Sequence[Tuple[str, Sequence[Optional[str]]]],
        rules: Optional[Sequence[Tuple[str, Any]]] = None) -> list:
    """Resolve a model's ``PARAM_LOGICAL_AXES`` table (regex → logical
    tuple) into the regex → PartitionSpec list the whole existing
    machinery consumes (`match_partition_rules`, `make_shardings`,
    `create_sharded_state`, offload policy) — the migration seam that
    keeps every downstream consumer unchanged."""
    return [(pattern, resolve_spec(axes, rules))
            for pattern, axes in param_axes]


def with_logical_constraint(x, logical_axes: Sequence[Optional[str]],
                            rules: Optional[Sequence[Tuple[str, Any]]]
                            = None, mesh=None):
    """Constrain an ACTIVATION by logical-axis names — the declarative
    form of `parallel.with_sharding_constraint`. Outside a mesh scope
    it degrades to identity like the underlying helper, so model code
    can annotate unconditionally."""
    from fengshen_tpu.parallel.partition import with_sharding_constraint
    return with_sharding_constraint(x, resolve_spec(logical_axes, rules),
                                    mesh=mesh)
