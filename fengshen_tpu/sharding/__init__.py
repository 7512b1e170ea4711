"""Declarative logical-axis sharding (docs/sharding.md).

Named logical axes (`axes.LOGICAL_AXES`) + ONE rules table
(`rules.DEFAULT_LOGICAL_AXIS_RULES`: logical axis → mesh axis or None)
replace per-model hand-written PartitionSpec regex tables. Models
declare ``PARAM_LOGICAL_AXES`` (regex → logical tuple);
:func:`to_partition_rules` resolves them against the active table into
the regex → PartitionSpec lists the existing partition/trainer/offload
machinery consumes unchanged; :func:`with_logical_constraint`
annotates activations.
"""

from fengshen_tpu.sharding.axes import LOGICAL_AXES, LOGICAL_AXIS_SET
from fengshen_tpu.sharding.rules import (DEFAULT_LOGICAL_AXIS_RULES,
                                         get_rules, resolve_spec,
                                         set_rules, to_partition_rules,
                                         use_rules,
                                         validate_rules,
                                         with_logical_constraint)

__all__ = [
    "LOGICAL_AXES",
    "LOGICAL_AXIS_SET",
    "DEFAULT_LOGICAL_AXIS_RULES",
    "get_rules",
    "set_rules",
    "use_rules",
    "validate_rules",
    "resolve_spec",
    "to_partition_rules",
    "with_logical_constraint",
]
