"""fslint rule modules — importing this package registers every rule.

To add rule 7: drop a module here with a ``@register``-decorated
``Rule`` subclass (~50 lines, see any sibling) and import it below.
"""

from fengshen_tpu.analysis.rules import (  # noqa: F401
    blanket_except,
    blocking_transfer,
    blocking_under_lock,
    donated_buffer_use,
    host_divergence,
    lock_order,
    metric_contract,
    metrics_in_traced_code,
    nondet_iteration,
    partition_spec_axes,
    resource_lifecycle,
    retrace_hazard,
    unguarded_shared_state,
)
