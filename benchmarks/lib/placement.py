"""Where a plain reference's arrays live when a cell spans chips: each
array split along its largest dimension the chips divide, rows split
over the chips. The mathematics is untouched; XLA inserts the
transfers."""

from __future__ import annotations

import numpy as np


def placer(chips: int):
    """`place(x, kind)` for `chips` devices; None on one chip."""
    if chips == 1:
        return None
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:chips]), ("d",))

    def sharding_of(shape):
        dims = [i for i, n in enumerate(shape) if n % chips == 0 and n > 1]
        if not dims:
            return NamedSharding(mesh, P())
        axis = max(dims, key=lambda i: shape[i])
        return NamedSharding(mesh, P(*[("d" if i == axis else None)
                                       for i in range(len(shape))]))

    def place(x, kind):
        if kind == "sharding":          # x is a shape
            return sharding_of(tuple(x))
        if kind == "rows":
            return jax.device_put(x, NamedSharding(mesh, P("d")))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sharding_of(np.shape(a))), x)
    return place
