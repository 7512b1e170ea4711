"""What the plain references share: the matmul they are told to use.

`highest` is float32 with every pass (on a TPU a float32 matmul runs in
fewer bf16 passes unless asked). The other entries are the controls of
"How correct is decided": the same reference computed one precision
step below what a configuration states — `int8` for a bf16
configuration (operands rounded to 8-bit integers per row and per
column, straight-through in the backward pass), `bf16` for a float32
one. A control stands where the program would and has to FAIL.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm_highest(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def _round_int8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.round(a / scale) * scale
    return a + jax.lax.stop_gradient(q - a)


def _mm_int8(x, w):
    x = _round_int8(x.astype(jnp.float32), -1)
    w = _round_int8(w.astype(jnp.float32), -2)
    return jnp.matmul(x, w, precision=HIGHEST)


def _round_fp8(a, axis):
    # e4m3 (3 mantissa bits), scaled per row or column to its range
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return a + jax.lax.stop_gradient(q - a)


def _mm_fp8(x, w):
    x = _round_fp8(x.astype(jnp.float32), -1)
    w = _round_fp8(w.astype(jnp.float32), -2)
    return jnp.matmul(x, w, precision=HIGHEST)


def _mm_bf16(x, w):
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


MATMULS = {"highest": _mm_highest, "int8": _mm_int8, "fp8": _mm_fp8,
           "bf16": _mm_bf16}

#: the control of a configuration that states this compute precision
CONTROL_OF = {"bfloat16": "int8", "float16": "int8", "float32": "bf16"}
