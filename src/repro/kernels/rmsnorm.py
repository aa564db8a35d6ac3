"""Pallas TPU fused RMSNorm.

Trivial but load-bearing: RMSNorm appears 2x per layer and in the jnp
path costs three HBM round-trips (square-mean, rsqrt-scale, affine).
The kernel fuses them into one read + one write per (rows, D) tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + s_ref[...].astype(jnp.float32))).astype(
        o_ref.dtype
    )


@functools.partial(
    jax.jit, static_argnames=("eps", "block_rows", "interpret")
)
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False) -> jax.Array:
    """x: (..., D); scale: (D,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for dim in x.shape[:-1]:
        rows *= dim
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x2, scale)
    return out.reshape(orig_shape)
