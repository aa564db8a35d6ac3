"""Pallas TPU kernel for the Mamba-2 SSD chunk recurrence.

The SSD computation is a sequential scan over chunks with a per-(batch,
head) state matrix (P, N).  On TPU we map grid = (B, H, num_chunks) with
the chunk axis innermost/sequential and keep the running state in a VMEM
scratch that persists across chunk steps (it is reset at chunk 0).  Per
step the working set is the (Q, P) x-chunk, (Q, N) B/C chunks, the
(Q, Q) intra-chunk decay matrix and the (P, N) state — for the
production config (Q=256, P=64, N=128) that is ~1 MiB, comfortably
inside VMEM, and every matmul dim is a multiple of 64/128 (MXU aligned).

This is the TPU-native adaptation of the paper-adjacent GPU SSD kernel:
instead of warp-level parallel prefix sums, the intra-chunk term is a
dense (Q, Q) matmul on the MXU and the inter-chunk recurrence rides the
sequential grid axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, q: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (1, Q) -> (Q,)
    dt = dt.reshape(q)
    a = a_ref[0, 0]  # scalar
    b = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)  # (Q, N)

    da = dt * a  # (Q,) negative decay exponents
    cum = jnp.cumsum(da)  # (Q,)

    # ---- intra-chunk (quadratic) term ----
    diff = cum[:, None] - cum[None, :]  # (Q, Q)
    iq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l_mat = jnp.where(ik <= iq, jnp.exp(diff), 0.0)
    cb = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    m = cb * l_mat * dt[None, :]
    y = jax.lax.dot_general(
        m, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    # ---- carried-state contribution ----
    state = state_ref[...]  # (P, N)
    y_off = jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, P)
    y = y + y_off * jnp.exp(cum)[:, None]

    # ---- state update ----
    decay_out = jnp.exp(cum[-1] - cum)  # (Q,)
    xw = x * (dt * decay_out)[:, None]  # (Q, P)
    new_state = state * jnp.exp(cum[-1]) + jax.lax.dot_general(
        xw, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P, N)
    state_ref[...] = new_state

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_out_ref[0, 0] = new_state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b_mat, c_mat, *, chunk: int = 256,
        interpret: bool = False):
    """Chunked SSD, single B/C group.

    x: (B, S, H, P); dt: (B, S, H); a: (H,) negative;
    b_mat, c_mat: (B, S, N).
    Returns (y: (B, S, H, P), final_state: (B, H, P, N) f32).
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    # layout for clean blocking: (B, H, NC, Q, ...)
    xk = x.transpose(0, 2, 1, 3).reshape(bsz, h, nc, chunk, p)
    dtk = dt.transpose(0, 2, 1).reshape(bsz, h, nc, 1, chunk)
    bk = b_mat.reshape(bsz, nc, chunk, n)
    ck = c_mat.reshape(bsz, nc, chunk, n)
    a2 = a.reshape(h, 1).astype(jnp.float32)

    kernel = functools.partial(_ssd_kernel, q=chunk, n_chunks=nc)
    y, final_state = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1), lambda bi, hi, ci: (hi, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, nc, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xk, dtk, a2, bk, ck)
    y = y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
    return y, final_state
