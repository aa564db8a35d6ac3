"""Pallas TPU flash attention (prefill), causal + sliding-window.

Tiling: grid (batch*heads, num_q_blocks, num_kv_blocks) with the kv axis
innermost ("arbitrary" = sequential on TPU), so the VMEM working set per
step is one (Bq, D) query block, one (Bk, D) key/value block and the
(Bq, D) f32 accumulator + (Bq,) running max/sum — the classic online
softmax.  Block sizes default to 128/256: multiples of the 128-wide MXU
and small enough that Bq*D + 2*Bk*D + Bq*Bk floats stay well under the
~16 MiB/core VMEM budget at D=128.

Fully-masked kv blocks (above the causal diagonal or outside the local
window) are skipped with ``pl.when`` — on real TPU this halves causal
prefill work; the jnp fallback cannot skip, which is exactly the gap the
roofline's MODEL_FLOPS/HLO_FLOPs ratio exposes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, n_kv: int, causal: bool, window: int,
                  scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level reachability: any (q, k) pair in range?
    reachable = True
    if causal:
        reachable = k_start <= q_start + bq - 1
    if window > 0:
        reachable = jnp.logical_and(
            reachable, q_start - (k_start + bk - 1) < window
        )

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q, k, v: (B, H, S, D) -> (B, H, S, D)."""
    b, h, s, d = q.shape
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    n_q = s // block_q
    n_kv = s // block_k
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _flash_kernel, bq=block_q, bk=block_k, n_kv=n_kv, causal=causal,
        window=window, scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, qi, ki: (g, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, qi, ki: (g, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, qi, ki: (g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda g, qi, ki: (g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
