"""Pallas TPU decode attention: one query token vs. a long KV cache.

Decode is memory-bound (arithmetic intensity ~1 flop/byte over the
cache), so the kernel's job is to stream the cache through VMEM in
(block_k, D) tiles exactly once while keeping the online-softmax state
(1, D) accumulator + running max/sum in VMEM.  Grid: (B, H, num_kv)
with the kv axis sequential.  Per-sequence valid length arrives via a
scalar-prefetch operand (SMEM) so masked tail blocks are skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   bk: int, n_kv: int, scale: float):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    kv_len = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * bk < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (1, bk)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_k", "interpret")
)
def decode_attention(q, k_cache, v_cache, kv_len, *, block_k: int = 256,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D); caches: (B, H, S, D); kv_len: (B,) -> (B, H, D)."""
    b, h, s, d = k_cache.shape
    assert s % block_k == 0, (s, block_k)
    n_kv = s // block_k
    scale = 1.0 / (d ** 0.5)
    q4 = q[:, :, None, :]  # (B, H, 1, D)

    kernel = functools.partial(
        _decode_kernel, bk=block_k, n_kv=n_kv, scale=scale
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi, ki, lens: (bi, hi, 0, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bi, hi, ki, lens: (bi, hi, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bi, hi, ki, lens: (bi, hi, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, d), lambda bi, hi, ki, lens: (bi, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q4, k_cache, v_cache)
    return out[:, :, 0, :]


# ---------------------------------------------------------------------------
# Paged decode attention (block/page KV layout)
# ---------------------------------------------------------------------------
#
# The KV cache lives in a pool of fixed-size pages (n_pages, H, ps, D);
# request b's logical token t sits at page_table[b, t // ps], offset
# t % ps.  The page table is a scalar-prefetch operand: the *index map*
# reads it to pick which physical page each grid step streams through
# VMEM, so the kernel never materializes a gathered contiguous cache.


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *,
                         ps: int, n_pg: int, scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(2)
    kv_len = len_ref[b]

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(pi * ps < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (ps, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (1, ps)
        kpos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pi == n_pg - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, page_table, kv_len, *,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); k/v_pages: (NP, Hkv, ps, D) with Hq % Hkv == 0
    (GQA: query head hi reads kv head hi // g through the index map —
    the shared pool is never replicated); page_table: (B, MP) int32
    (-1 = unallocated); kv_len: (B,).  Returns (B, Hq, D)."""
    b, h, d = q.shape
    n_pages, hkv, ps, _ = k_pages.shape
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    mp = page_table.shape[1]
    scale = 1.0 / (d ** 0.5)
    q4 = q[:, :, None, :]  # (B, Hq, 1, D)
    # Unallocated entries are masked via kv_len; clamp so the index map
    # still names a real page.
    pt = jnp.clip(page_table, 0, n_pages - 1).astype(jnp.int32)

    kernel = functools.partial(
        _paged_decode_kernel, ps=ps, n_pg=mp, scale=scale
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, mp),
        in_specs=[
            pl.BlockSpec(
                (1, 1, 1, d), lambda bi, hi, pi, pt, lens: (bi, hi, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda bi, hi, pi, pt, lens: (pt[bi, pi], hi // g, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda bi, hi, pi, pt, lens: (pt[bi, pi], hi // g, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, d), lambda bi, hi, pi, pt, lens: (bi, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pt, kv_len.astype(jnp.int32), q4, k_pages, v_pages)
    return out[:, :, 0, :]
