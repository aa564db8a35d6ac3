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
import math

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
# The KV cache lives in a pool of fixed-size pages (NP, Hkv, ps, D):
# request b's logical token t sits at page page_table[b, t // ps],
# offset t % ps, and one page holds that token range for every KV head,
# contiguous in HBM.
#
# One grid step does the whole call, so its cost follows the live pages:
#
# - Schedule.  The scalar core lists the live blocks, (lane, block) pairs
#   in lane order, where a block is P pages (P * ps tokens) and lane b
#   has cdiv(kv_len[b], P * ps) of them.  Lanes with kv_len 0 and blocks
#   past a lane's last token are never visited; such lanes output zeros.
# - DMA.  The pools stay in HBM (``pl.ANY``).  Each live page of a block
#   is fetched by its own copy, all Hkv heads at once, named by the
#   scalar-prefetched page table, into a VMEM buffer of (Hkv, P * ps, D)
#   per K and V.  Two buffers: the next block's copies (the next lane's
#   first block, across lanes) fly while this block computes.  Pages of a
#   block past the lane's last token are not fetched, so ``-1`` entries
#   there are never read.
# - Compute.  q arrives grouped by KV head, (B, Hkv, g, D) with
#   g = Hq // Hkv: per block and KV head one (g, D) x (D, P * ps) product
#   serves the g query heads that share the head's pages (GQA; MHA is
#   g = 1).  Tokens past kv_len are masked, and V's rows there zeroed so
#   that a buffer's stale rows never reach the sum.  The online-softmax
#   state per KV head (running max, sum and (g, D) accumulator) is f32 in
#   VMEM, as are K, V and q inside the products.  The heads run in
#   unrolled groups of up to _HEAD_GROUP, so that their independent
#   products overlap; a full unroll of 32 heads only lengthens every
#   program's lowering.
#
# P comes from the shapes alone: as many pages as fit one buffer of
# _BLOCK_BYTES, at most the page table's width.

_BLOCK_BYTES = 2 << 20
_HEAD_GROUP = 8


def pages_per_block(hkv: int, ps: int, d: int, dtype, mp: int) -> int:
    """P: the pages of one block, as many as fit one K (or V) buffer of
    ``_BLOCK_BYTES``, at least one and at most the table's ``mp``."""
    page_bytes = hkv * ps * d * jnp.dtype(dtype).itemsize
    return max(1, min(mp, _BLOCK_BYTES // page_bytes))


def _live_blocks(pt_ref, len_ref, lane_ref, blk_ref, pools, bufs, sem, *,
                 ppb: int):
    """The schedule both paged decode kernels walk: fills ``lane_ref``
    and ``blk_ref`` with the live (lane, block) pairs in lane order and
    returns ``(n_blocks, live, each_copy)``.  ``live(b)`` is the tokens
    lane b attends, at most the table's reach; ``each_copy(n, slot,
    act)`` calls ``act`` on the copies of schedule entry n's live pages,
    one per pool in ``pools`` (HBM, (NP, H, ps, D)), into buffer
    ``slot`` of the matching VMEM buffer in ``bufs``."""
    n_lanes, mp = pt_ref.shape
    n_pages, _, ps, _ = pools[0].shape
    bt = ppb * ps

    def live(b):
        return jnp.minimum(len_ref[b], mp * ps)

    def add_lane(b, n):
        def add(i, n):
            lane_ref[n] = b
            blk_ref[n] = i
            return n + 1
        return jax.lax.fori_loop(0, (live(b) + bt - 1) // bt, add, n)

    n_blocks = jax.lax.fori_loop(0, n_lanes, add_lane, 0)

    def each_copy(n, slot, act):
        b, first = lane_ref[n], blk_ref[n] * ppb
        n_live = jnp.minimum((live(b) + ps - 1) // ps - first, ppb)

        def page_copies(j, _):
            page = jnp.clip(pt_ref[b, first + j], 0, n_pages - 1)
            rows = pl.ds(pl.multiple_of(j * ps, ps), ps)
            for i, (hbm, buf) in enumerate(zip(pools, bufs)):
                act(pltpu.make_async_copy(hbm.at[page], buf.at[slot, :, rows],
                                          sem.at[i, slot]))

        jax.lax.fori_loop(0, n_live, page_copies, None)

    return n_blocks, live, each_copy


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sem, acc_ref, m_ref, l_ref,
                         lane_ref, blk_ref, *, ppb: int, scale: float):
    hkv, ps = k_hbm.shape[1], k_hbm.shape[2]
    bt = ppb * ps
    hg = math.gcd(hkv, _HEAD_GROUP)
    n_blocks, live, each_copy = _live_blocks(
        pt_ref, len_ref, lane_ref, blk_ref, (k_hbm, v_hbm), (k_buf, v_buf),
        sem, ppb=ppb)

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _():
        each_copy(0, 0, lambda c: c.start())

    def block(n, _):
        slot = n % 2
        b, blk = lane_ref[n], blk_ref[n]
        kv_len = live(b)

        @pl.when(n + 1 < n_blocks)
        def _():
            each_copy(n + 1, 1 - slot, lambda c: c.start())

        @pl.when(blk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        each_copy(n, slot, lambda c: c.wait())
        kpos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        vpos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)

        def heads(i, _):
            for u in range(hg):  # unrolled: the heads' products overlap
                h = i * hg + u
                q = q_ref[b, h].astype(jnp.float32)  # (g, d)
                k = k_buf[slot, h].astype(jnp.float32)  # (bt, d)
                v = jnp.where(vpos < kv_len, v_buf[slot, h].astype(jnp.float32),
                              0.0)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # (g, bt)
                s = jnp.where(kpos < kv_len, s, NEG_INF)
                m_prev = m_ref[h]  # (g, 1)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
                m_ref[h] = m_new
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

        jax.lax.fori_loop(0, hkv // hg, heads, None)

        @pl.when((blk + 1) * bt >= kv_len)
        def _():
            denom = jnp.maximum(l_ref[...], 1e-30)
            o_ref[b] = (acc_ref[...] / denom).astype(o_ref.dtype)

    jax.lax.fori_loop(0, n_blocks, block, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, page_table, kv_len, *,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); k/v_pages: (NP, Hkv, ps, D) with Hq % Hkv == 0
    (GQA: query head hi reads KV head hi // g, g = Hq // Hkv; the shared
    pool is never replicated); page_table: (B, MP) int32 (-1 =
    unallocated; entries past a lane's kv_len are never read); kv_len:
    (B,).  Returns (B, Hq, D)."""
    b, h, d = q.shape
    _, hkv, ps, _ = k_pages.shape
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    mp = page_table.shape[1]
    ppb = pages_per_block(hkv, ps, d, k_pages.dtype, mp)
    bt = ppb * ps
    n_sched = b * (-(-mp // ppb))

    kernel = functools.partial(
        _paged_decode_kernel, ppb=ppb, scale=1.0 / (d ** 0.5)
    )
    whole = pl.BlockSpec((b, hkv, g, d), lambda i, pt, lens: (0, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            whole,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2, hkv, bt, d), k_pages.dtype),
            pltpu.VMEM((2, hkv, bt, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.SMEM((n_sched,), jnp.int32),
            pltpu.SMEM((n_sched,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      q.reshape(b, hkv, g, d), k_pages, v_pages)
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# Paged MLA decode attention (latent pool)
# ---------------------------------------------------------------------------
#
# Latent attention's decode in the absorbed form (``models/attention.py``)
# is MQA over one latent "head": the pool (NP, 1, ps, W) holds each
# token's [c, k_r], zero-padded to W, a multiple of 128 lanes (a page's
# DMA refuses a narrower last dim); every query head scores the whole
# row with its [q_nope W_UK^T, q_rope, 0], and reads the row's first kv_lora
# columns back as its value.  The schedule, DMAs and double buffers are
# those of ``paged_decode_attention`` (g = Hq, Hkv = 1, and V the same
# buffer as K): each live page is fetched once per lane, and no page is
# ever expanded per head.  Per block: scores (Hq, P*ps), one online-
# softmax state per query head, and the (Hq, kv_lora) accumulator.


def _paged_mla_kernel(pt_ref, len_ref, q_ref, hbm, o_ref, buf, sem,
                      acc_ref, m_ref, l_ref, lane_ref, blk_ref, *,
                      ppb: int, scale: float):
    ps = hbm.shape[2]
    lora = o_ref.shape[-1]
    bt = ppb * ps
    n_blocks, live, each_copy = _live_blocks(
        pt_ref, len_ref, lane_ref, blk_ref, (hbm,), (buf,), sem, ppb=ppb)

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _():
        each_copy(0, 0, lambda c: c.start())

    def block(n, _):
        slot = n % 2
        b, blk = lane_ref[n], blk_ref[n]
        kv_len = live(b)

        @pl.when(n + 1 < n_blocks)
        def _():
            each_copy(n + 1, 1 - slot, lambda c: c.start())

        @pl.when(blk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        each_copy(n, slot, lambda c: c.wait())
        kpos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        vpos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        rows = buf[slot, 0].astype(jnp.float32)         # (bt, W)
        s = jax.lax.dot_general(
            q_ref[b].astype(jnp.float32), rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                       # (Hq, bt)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        v = jnp.where(vpos < kv_len, rows[:, :lora], 0.0)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when((blk + 1) * bt >= kv_len)
        def _():
            denom = jnp.maximum(l_ref[...], 1e-30)
            o_ref[b] = (acc_ref[...] / denom).astype(o_ref.dtype)

    jax.lax.fori_loop(0, n_blocks, block, None)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_mla_decode_attention(q_c, q_r, pages, page_table, kv_len, *,
                               scale: float,
                               interpret: bool = False) -> jax.Array:
    """q_c: (B, Hq, L) absorbed queries, q_r: (B, Hq, R) rotary queries;
    pages: (NP, 1, ps, W), each token's [latent, rotary key] zero-padded
    to W >= L + R, a multiple of 128;
    page_table: (B, MP) int32 (-1 = unallocated; entries past a lane's
    kv_len are never read); kv_len: (B,).  Returns (B, Hq, L): each
    head's softmax-weighted latents."""
    b, h, lora = q_c.shape
    _, _, ps, width = pages.shape
    pad = jnp.zeros((b, h, width - lora - q_r.shape[-1]), q_c.dtype)
    q = jnp.concatenate([q_c, q_r, pad], axis=-1)
    mp = page_table.shape[1]
    ppb = pages_per_block(1, ps, width, pages.dtype, mp)
    bt = ppb * ps
    n_sched = b * (-(-mp // ppb))

    kernel = functools.partial(_paged_mla_kernel, ppb=ppb, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, h, width), lambda i, pt, lens: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((b, h, lora), lambda i, pt, lens: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, bt, width), pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.VMEM((h, lora), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.SMEM((n_sched,), jnp.int32),
            pltpu.SMEM((n_sched,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lora), q_c.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32), q, pages)
