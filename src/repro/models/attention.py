"""GQA attention: chunked prefill, cached decode, local windows.

Memory discipline: prefill attention is computed in query *chunks* with
``lax.scan`` (flash-attention structure) so the (S, S) score matrix is
never materialized — required for the 32k/500k shape cells.  On TPU the
Pallas kernels in ``repro.kernels`` implement the same blocking in VMEM;
the jnp path here is the oracle and the CPU/dry-run implementation.

Sharding is expressed through logical axes (see
``repro.distributed.sharding``):

- archs whose head count divides the model axis shard heads (classic TP);
- small/odd-head archs (gemma3: 8 heads, qwen2.5: 40 heads vs a 16-way
  model axis) instead shard the *query-chunk rows* over the model axis
  (sequence-parallel attention) via the ``qblocks`` logical axis.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import apply_rope, init_dense, rms_norm, split_keys

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_param_shapes(cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    shapes = {
        "wq": (d, cfg.n_heads * hd),
        "wk": (d, cfg.n_kv_heads * hd),
        "wv": (d, cfg.n_kv_heads * hd),
        "wo": (cfg.n_heads * hd, d),
    }
    if cfg.qkv_bias:
        shapes["bq"] = (cfg.n_heads * hd,)
        shapes["bk"] = (cfg.n_kv_heads * hd,)
        shapes["bv"] = (cfg.n_kv_heads * hd,)
    return shapes


def init_attn(cfg: ModelConfig, key, dtype) -> dict:
    shapes = attn_param_shapes(cfg)
    keys = split_keys(key, len(shapes))
    out = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.startswith("b"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = init_dense(k, shape, dtype=dtype)
    return out


# Logical axes for attention params: (embed-in, fused-heads-out).  The
# fused head dim shards over 'heads' when the arch's head count divides
# the model axis (rules decide), else falls back to fsdp only.
ATTN_PARAM_AXES = {
    "wq": ("fsdp", "heads_fused"),
    "wk": ("fsdp", "kv_fused"),
    "wv": ("fsdp", "kv_fused"),
    "wo": ("heads_fused", "fsdp"),
    "bq": ("heads_fused",),
    "bk": ("kv_fused",),
    "bv": ("kv_fused",),
}


# ---------------------------------------------------------------------------
# QKV projection
# ---------------------------------------------------------------------------


def project_qkv(params: dict, x: jax.Array, cfg: ModelConfig, *,
                positions: jax.Array, kv_repeat: int = 1,
                use_rope: bool = True):
    """x: (B, S, d) -> q (B, Hq, S, hd), k/v (B, Hkv_eff, S, hd)."""
    hd = cfg.resolved_head_dim
    b, s, _ = x.shape
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if use_rope:
        q = apply_rope(q, positions[:, None, :], cfg.rope_theta)
        k = apply_rope(k, positions[:, None, :], cfg.rope_theta)
    if kv_repeat > 1:
        k = jnp.repeat(k, kv_repeat, axis=1)
        v = jnp.repeat(v, kv_repeat, axis=1)
    q = constrain(q, "batch", "heads", "seq", None)
    k = constrain(k, "batch", "kv_heads", "seq", None)
    v = constrain(v, "batch", "kv_heads", "seq", None)
    return q, k, v


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------


def _mask(q_pos, kv_pos, kv_len, *, causal: bool, window: int):
    """q_pos: (B, Q), kv_pos: (B, K), kv_len: (B,) -> bool (B, 1, Q, K)."""
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    valid = (kp >= 0) & (kp < kv_len[:, None, None])
    if causal:
        valid &= kp <= qp
    if window > 0:
        valid &= (qp - kp) < window
    return valid[:, None, :, :]


def _sdpa(q_blk, k, v, mask, scale):
    """q_blk: (B, Hkv, G, Qc, hd), k/v: (B, Hkv, K, hd), mask: (B,1,Qc,K)."""
    scores = jnp.einsum(
        "bhgqd,bhkd->bhgqk", q_blk, k, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(mask[:, :, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs.astype(v.dtype), v)
    return out


# ---------------------------------------------------------------------------
# Prefill / train attention (chunked over query blocks)
# ---------------------------------------------------------------------------


def chunked_attention(q, k, v, *, lens, causal: bool, window: int = 0,
                      q_chunk: int = 512,
                      unroll: bool = False) -> jax.Array:
    """Flash-structured attention.

    q: (B, Hq, S, hd); k, v: (B, Hkv_eff, S, hd); lens: (B,) valid lengths.
    Returns (B, Hq, S, hd).
    """
    b, hq, s, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = 1.0 / (hd ** 0.5)
    qc = min(q_chunk, s)
    n_chunks = -(-s // qc)
    pad = n_chunks * qc - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(b, hkv, g, n_chunks * qc, hd)
    kv_pos = jnp.broadcast_to(jnp.arange(s), (b, s))

    local = causal and window > 0 and window < s
    if local:
        # Only a (qc + window)-wide K band is relevant per chunk: padding
        # `window` zeros in front makes k_pad[start : start + band] cover
        # original positions [start - window, start + qc).
        band = qc + window
        end = n_chunks * qc - s  # keep the last chunk's slice in bounds
        k_pad = jnp.pad(k, ((0, 0), (0, 0), (window, end), (0, 0)))
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (window, end), (0, 0)))
        pos_pad = jnp.pad(
            kv_pos, ((0, 0), (window, end)), constant_values=-1
        )

    def body(carry, idx):
        start = idx * qc
        q_blk = jax.lax.dynamic_slice_in_dim(qg, start, qc, axis=3)
        q_blk = constrain(q_blk, "batch", "kv_heads", None, "qblocks", None)
        q_pos = start + jnp.arange(qc)
        q_pos_b = jnp.broadcast_to(q_pos, (b, qc))
        if local:
            # K band covering [start - window, start + qc)
            k_blk = jax.lax.dynamic_slice_in_dim(k_pad, start, band, axis=2)
            v_blk = jax.lax.dynamic_slice_in_dim(v_pad, start, band, axis=2)
            pos_blk = jax.lax.dynamic_slice_in_dim(pos_pad, start, band, axis=1)
        else:
            k_blk, v_blk, pos_blk = k, v, kv_pos
        m = _mask(q_pos_b, pos_blk, lens, causal=causal, window=window)
        out = _sdpa(q_blk, k_blk, v_blk, m, scale)
        return carry, out.astype(q.dtype)

    if unroll:
        # python loop so HLO cost analysis sees every chunk (dry-run)
        chunks = [body(None, jnp.asarray(i))[1] for i in range(n_chunks)]
        outs = jnp.stack(chunks)
    else:
        _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))
    # outs: (n_chunks, B, Hkv, G, qc, hd) -> (B, Hq, S, hd)
    outs = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, n_chunks * qc, hd)
    if pad:
        outs = outs[:, :, :s]
    return constrain(outs, "batch", "heads", "seq", None)


# ---------------------------------------------------------------------------
# Decode attention (one query token against a cache)
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, *, q_pos, kv_pos, kv_len,
                     causal: bool = True, window: int = 0) -> jax.Array:
    """q: (B, Hq, 1, hd); caches: (B, Hkv_eff, S, hd); q_pos/kv_len: (B,).

    kv_pos: (B, S) absolute positions held in each cache slot (-1 = empty).
    """
    b, hq, _, hd = q.shape
    hkv = k_cache.shape[1]
    g = hq // hkv
    scale = 1.0 / (hd ** 0.5)
    qg = q.reshape(b, hkv, g, 1, hd)
    m = _mask(q_pos[:, None], kv_pos, kv_len, causal=causal, window=window)
    out = _sdpa(qg, k_cache, v_cache, m, scale)
    return out.reshape(b, hq, 1, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV cache: scatter writes + chunk attention over a page pool
# ---------------------------------------------------------------------------


def update_paged_cache(k_pages, v_pages, page_table, k_new, v_new,
                       positions, valid):
    """Scatter a chunk of new K/V tokens into the page pool.

    k_pages/v_pages: (NP, H, ps, hd); page_table: (B, MP) int32;
    k_new/v_new: (B, H, C, hd); positions: (B, C) absolute token
    positions; valid: (B, C) bool — invalid rows (chunk padding, idle
    slots) are dropped by scattering out of bounds.
    """
    phys, off = _page_slots(k_pages.shape, page_table, positions, valid)
    kv = k_new.transpose(0, 2, 1, 3)                # (B, C, H, hd)
    vv = v_new.transpose(0, 2, 1, 3)
    k_pages = k_pages.at[phys, :, off, :].set(
        kv.astype(k_pages.dtype), mode="drop"
    )
    v_pages = v_pages.at[phys, :, off, :].set(
        vv.astype(v_pages.dtype), mode="drop"
    )
    return k_pages, v_pages


def _page_slots(pool_shape, page_table, positions, valid):
    """(physical page, offset) of each chunk token in a pool of
    ``pool_shape`` (NP, H, ps, hd); invalid rows (chunk padding, idle
    slots) get page NP, out of bounds, so a ``mode="drop"`` scatter
    drops them."""
    ps = pool_shape[2]
    logical = positions // ps                       # (B, C) page index
    off = positions % ps
    phys = jnp.take_along_axis(
        page_table, jnp.clip(logical, 0, page_table.shape[1] - 1), axis=1
    )
    phys = jnp.where(valid & (phys >= 0), phys, pool_shape[0])
    return phys, off


def paged_chunk_attention(q, k_pages, v_pages, page_table, *, q_pos,
                          kv_len, causal: bool = True) -> jax.Array:
    """Chunk of queries against a paged cache (gather path).

    q: (B, Hq, C, hd); pages: (NP, Hkv, ps, hd); page_table: (B, MP);
    q_pos: (B, C) absolute positions; kv_len: (B,) valid tokens
    (including this chunk).  Logical kv position of (page i, offset o)
    is i*ps + o, so masking is positional — stale data in reclaimed
    pages sits above q_pos and is masked by causality + kv_len.
    """
    from repro.kernels.ref import paged_gather
    b, hq, c, hd = q.shape
    hkv = k_pages.shape[1]
    g = hq // hkv
    scale = 1.0 / (hd ** 0.5)
    k = paged_gather(k_pages, page_table)
    v = paged_gather(v_pages, page_table)
    kv_pos = jnp.broadcast_to(jnp.arange(k.shape[2]), (b, k.shape[2]))
    m = _mask(q_pos, kv_pos, kv_len, causal=causal, window=0)
    out = _sdpa(q.reshape(b, hkv, g, c, hd), k, v, m, scale)
    return out.reshape(b, hq, c, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Local (sliding-window) ring cache helpers
# ---------------------------------------------------------------------------


def build_local_cache(k, v, lens, window: int):
    """Extract the last-`window` tokens into ring-buffer order.

    Slot i holds the most recent absolute position p < len with
    p % window == i (so decode writes at ``pos % window``).
    k, v: (B, H, S, hd) -> (B, H, window, hd); returns (k, v, pos (B, W)).
    """
    b, h, s, hd = k.shape
    w = window
    i = jnp.arange(w)
    last = lens[:, None] - 1  # (B, 1)
    p = last - ((last - i) % w)  # (B, W) candidate absolute positions
    valid = (p >= 0) & (p < lens[:, None]) & (p > last - w)
    p_gather = jnp.clip(p, 0, s - 1)
    kc = jnp.take_along_axis(k, p_gather[:, None, :, None], axis=2)
    vc = jnp.take_along_axis(v, p_gather[:, None, :, None], axis=2)
    pos = jnp.where(valid, p, -1)
    kc = jnp.where(valid[:, None, :, None], kc, 0)
    vc = jnp.where(valid[:, None, :, None], vc, 0)
    return kc, vc, pos


def update_cache(k_cache, v_cache, kv_pos, k_new, v_new, pos, *,
                 window: int = 0):
    """Insert one token per sequence into a (ring or linear) cache.

    k_cache/v_cache: (B, H, S, hd); k_new/v_new: (B, H, 1, hd);
    pos: (B,) absolute position of the new token.
    """
    b = k_cache.shape[0]
    slot = pos % window if window > 0 else pos
    bidx = jnp.arange(b)
    k_cache = k_cache.at[bidx, :, slot, :].set(k_new[:, :, 0, :])
    v_cache = v_cache.at[bidx, :, slot, :].set(v_new[:, :, 0, :])
    kv_pos = kv_pos.at[bidx, slot].set(pos)
    return k_cache, v_cache, kv_pos


# ---------------------------------------------------------------------------
# Multi-head latent attention (MLA, DeepSeek-V2/V3)
# ---------------------------------------------------------------------------
#
# Per token the cache keeps one latent c (kv_lora_rank wide, RMS-normed)
# and one rotary key k_r (qk_rope_head_dim wide) shared by every head;
# head h's key is [c W_UK_h, k_r] and its value c W_UV_h.  Attention is
# computed in the absorbed form throughout the served path:
#
#   score_h(t) = (q_nope_h W_UK_h^T) . c_t + q_rope_h . k_r_t
#   out_h      = (sum_t p_h(t) c_t) W_UV_h
#
# so the pool, one [c, k_r] row of kv_lora + rope per token (zero-padded
# to the lane tiling, 576 -> 640, for the kernel's page DMAs), is read as
# one 576-wide "head" that 16 query heads share (MQA over the latent; the
# values are its first kv_lora columns) and is never expanded per head.
# Params:
#   wq (d, H*(nope+rope)), wkv_a (d, kv_lora + rope), kv_norm (kv_lora,),
#   wk_b (H, nope, kv_lora) = W_UK_h^T, wv_b (H, kv_lora, v), wo (H*v, d).
# The rotary dims use the rotate-half layout of ``apply_rope``; the
# latent's RMSNorm takes eps 1e-6 (the HF module's default), whatever
# the model's.

LATENT_EPS = 1e-6


def mla_param_shapes(cfg: ModelConfig) -> dict:
    a, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    return {
        "wq": (d, h * a.qk_head_dim),
        "wkv_a": (d, a.latent_dim),
        "kv_norm": (a.kv_lora_rank,),
        "wk_b": (h, a.qk_nope_head_dim, a.kv_lora_rank),
        "wv_b": (h, a.kv_lora_rank, a.v_head_dim),
        "wo": (h * a.v_head_dim, d),
    }


MLA_PARAM_AXES = {
    "wq": ("fsdp", "heads_fused"),
    "wkv_a": ("fsdp", None),
    "kv_norm": (None,),
    "wk_b": ("heads", None, None),
    "wv_b": ("heads", None, None),
    "wo": ("heads_fused", "fsdp"),
}


def init_mla(cfg: ModelConfig, key, dtype) -> dict:
    shapes = mla_param_shapes(cfg)
    keys = split_keys(key, len(shapes))
    out = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.endswith("_norm"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = init_dense(k, shape, dtype=dtype)
    return out


def mla_project(params: dict, x: jax.Array, cfg: ModelConfig, *,
                positions: jax.Array):
    """x: (B, S, d) -> absorbed query (q_c (B, H, S, kv_lora), q_r
    (B, H, S, rope)) and the tokens' cache entries (c (B, S, kv_lora),
    k_r (B, S, rope)), RoPE applied at ``positions`` (B, S)."""
    a, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    q = x @ params["wq"].astype(x.dtype)
    q = q.reshape(b, s, h, a.qk_head_dim).transpose(0, 2, 1, 3)
    q_nope = q[..., :a.qk_nope_head_dim]
    q_r = apply_rope(q[..., a.qk_nope_head_dim:], positions[:, None, :],
                     cfg.rope_theta)
    q_c = jnp.einsum("bhsn,hnc->bhsc", q_nope,
                     params["wk_b"].astype(x.dtype))
    kv = x @ params["wkv_a"].astype(x.dtype)
    c = rms_norm(kv[..., :a.kv_lora_rank], params["kv_norm"], LATENT_EPS)
    k_r = apply_rope(kv[..., a.kv_lora_rank:], positions, cfg.rope_theta)
    return q_c, q_r, c, k_r


def mla_output(params: dict, ctx_c: jax.Array) -> jax.Array:
    """ctx_c: (B, H, S, kv_lora) attention-weighted latents -> (B, S, d)."""
    b, h, s, _ = ctx_c.shape
    o = jnp.einsum("bhsc,hcv->bshv", ctx_c,
                   params["wv_b"].astype(ctx_c.dtype))
    return o.reshape(b, s, -1) @ params["wo"].astype(ctx_c.dtype)


def mla_scale(cfg: ModelConfig) -> float:
    return 1.0 / (cfg.mla.qk_head_dim ** 0.5)


def _mla_sdpa(q, latent, mask, scale, lora: int):
    """q: (B, H, Q, lora + rope) absorbed queries; latent: (B, K, lora +
    rope), each token's [c, k_r]; mask: (B, 1, Q, K) -> (B, H, Q, lora):
    the scores read the whole latent, the values its first ``lora``."""
    scores = jnp.einsum("bhqc,bkc->bhqk", q, latent,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkc->bhqc", probs.astype(latent.dtype),
                      latent[..., :lora])


def mla_attention(q_c, q_r, c, k_r, *, lens, scale) -> jax.Array:
    """Causal MLA over a whole sequence (no cache): (B, H, S, kv_lora)."""
    b, s = c.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    m = _mask(pos, pos, lens, causal=True, window=0)
    q = jnp.concatenate([q_c, q_r], axis=-1)
    latent = jnp.concatenate([c, k_r], axis=-1)
    return _mla_sdpa(q, latent, m, scale, c.shape[-1]).astype(q_c.dtype)


def update_latent_pages(pages, page_table, c_new, r_new, positions, valid):
    """Scatter a chunk's latents into the latent pool, laid out as a
    one-head pool (NP, 1, ps, W) whose rows are [c, k_r, 0...] (W is
    kv_lora + rope padded to the lane tiling): c_new (B, C, kv_lora),
    r_new (B, C, rope)."""
    pad = pages.shape[-1] - c_new.shape[-1] - r_new.shape[-1]
    latent = jnp.concatenate(
        [c_new, r_new, jnp.zeros(c_new.shape[:-1] + (pad,), c_new.dtype)],
        axis=-1)                                    # (B, C, W)
    phys, off = _page_slots(pages.shape, page_table, positions, valid)
    return pages.at[phys, 0, off, :].set(latent.astype(pages.dtype),
                                         mode="drop")


def paged_mla_chunk_attention(q_c, q_r, pages, page_table, *, q_pos,
                              kv_len, chunk_lens, scale) -> jax.Array:
    """A chunk of queries against the latent pool (gather path).

    q_c: (B, H, C, kv_lora), q_r: (B, H, C, rope); pages (NP, 1, ps,
    kv_lora + rope); page_table (B, MP); q_pos (B, C); kv_len (B,)
    tokens including the chunk's.  Rows run one at a time (a row's
    scores are (H, C, MP*ps)) and a row with no tokens in the chunk is
    skipped.
    """
    from repro.kernels.ref import paged_gather

    lora = q_c.shape[-1]
    q = jnp.concatenate([q_c, q_r], axis=-1)
    width = q.shape[-1]

    def row(args):
        qr, pt, qp, n, live = args

        def attend(_):
            latent = paged_gather(pages, pt[None])[:, 0, :, :width]
            kv_pos = jnp.arange(latent.shape[1])[None]
            m = _mask(qp[None], kv_pos, n[None], causal=True, window=0)
            return _mla_sdpa(qr[None], latent, m, scale, lora)[0]

        return jax.lax.cond(live > 0, attend,
                            lambda _: jnp.zeros(qr.shape[:-1] + (lora,),
                                                qr.dtype), None)

    out = jax.lax.map(row, (q, page_table, q_pos, kv_len, chunk_lens))
    return out.astype(q_c.dtype)


def paged_mla_decode_attention(q_c, q_r, pages, page_table, kv_len, *,
                               scale) -> jax.Array:
    """One query token per lane against the latent pool (jnp path):
    q_c (B, H, kv_lora), q_r (B, H, rope) -> (B, H, kv_lora)."""
    from repro.kernels.ref import paged_gather

    q = jnp.concatenate([q_c, q_r], axis=-1)[:, :, None]
    latent = paged_gather(pages, page_table)[:, 0, :, :q.shape[-1]]
    b, t = latent.shape[:2]
    kv_pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    m = _mask((kv_len - 1)[:, None], kv_pos, kv_len, causal=True, window=0)
    out = _mla_sdpa(q, latent, m, scale, q_c.shape[-1])
    return out[:, :, 0].astype(q_c.dtype)
