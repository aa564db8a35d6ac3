"""Mamba-2 (SSD — state-space duality) block, chunked for memory.

Prefill/train runs the chunked SSD algorithm as a sequential
``lax.scan`` over chunks (the within-chunk quadratic term only ever
materializes one (B, H, Q, Q) decay matrix at a time — required for the
train_4k and 500k cells).  Decode is the O(1) recurrent state update.
The Pallas kernel in ``repro.kernels.ssd`` implements the same chunk
loop with VMEM-resident state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import init_dense, rms_norm, silu, split_keys


def mamba_dims(cfg: ModelConfig):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    h = s.n_heads(cfg.d_model)
    return di, h, s.d_state, s.n_groups, s.head_dim, s.conv_width


def mamba_param_shapes(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    di, h, n, g, _, cw = mamba_dims(cfg)
    return {
        "w_z": (d, di),
        "w_x": (d, di),
        "w_bc": (d, 2 * g * n),
        "w_dt": (d, h),
        "dt_bias": (h,),
        "conv_x": (cw, di),
        "conv_bc": (cw, 2 * g * n),
        "A_log": (h,),
        "D": (h,),
        "norm_scale": (di,),
        "w_out": (di, d),
    }


MAMBA_PARAM_AXES = {
    "w_z": ("fsdp", "ssm_inner"),
    "w_x": ("fsdp", "ssm_inner"),
    "w_bc": ("fsdp", None),
    "w_dt": ("fsdp", "ssm_heads"),
    "dt_bias": ("ssm_heads",),
    "conv_x": (None, "ssm_inner"),
    "conv_bc": (None, None),
    "A_log": ("ssm_heads",),
    "D": ("ssm_heads",),
    "norm_scale": ("ssm_inner",),
    "w_out": ("ssm_inner", "fsdp"),
}


def init_mamba(cfg: ModelConfig, key, dtype) -> dict:
    shapes = mamba_param_shapes(cfg)
    keys = split_keys(key, len(shapes))
    out = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name == "A_log":
            out[name] = jnp.log(
                jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)
            ).astype(dtype)
        elif name == "dt_bias":
            # dt ~ softplus^-1 of U(1e-3, 1e-1)
            dt = jax.random.uniform(k, shape, jnp.float32, 1e-3, 1e-1)
            out[name] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        elif name == "D":
            out[name] = jnp.ones(shape, dtype)
        elif name == "norm_scale":
            out[name] = jnp.zeros(shape, dtype)
        elif name.startswith("conv"):
            out[name] = init_dense(k, shape, dtype=dtype)
        else:
            out[name] = init_dense(k, shape, dtype=dtype)
    return out


# ---------------------------------------------------------------------------
# Causal depthwise conv
# ---------------------------------------------------------------------------


def causal_conv(x: jax.Array, w: jax.Array, state: jax.Array | None = None):
    """x: (B, S, C), w: (cw, C).  state: (B, cw-1, C) history or None.

    Returns (y: (B, S, C), new_state: (B, cw-1, C)).
    """
    cw = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], cw - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, S+cw-1, C)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(cw)
    )
    new_state = xp[:, -(cw - 1):, :] if cw > 1 else state
    return y, new_state


# ---------------------------------------------------------------------------
# Chunked SSD scan (prefill / train)
# ---------------------------------------------------------------------------


def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk: int, init_state=None,
             unroll: bool = False):
    """Chunked SSD.

    x: (B, S, H, P); dt: (B, S, H) (post-softplus); a: (H,) negative;
    b_mat/c_mat: (B, S, G, N) with H % G == 0.
    Returns (y: (B, S, H, P), final_state: (B, H, P, N)).
    """
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    hg = h // g
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def to_chunks(t):
        return t.reshape((bsz, nc, q) + t.shape[2:]).swapaxes(0, 1)

    xc, dtc, bc, cc = map(to_chunks, (x, dt, b_mat, c_mat))
    # per-chunk leading axis nc for lax.scan
    da = dtc * a  # (nc, B, Q, H) negative decay exponents

    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    # pre-broadcast C from groups to heads so every einsum is head-indexed
    cc_h = jnp.repeat(cc, hg, axis=3)  # (nc, B, Q, H, N)

    def body(state, inp):
        xq, dtq, daq, bq, cqh = inp
        cum = jnp.cumsum(daq, axis=1)
        diff = cum[:, :, None, :] - cum[:, None, :, :]
        iq = jnp.arange(q)
        tri = (iq[:, None] >= iq[None, :])[None, :, :, None]
        l_mat = jnp.where(tri, jnp.exp(diff), 0.0)
        cb = jnp.einsum(
            "bqhn,bkhn->bhqk",
            cqh.astype(jnp.float32),
            jnp.repeat(bq, hg, axis=2).astype(jnp.float32),
        )
        m = cb * l_mat.transpose(0, 3, 1, 2) * dtq.transpose(0, 2, 1)[:, :, None, :]
        y_diag = jnp.einsum("bhqk,bkhp->bqhp", m, xq.astype(jnp.float32))
        y_off = jnp.einsum("bqhn,bhpn->bqhp", cqh.astype(jnp.float32), state)
        y_off = y_off * jnp.exp(cum)[..., None]
        decay_out = jnp.exp(cum[:, -1:, :] - cum)
        contrib = (dtq * decay_out)[..., None, None] * (
            jnp.repeat(bq, hg, axis=2)[:, :, :, None, :] * xq[..., :, None]
        ).astype(jnp.float32)
        new_state = state * jnp.exp(cum[:, -1, :])[:, :, None, None] + (
            contrib.sum(axis=1)
        )
        return new_state, (y_diag + y_off).astype(x.dtype)

    if unroll:
        state = init_state
        ys = []
        for i in range(nc):
            state, yi = body(
                state, (xc[i], dtc[i], da[i], bc[i], cc_h[i])
            )
            ys.append(yi)
        final_state, yc = state, jnp.stack(ys)
    else:
        final_state, yc = jax.lax.scan(
            body, init_state, (xc, dtc, da, bc, cc_h)
        )
    y = yc.swapaxes(0, 1).reshape(bsz, nc * q, h, p)
    if pad:
        y = y[:, :s]
    return y, final_state


def ssd_decode_step(state, x_t, dt_t, a, b_t, c_t):
    """One-token SSD update.

    state: (B, H, P, N) f32; x_t: (B, H, P); dt_t: (B, H);
    b_t/c_t: (B, G, N).  Returns (y: (B, H, P), new_state).
    """
    h = x_t.shape[1]
    g = b_t.shape[1]
    hg = h // g
    bh = jnp.repeat(b_t, hg, axis=1).astype(jnp.float32)  # (B, H, N)
    ch = jnp.repeat(c_t, hg, axis=1).astype(jnp.float32)
    da = jnp.exp(dt_t * a)  # (B, H)
    new_state = state * da[..., None, None] + (
        dt_t[..., None, None]
        * bh[:, :, None, :]
        * x_t.astype(jnp.float32)[..., None]
    )
    y = jnp.einsum("bhpn,bhn->bhp", new_state, ch)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# Full block
# ---------------------------------------------------------------------------


def mamba_block(params: dict, x: jax.Array, cfg: ModelConfig, *,
                conv_state=None, ssm_state=None, decode: bool = False,
                unroll: bool = False,
                lens=None):
    """x: (B, S, d) -> (y: (B, S, d), (conv_state, ssm_state)).

    `lens` (B,) marks right-padded prompts: pad positions get dt = 0 so
    the SSM state freezes at each sequence's true end, and the conv
    state is gathered from the last `conv_width-1` *valid* positions.
    """
    di, h, n, g, p, cw = mamba_dims(cfg)
    bsz, s, _ = x.shape
    dt_f = x @ params["w_dt"].astype(x.dtype)
    z = x @ params["w_z"].astype(x.dtype)
    xs = x @ params["w_x"].astype(x.dtype)
    bc = x @ params["w_bc"].astype(x.dtype)
    xs = constrain(xs, "batch", "seq", "ssm_inner")
    z = constrain(z, "batch", "seq", "ssm_inner")

    xs_raw, bc_raw = xs, bc
    xs, conv_x_state = causal_conv(
        xs, params["conv_x"].astype(x.dtype),
        None if conv_state is None else conv_state["x"],
    )
    bc, conv_bc_state = causal_conv(
        bc, params["conv_bc"].astype(x.dtype),
        None if conv_state is None else conv_state["bc"],
    )
    xs = silu(xs)
    bc = silu(bc)
    b_mat = bc[..., : g * n].reshape(bsz, s, g, n)
    c_mat = bc[..., g * n:].reshape(bsz, s, g, n)

    dt = jax.nn.softplus(
        dt_f.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )  # (B, S, H)
    if lens is not None and not decode:
        valid = (jnp.arange(s)[None, :] < lens[:, None])  # (B, S)
        dt = dt * valid[..., None]  # pad positions: no state update
    a = -jnp.exp(params["A_log"].astype(jnp.float32))  # (H,)
    xh = xs.reshape(bsz, s, h, p)

    if decode:
        assert s == 1
        y_t, new_ssm = ssd_decode_step(
            ssm_state, xh[:, 0], dt[:, 0], a, b_mat[:, 0], c_mat[:, 0]
        )
        y = y_t[:, None]
    else:
        y, new_ssm = ssd_scan(
            xh, dt, a, b_mat, c_mat, chunk=cfg.ssm.chunk_size,
            init_state=ssm_state, unroll=unroll,
        )
    d_skip = params["D"].astype(jnp.float32)[None, None, :, None]
    y = (y.astype(jnp.float32)
         + d_skip * xh.astype(jnp.float32)).astype(x.dtype)
    y = y.reshape(bsz, s, di)
    y = constrain(y, "batch", "seq", "ssm_inner")
    y = rms_norm(y * silu(z), params["norm_scale"], cfg.norm_eps)
    out = y @ params["w_out"].astype(x.dtype)
    if lens is not None and not decode:
        conv_x_state = _gather_conv_state(
            xs_raw, lens, cw,
            None if conv_state is None else conv_state["x"],
        )
        conv_bc_state = _gather_conv_state(
            bc_raw, lens, cw,
            None if conv_state is None else conv_state["bc"],
        )
    new_conv = {"x": conv_x_state, "bc": conv_bc_state}
    return out, (new_conv, new_ssm)


def _gather_conv_state(raw: jax.Array, lens: jax.Array, cw: int,
                       prior=None):
    """Last (cw-1) *valid* pre-activation conv inputs per sequence.

    raw: (B, S, C) pre-conv projections; returns (B, cw-1, C).  For a
    continuation chunk (chunked prefill), `prior` is the previous conv
    state so short chunks (lens < cw-1) still see earlier tokens.
    """
    b, s, c = raw.shape
    front = (prior.astype(raw.dtype) if prior is not None
             else jnp.zeros((b, cw - 1, c), raw.dtype))
    xp = jnp.concatenate([front, raw], axis=1)
    idx = lens[:, None] + jnp.arange(cw - 1)[None, :]  # (B, cw-1)
    return jnp.take_along_axis(xp, idx[:, :, None], axis=1)
