"""Dropless top-k mixture-of-experts FFN over the experts this chip holds.

The router scores every expert of the model (``MoEConfig.num_experts``)
and picks ``top_k`` per token.  The chip holds ``n_held`` of them, from
``held_offset`` on, and computes every assignment to a held expert:
there is no capacity, so a token's result never depends on what else is
in the batch.  Assignments to experts held elsewhere are left out; under
expert parallelism their part of the sum arrives from the chips that
hold them (on one chip the layer runs without that exchange).

Dispatch sorts the flat (token, k) assignment list by held expert, the
assignments to absent experts (and padding tokens) last, and runs the
experts' SwiGLU as three ``jax.lax.ragged_dot`` over the sorted rows,
one group per held expert; rows past the held groups are zeroed.
The combine scatters each row, times its routing weight, back onto its
token.  Shared experts (DeepSeek-V3) run for every token and are added
without a gate.

Two routers:

- ``softmax``: the k largest softmax probabilities are the weights;
- ``sigmoid`` (DeepSeek-V3's ``noaux_tc``): scores are
  ``sigmoid(logits)``; experts are *selected* on ``scores + bias`` and
  *weighted* by their unbiased scores.

Either renormalizes the k weights to sum to 1 and scales them by
``routed_scaling``.  The router's logits are float32 products at
``HIGHEST`` precision: one bf16 pass flips near-tied top-k choices, and
a flipped choice moves a served token's logits far.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import init_dense, silu, split_keys, swiglu

HIGHEST = jax.lax.Precision.HIGHEST


def moe_param_shapes(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, e, f = cfg.d_model, m.held, m.expert_d_ff
    shapes = {
        "w_router": (d, m.num_experts),
        "w_gate": (e, d, f),
        "w_up": (e, d, f),
        "w_down": (e, f, d),
    }
    if m.router == "sigmoid":
        shapes["router_bias"] = (m.num_experts,)
    if m.n_shared:
        fs = m.n_shared * f
        shapes.update(ws_gate=(d, fs), ws_up=(d, fs), ws_down=(fs, d))
    return shapes


MOE_PARAM_AXES = {
    "w_router": ("fsdp", None),
    "router_bias": (None,),
    "w_gate": ("experts", "fsdp", "ff"),
    "w_up": ("experts", "fsdp", "ff"),
    "w_down": ("experts", "ff", "fsdp"),
    "ws_gate": ("fsdp", "ff"),
    "ws_up": ("fsdp", "ff"),
    "ws_down": ("ff", "fsdp"),
}


def moe_param_axes(cfg: ModelConfig) -> dict:
    return {k: MOE_PARAM_AXES[k] for k in moe_param_shapes(cfg)}


def init_moe(cfg: ModelConfig, key, dtype) -> dict:
    shapes = moe_param_shapes(cfg)
    keys = split_keys(key, len(shapes))
    out = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name == "router_bias":
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = init_dense(k, shape, dtype=dtype)
    return out


def route(params: dict, xt: jax.Array, cfg: ModelConfig):
    """xt: (T, d) -> (probs (T, E) for the balance loss, chosen experts
    (T, k) int32, their weights (T, k) float32)."""
    m = cfg.moe
    logits = jnp.matmul(xt.astype(jnp.float32),
                        params["w_router"].astype(jnp.float32),
                        precision=HIGHEST)
    if m.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(
            scores + params["router_bias"].astype(jnp.float32), m.top_k)
        weight = jnp.take_along_axis(scores, sel, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        weight, sel = jax.lax.top_k(probs, m.top_k)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return probs, sel, weight * m.routed_scaling


def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes)


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig, valid=None):
    """x: (B, S, d); valid: optional (B, S) bool, False for padding
    tokens (routed nowhere, so they take no expert's time).

    Returns (y (B, S, d), aux) with aux holding the balance loss
    ``lb_loss`` and the load of the held experts: ``expert_tokens``
    (assignments computed) and ``experts_hit`` (held experts with at
    least one), both int32 scalars."""
    m = cfg.moe
    b, s, d = x.shape
    t, k, held = b * s, m.top_k, m.held
    xt = x.reshape(t, d)
    probs, sel, weight = route(params, xt, cfg)

    local = sel - m.held_offset
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & valid.reshape(t, 1)
    key = jnp.where(mine, local, held).reshape(t * k)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                    axis=0)[:held]
    n_rows = jnp.sum(sizes)
    tok = order // k
    rows = xt[tok]                                  # (T*k, d), by expert
    h = silu(_ragged(rows, params["w_gate"], sizes)) * _ragged(
        rows, params["w_up"], sizes)
    out = _ragged(h, params["w_down"], sizes)
    live = jnp.arange(t * k) < n_rows
    wk = jnp.where(mine, weight, 0.0).reshape(t * k)[order]
    out = jnp.where(live[:, None], out, 0.0) * wk[:, None].astype(out.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok].add(out.astype(x.dtype))

    if m.n_shared:
        y = y + swiglu(xt, *(params[n].astype(x.dtype)
                             for n in ("ws_gate", "ws_up", "ws_down")))

    # switch-style balance loss over the router's full width
    e = m.num_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(sel, e).sum(axis=1), axis=0)
    aux = {"lb_loss": e * jnp.sum(me * ce) / k,
           "expert_tokens": n_rows.astype(jnp.int32),
           "experts_hit": jnp.sum(sizes > 0).astype(jnp.int32)}
    return y.reshape(b, s, d), aux
