"""Plain float32 reference of the DeepSeek-V3 block, and its weights.

Moonlight-16B-A3B, Kimi-K2 and DeepSeek-V3 share this block (Hugging
Face ``DeepseekV3ForCausalLM``, arXiv:2412.19437, without the MTP
module).  Per layer, with RMSNorm ``w * x / sqrt(mean(x^2) + eps)``:

    h = RMSNorm(x)
    q = h Wq                     (S, H, nope + rope), q_lora_rank null
    c, k_pe = split(h Wkv_a)     (S, kv_lora), (S, rope)
    c = kv_a_layernorm(c)        eps 1e-6, the HF module's default
    k_nope, v = split(c Wkv_b)   (S, H, nope), (S, H, v), per head
    q_pe, k_pe = rope(q_pe), rope(k_pe)   k_pe shared by every head
    x = x + Wo . softmax((q . [k_nope, k_pe]) / sqrt(nope + rope)) v
    x = x + FFN(RMSNorm(x))

RoPE follows the HF code: the rope dims of q and k come in interleaved
pairs, which it de-interleaves (``view(d/2, 2).transpose``) and then
rotates by halves with inverse frequencies ``theta^(-2i/rope)``.
Attention is computed in the expanded form, keys and values built per
head from the latent, so the program's absorbed form is checked and not
copied.

The first ``first_k_dense_replace`` layers have a dense SwiGLU FFN of
``intermediate_size``; the rest have the expert layer:

    s     = sigmoid(h Wr)                     router over every expert
    top   = top_k(s + bias)                   bias selects, never weighs
    w     = s[top] / sum(s[top]) * routed_scaling_factor
    FFN   = sum over chosen held experts e of w_e SwiGLU_e(h)
            + SwiGLU_shared(h)                shared experts, no gate

Departure, as the deployment cut asks (``held_experts`` in the
configuration): the weights hold the chip's share of the routed experts,
``n_routed_experts`` of them from ``held_experts.first``, while the
router keeps all ``held_experts.router_width`` outputs.  What the absent
experts would add is left out, here as in the program, and that partial
result goes on to the next layer.  Each held expert runs over every
token and is weighed by its routing weight (0 where not chosen): no
sorting, grouping or capacity.  Only ``n_group`` = ``topk_group`` = 1
(no group-limited routing) and a null ``q_lora_rank`` are written here.

Every product runs at ``HIGHEST`` precision, so on a TPU it is a float32
product.  ``quant="fp8"`` rounds both operands of every product to
float8_e4m3fn with a per-tensor scale first, the lower-precision
control of ``harness/check.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn
LATENT_EPS = 1e-6


def dims(cfg: dict) -> dict:
    """The sizes the block needs, from a Hugging Face style config."""
    if cfg.get("q_lora_rank") or cfg.get("n_group", 1) != 1 or cfg.get(
            "topk_group", 1) != 1:
        raise NotImplementedError("q_lora_rank or group-limited routing")
    held = cfg.get("held_experts", {})
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], lora=cfg["kv_lora_rank"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        v=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
        dense=cfg["first_k_dense_replace"], k=cfg["num_experts_per_tok"],
        held=cfg["n_routed_experts"], first=held.get("first", 0),
        experts=held.get("router_width", cfg["n_routed_experts"]),
        shared=cfg["n_shared_experts"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def init_weights(cfg: dict, key) -> dict:
    """Random float32 weights, stacked over the dense and the expert
    layers.  Matrices are N(0, ``initializer_range``) and stored
    ``(in, out)``; norm weights are 1 + N(0, 0.1) in steps of 2^-10 and
    the router's selection bias N(0, 0.05), so that neither is an
    identity or a no-op the block could drop unnoticed.  The attention
    matrices keep their head axis (``wq`` (d, H, nope + rope), ``wkv_b``
    (kv_lora, H, nope + v), ``wo`` (H, v, d)), so that the sizes can be
    read off the weights.  ``head`` is ``(vocab, hidden)`` as in the
    published checkpoints."""
    n = dims(cfg)
    d, h, qk = n["d"], n["h"], n["nope"] + n["rope"]
    std = float(cfg.get("initializer_range", 0.02))
    ks = iter(jax.random.split(key, 32))

    def normal(shape, s=std):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    def norm(shape):
        # multiples of 2^-10 near 1: exact in float32 under any shift
        return 1.0 + jnp.round(normal(shape, 0.1) * 1024.0) / 1024.0

    def layers(nl):
        return {
            "ln1": norm((nl, d)), "ln2": norm((nl, d)),
            "wq": normal((nl, d, h, qk)),
            "wkv_a": normal((nl, d, n["lora"] + n["rope"])),
            "kv_norm": norm((nl, n["lora"])),
            "wkv_b": normal((nl, n["lora"], h, n["nope"] + n["vd"])),
            "wo": normal((nl, h, n["vd"], d)),
        }

    nd, nm = n["dense"], n["layers"] - n["dense"]
    fs, fe, e = n["shared"] * n["fe"], n["fe"], n["held"]
    dense = {**layers(nd), "w_gate": normal((nd, d, n["f"])),
             "w_up": normal((nd, d, n["f"])),
             "w_down": normal((nd, n["f"], d))}
    moe = {**layers(nm), "router": normal((nm, d, n["experts"])),
           "router_bias": normal((nm, n["experts"]), 0.05),
           "e_gate": normal((nm, e, d, fe)), "e_up": normal((nm, e, d, fe)),
           "e_down": normal((nm, e, fe, d)),
           "s_gate": normal((nm, d, fs)), "s_up": normal((nm, d, fs)),
           "s_down": normal((nm, fs, d))}
    return {"embed": normal((n["v"], d)), "head": normal((n["v"], d)),
            "final_norm": norm((d,)), "dense": dense, "moe": moe}


def _fp8(x):
    """Round to float8_e4m3fn with a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (S, H, rope) in the checkpoint's interleaved pair layout,
    position = row index; returned de-interleaved and rotated, as
    ``apply_rotary_pos_emb`` of the HF DeepSeek-V3 code returns it."""
    s, h, r = x.shape
    x = x.reshape(s, h, r // 2, 2).swapaxes(-1, -2).reshape(s, h, r)
    half = r // 2
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, g, u, dn, quant):
    return _mm(jax.nn.silu(_mm(h, g, quant)) * _mm(h, u, quant), dn, quant)


def _attention(x, p, n, causal, quant):
    s = x.shape[0]
    hh, nope, rope, vd, lora = n["h"], n["nope"], n["rope"], n["vd"], n["lora"]
    h = _rms(x, p["ln1"], n["eps"])
    q = _mm(h, p["wq"].reshape(h.shape[1], -1), quant).reshape(
        s, hh, nope + rope)
    ckv = _mm(h, p["wkv_a"], quant)
    c = _rms(ckv[:, :lora], p["kv_norm"], LATENT_EPS)
    kv = _mm(c, p["wkv_b"].reshape(lora, -1), quant).reshape(
        s, hh, nope + vd)
    k_pe = _rope(ckv[:, None, lora:], n["theta"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], n["theta"])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (s, hh, rope))], -1)
    v = kv[..., nope:]
    if quant == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / (
        nope + rope) ** 0.5
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    if quant == "fp8":
        pr = _fp8(pr)
    ctx = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
    return x + _mm(ctx.reshape(s, hh * vd), p["wo"].reshape(hh * vd, -1),
                   quant)


def route(h, p, n):
    """(S, d) normed hidden -> (the k experts chosen per row, (S, k), and
    the routing weight of each held expert, (S, held), 0 where the
    expert was not chosen)."""
    scores = jax.nn.sigmoid(jnp.matmul(h, p["router"], precision=HIGHEST))
    _, top = jax.lax.top_k(scores + p["router_bias"], n["k"])
    w = jnp.take_along_axis(scores, top, axis=-1)
    if n["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * n["scaling"]
    ids = n["first"] + jnp.arange(n["held"])
    return top, jnp.sum(jnp.where(top[:, :, None] == ids, w[:, :, None], 0.0),
                        axis=1)


def expert_ffn(h, p, n, quant=None):
    """The expert layer's FFN for (S, d) normed hidden states, and the
    experts chosen per row."""
    top, w = route(h, p, n)
    y = _swiglu(h, p["s_gate"], p["s_up"], p["s_down"], quant)
    for e in range(n["held"]):
        y = y + w[:, e:e + 1] * _swiglu(h, p["e_gate"][e], p["e_up"][e],
                                        p["e_down"][e], quant)
    return y, top


def _forward(w: dict, cfg: dict, tokens, quant=None):
    n = dims(cfg)
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def dense_layer(x, p):
        x = _attention(x, p, n, causal, quant)
        h = _rms(x, p["ln2"], n["eps"])
        return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], quant), None

    def moe_layer(x, p):
        x = _attention(x, p, n, causal, quant)
        y, top = expert_ffn(_rms(x, p["ln2"], n["eps"]), p, n, quant)
        return x + y, top

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(dense_layer, x, w["dense"])
    x, tops = jax.lax.scan(moe_layer, x, w["moe"])
    return _rms(x, w["final_norm"], n["eps"]), tops


def hidden(w: dict, cfg: dict, tokens, quant=None):
    """Final normed hidden states (S, d) of one sequence ``tokens``."""
    return _forward(w, cfg, tokens, quant)[0]


def routes(w: dict, cfg: dict, tokens):
    """The experts each expert layer chose for each row of ``tokens``:
    (expert layers, S, k)."""
    return _forward(w, cfg, tokens)[1]


def logits(w: dict, h, quant=None):
    """(rows, d) hidden states -> (rows, vocab) logits."""
    return _mm(h, w["head"].T, quant)
