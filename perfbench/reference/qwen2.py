"""Plain float32 reference of the dense Qwen2 block, and its weights.

Qwen1.5 and Qwen2.5 share this block (Hugging Face ``Qwen2ForCausalLM``):

    h   = x + Wo . attn(rope(RMSNorm(x) Wq + bq), rope(RMSNorm(x) Wk + bk),
                        RMSNorm(x) Wv + bv)          causal, GQA
    out = h + Wd (silu(RMSNorm(h) Wg) * (RMSNorm(h) Wu))
    logits = RMSNorm(x_L) . head^T

RMSNorm is ``w * x / sqrt(mean(x^2) + eps)``; RoPE rotates the two
halves of each head (``rotate_half``) with inverse frequencies
``theta^(-i / (head_dim / 2))``; query head ``i`` reads key/value head
``i // (n_heads / n_kv_heads)``.  Nothing here imports the program
under test: the weights are made from the seed by ``init_weights``,
and the same function feeds the program through the harness.

Every matrix product runs at ``HIGHEST`` precision, so on a TPU it is a
float32 product and not one bf16 pass.  ``quant="fp8"`` rounds both
operands of every product to float8_e4m3fn with a per-tensor scale
first: that is the lower-precision control of ``harness/check.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(cfg: dict) -> dict:
    """The sizes the block needs, from a Hugging Face style config."""
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, hq=hq, hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // hq,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def init_weights(cfg: dict, key) -> dict:
    """Random float32 weights, stacked over layers.

    Matrices are N(0, std) with the config's ``initializer_range`` (as
    Hugging Face initializes Qwen2); the q/k/v biases are N(0, 0.1) and
    the norm weights 1 + N(0, 0.1) in steps of 2^-10, so that neither
    is an identity the block could drop unnoticed.  Matrices are stored
    ``(in, out)``:
    ``x @ w``.  ``head`` is ``(vocab, hidden)`` as in the published
    checkpoints."""
    n = dims(cfg)
    d, hq, hkv, hd, f, v, nl = (n[k] for k in
                                ("d", "hq", "hkv", "hd", "f", "v", "layers"))
    std = float(cfg.get("initializer_range", 0.02))
    ks = iter(jax.random.split(key, 16))

    def normal(shape, s=std):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    def norm(shape):
        # multiples of 2^-10 near 1: exact in float32 under any shift
        return 1.0 + jnp.round(normal(shape, 0.1) * 1024.0) / 1024.0

    return {
        "embed": normal((v, d)),
        "head": normal((v, d)),
        "final_norm": norm((d,)),
        "ln1": norm((nl, d)),
        "ln2": norm((nl, d)),
        "wq": normal((nl, d, hq * hd)),
        "wk": normal((nl, d, hkv * hd)),
        "wv": normal((nl, d, hkv * hd)),
        "bq": normal((nl, hq * hd), 0.1),
        "bk": normal((nl, hkv * hd), 0.1),
        "bv": normal((nl, hkv * hd), 0.1),
        "wo": normal((nl, hq * hd, d)),
        "w_gate": normal((nl, d, f)),
        "w_up": normal((nl, d, f)),
        "w_down": normal((nl, f, d)),
    }


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
    """x: (S, H, hd), position = row index."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(w: dict, cfg: dict, tokens, quant=None):
    """Final normed hidden states (S, d) of one sequence ``tokens``."""
    n = dims(cfg)
    hq, hkv, hd, eps = n["hq"], n["hkv"], n["hd"], n["eps"]
    s = tokens.shape[0]
    x = w["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = (_mm(h, p["wq"], quant) + p["bq"]).reshape(s, hq, hd)
        k = (_mm(h, p["wk"], quant) + p["bk"]).reshape(s, hkv, hd)
        v = (_mm(h, p["wv"], quant) + p["bv"]).reshape(s, hkv, hd)
        q, k = _rope(q, n["theta"]), _rope(k, n["theta"])
        g = hq // hkv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        if quant == "fp8":
            q, k, v = _fp8(q), _fp8(k), _fp8(v)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / hd ** 0.5
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        if quant == "fp8":
            pr = _fp8(pr)
        ctx = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
        x = x + _mm(ctx.reshape(s, hq * hd), p["wo"], quant)
        h = _rms(x, p["ln2"], eps)
        y = jax.nn.silu(_mm(h, p["w_gate"], quant)) * _mm(h, p["w_up"], quant)
        return x + _mm(y, p["w_down"], quant), None

    per_layer = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "bq",
                                   "bk", "bv", "wo", "w_gate", "w_up",
                                   "w_down")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    return _rms(x, w["final_norm"], eps)


def logits(w: dict, h, quant=None):
    """(rows, d) hidden states -> (rows, vocab) logits."""
    return _mm(h, w["head"].T, quant)
