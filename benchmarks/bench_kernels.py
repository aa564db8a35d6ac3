"""Kernel micro-bench: jnp oracle wall-time on CPU (the only honest
timing this container can produce) + interpret-mode Pallas parity checks
at production-relevant tile shapes."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd import ssd

from benchmarks.common import row


def _time(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(quick: bool = True) -> list[dict]:
    rows: list[dict] = []
    key = jax.random.key(0)
    ks = jax.random.split(key, 8)

    # flash attention (prefill tile): B1 H8 S2048 D128
    s = 1024 if quick else 4096
    q = jax.random.normal(ks[0], (1, 8, s, 128), jnp.bfloat16)
    jitted_ref = jax.jit(
        lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True)
    )
    us = _time(jitted_ref, q, q, q)
    got = flash_attention(q, q, q, causal=True, block_q=256, block_k=256,
                          interpret=True)
    err = float(jnp.max(jnp.abs(
        got.astype(jnp.float32)
        - jitted_ref(q, q, q).astype(jnp.float32))))
    rows.append(row(f"kernels/flash_attn/S{s}", us,
                    f"pallas_interpret_maxerr={err:.3e}"))

    # decode attention: B8 H8 S8192 D128
    sd = 2048 if quick else 8192
    qd = jax.random.normal(ks[1], (8, 8, 128), jnp.bfloat16)
    kc = jax.random.normal(ks[2], (8, 8, sd, 128), jnp.bfloat16)
    kv_len = jnp.full((8,), sd, jnp.int32)
    jit_dec = jax.jit(ref.decode_attention_ref)
    us = _time(jit_dec, qd, kc, kc, kv_len)
    got = decode_attention(qd, kc, kc, kv_len, block_k=256,
                           interpret=True)
    err = float(jnp.max(jnp.abs(
        got.astype(jnp.float32)
        - jit_dec(qd, kc, kc, kv_len).astype(jnp.float32))))
    rows.append(row(f"kernels/decode_attn/S{sd}", us,
                    f"pallas_interpret_maxerr={err:.3e}"))

    # ssd chunk scan: B2 S1024 H8 P64 N128
    ss = 512 if quick else 2048
    x = jax.random.normal(ks[3], (2, ss, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (2, ss, 8)))
    a = -jnp.exp(jax.random.normal(ks[5], (8,)) * 0.5)
    bm = jax.random.normal(ks[6], (2, ss, 128))
    cm = jax.random.normal(ks[7], (2, ss, 128))
    jit_ssd = jax.jit(ref.ssd_ref)
    us = _time(jit_ssd, x, dt, a, bm, cm)
    y1, s1 = ssd(x, dt, a, bm, cm, chunk=256, interpret=True)
    y2, s2 = jit_ssd(x, dt, a, bm, cm)
    err = float(jnp.max(jnp.abs(y1 - y2)))
    rows.append(row(f"kernels/ssd/S{ss}", us,
                    f"pallas_interpret_maxerr={err:.3e}"))
    return rows
