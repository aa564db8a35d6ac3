"""Pallas kernel sweeps (interpret mode) vs the pure-jnp oracles, and
the one kernel decision: compiled on TPU, never a silent interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import (
    decode_attention,
    paged_decode_attention,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.page_gather import page_gather
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd import ssd


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else (
        dict(rtol=2e-5, atol=2e-5)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,d,bq,bk", [
    (128, 64, 64, 64),
    (256, 64, 64, 128),
    (256, 128, 128, 64),
    (512, 32, 128, 128),
])
@pytest.mark.parametrize("causal,window", [
    (True, 0), (False, 0), (True, 64),
])
def test_flash_attention_sweep(s, d, bq, bk, causal, window, dtype):
    k0, k1, k2 = jax.random.split(jax.random.key(0), 3)
    shape = (2, 3, s, d)
    q = jax.random.normal(k0, shape, dtype)
    k = jax.random.normal(k1, shape, dtype)
    v = jax.random.normal(k2, shape, dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,d,bk", [(256, 64, 64), (512, 128, 128),
                                    (1024, 64, 256)])
def test_decode_attention_sweep(s, d, bk, dtype):
    k0, k1, k2 = jax.random.split(jax.random.key(1), 3)
    b, h = 3, 4
    q = jax.random.normal(k0, (b, h, d), dtype)
    kc = jax.random.normal(k1, (b, h, s, d), dtype)
    vc = jax.random.normal(k2, (b, h, s, d), dtype)
    kv_len = jnp.array([s, s // 2, 7][:b])
    got = decode_attention(q, kc, vc, kv_len, block_k=bk, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, kv_len)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


@pytest.mark.parametrize("s,h,p,n,chunk", [
    (128, 2, 16, 32, 32),
    (256, 4, 16, 32, 64),
    (256, 4, 32, 64, 128),
])
def test_ssd_sweep(s, h, p, n, chunk):
    ks = jax.random.split(jax.random.key(2), 5)
    b = 2
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    y1, s1 = ssd(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    y2, s2 = ref.ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,d,br", [(128, 64, 32), (256, 512, 256),
                                       (64, 128, 64)])
def test_rmsnorm_sweep(rows, d, br, dtype):
    k0, k1 = jax.random.split(jax.random.key(3))
    x = jax.random.normal(k0, (rows, d), dtype)
    sc = jax.random.normal(k1, (d,)) * 0.1
    got = rmsnorm(x, sc, block_rows=br, interpret=True)
    want = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


def test_flash_attention_long_context_memory_shape():
    """Blocked kernel output matches shapes on longer sequences."""
    q = jax.random.normal(jax.random.key(4), (1, 2, 1024, 64))
    out = flash_attention(q, q, q, causal=True, block_q=256, block_k=256,
                          interpret=True)
    assert out.shape == q.shape


def _kernel_calls():
    x4 = jnp.ones((1, 2, 128, 64))
    q3 = jnp.ones((2, 2, 64))
    pages = jnp.ones((4, 2, 16, 64))
    table = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.array([5, 9])
    return {
        "flash_attention": lambda: flash_attention(x4, x4, x4),
        "decode_attention": lambda: decode_attention(
            q3, jnp.ones((2, 2, 256, 64)), jnp.ones((2, 2, 256, 64)), lens),
        "paged_decode_attention": lambda: paged_decode_attention(
            q3, pages, pages, table, lens),
        "page_gather": lambda: page_gather(pages, jnp.arange(2)),
        "ssd": lambda: ssd(jnp.ones((1, 64, 2, 16)), jnp.ones((1, 64, 2)),
                           -jnp.ones((2,)), jnp.ones((1, 64, 32)),
                           jnp.ones((1, 64, 32)), chunk=32),
        "rmsnorm": lambda: rmsnorm(jnp.ones((64, 128)), jnp.zeros((128,))),
    }


@pytest.mark.parametrize("name", sorted(_kernel_calls()))
def test_kernel_without_interpret_raises_off_tpu(name):
    """No kernel entry point interprets by default: off a TPU, a call
    without ``interpret=True`` fails instead of running slowly."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(ValueError, match="interpret"):
        _kernel_calls()[name]()


def test_kernel_decision_follows_the_platform():
    """The served path's one switch: kernels only where the backend is
    a TPU; page_gather then falls back to its jnp oracle."""
    assert ops.kernels_enabled() == (jax.default_backend() == "tpu")
    pages = jax.random.normal(jax.random.key(5), (6, 2, 8, 16))
    ids = jnp.array([4, 1, -1], jnp.int32)
    np.testing.assert_array_equal(np.asarray(ops.page_gather(pages, ids)),
                                  np.asarray(ref.page_gather_ref(pages, ids)))
