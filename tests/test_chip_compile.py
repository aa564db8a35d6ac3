"""Compile rehearsals for one v5e chip, with no chip attached.

The TPU compiler is installed here and compiles for a described (not
attached) ``v5e:2x2`` topology.  These tests compile the served path's
kernels and programs at ``qwen7b``'s published widths and the shapes
``chip_smoke.py`` serves, so tiling and VMEM refusals and programs too
large for the chip surface without chip time.  Only shapes are passed:
nothing is materialized.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and xdist workers
import every test file.
"""

import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.page_gather import page_gather
from repro.models import build_model

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

CUT = chip_smoke.qwen7b_cut()
ENG = chip_smoke.ENGINE
B, PS, CHUNK = ENG.n_slots, ENG.page_size, ENG.chunk_size
MP = -(-ENG.max_len // PS)
HQ, HKV, D = CUT.n_heads, CUT.n_kv_heads, CUT.resolved_head_dim


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_decision(monkeypatch):
    """Take the platform's kernel decision as a TPU process takes it."""
    monkeypatch.setattr(ops, "kernels_enabled", lambda: True)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


def _assert_fits(compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes)
    assert total < chip_smoke.HBM_BYTES, (
        m.argument_size_in_bytes, m.temp_size_in_bytes,
        m.output_size_in_bytes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_kernel_compiles(one_chip, dtype):
    pages = _spec((B * MP, HKV, PS, D), dtype, one_chip)
    c = jax.jit(paged_decode_attention).lower(
        _spec((B, HQ, D), dtype, one_chip), pages, pages,
        _spec((B, MP), jnp.int32, one_chip), _spec((B,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in c.as_text()


def test_page_gather_kernel_compiles(one_chip):
    c = jax.jit(page_gather).lower(
        _spec((B * MP, HKV, PS, D), jnp.float32, one_chip),
        _spec((MP,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in c.as_text()


def test_kv_export_gather_compiles(one_chip, tpu_decision):
    """The P/D export path: ``page_gather`` vmapped over the layer axis
    of one K (or V) pool, as ``kv_manager.gather_slot_kv`` runs it."""
    from repro.serving.kv_manager import _gather_pages_leaf

    c = jax.jit(lambda leaf, ids: _gather_pages_leaf(leaf, ids, 1000)).lower(
        _spec((CUT.n_layers, B * MP, HKV, PS, D), jnp.float32, one_chip),
        _spec((MP,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture(scope="module")
def served_model(one_chip):
    """The served cut of qwen7b in float32, with its params and page
    pool as shapes on one chip."""
    model = build_model(CUT)
    params = _on(model.abstract_params(), one_chip)
    caches = _on(jax.eval_shape(
        lambda: model.init_paged_cache(B, ENG.max_len, PS)), one_chip)
    return model, params, caches


@pytest.mark.parametrize("chunk", [1, CHUNK])
def test_chunk_step_fits_one_chip(served_model, one_chip, tpu_decision,
                                  chunk):
    model, params, caches = served_model
    i32 = jnp.int32
    c = jax.jit(model.chunk_step).lower(
        params, caches, _spec((B, MP), i32, one_chip),
        _spec((B, chunk), i32, one_chip), _spec((B,), i32, one_chip),
        _spec((B,), i32, one_chip),
    ).compile()
    _assert_fits(c)
    # one-token decode runs the paged kernel; prefill chunks run jnp
    assert ("tpu_custom_call" in c.as_text()) == (chunk == 1)


def test_decode_block_fits_one_chip(served_model, one_chip, tpu_decision):
    model, params, caches = served_model
    i32, vec = jnp.int32, (B,)
    c = jax.jit(lambda *a: model.decode_block(*a, k=ENG.decode_block)).lower(
        params, caches, _spec((B, MP), i32, one_chip),
        _spec(vec, i32, one_chip), _spec(vec, i32, one_chip),
        _spec(vec, jnp.bool_, one_chip), _spec(vec, i32, one_chip),
        _spec((), i32, one_chip), _spec((), i32, one_chip),
    ).compile()
    _assert_fits(c)
    assert "tpu_custom_call" in c.as_text()


def test_reference_scorer_fits_one_chip(served_model, one_chip):
    """chip_smoke.py's correctness pass (a highest- and a default-
    precision forward) over its longest padded sequence: a 768-token
    prompt plus 64 generated tokens, less the last."""
    model, params, _ = served_model
    n_out = chip_smoke.TRAFFIC["out"][1]
    length = chip_smoke.TRAFFIC["prompt"][1] - 1 + n_out
    c = chip_smoke.reference_scorer(model, n_out).lower(
        params, _spec((1, length), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
    ).compile()
    _assert_fits(c)
