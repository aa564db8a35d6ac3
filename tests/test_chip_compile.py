"""Compile rehearsals for one v5e chip, with no chip attached.

The TPU compiler is installed here and compiles for a described (not
attached) ``v5e:2x2`` topology.  These tests compile the served path's
kernels and programs at ``qwen7b``'s published widths and the shapes
``chip_smoke.py`` serves, and Moonlight-16B-A3B's as the benchmark's
configuration cuts it (``perfbench/configs/moonlight-16b-a3b.json``),
so tiling and VMEM refusals and programs too large for the chip surface
without chip time.  Only shapes are passed:
nothing is materialized.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and xdist workers
import every test file.
"""

import importlib.util
import json
import os
import pathlib
import sys
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import (
    paged_decode_attention,
    paged_mla_decode_attention,
)
from repro.kernels.page_gather import page_gather
from repro.models import build_model
from repro.serving.engine import EngineConfig, _on_rows

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

CUT = chip_smoke.qwen7b_cut()
ENG = chip_smoke.ENGINE
B, PS, CHUNK = ENG.n_slots, ENG.page_size, ENG.chunk_size
P = min(ENG.prefill_batch, B)   # rows of a served prefill chunk
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
@pytest.mark.parametrize("hq,hkv", [(HQ, HKV), (40, 8)],
                         ids=["qwen7b", "qwen2.5-14b"])
def test_paged_decode_kernel_compiles(one_chip, hq, hkv, dtype):
    """Both benchmark configurations' heads (MHA 32/32, GQA 40/8) at the
    served pool, table and head size."""
    pages = _spec((B * MP, hkv, PS, D), dtype, one_chip)
    c = jax.jit(paged_decode_attention).lower(
        _spec((B, hq, D), dtype, one_chip), pages, pages,
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


def _compile_prefill_rows(model, params, caches, table, rows, chunk,
                          sharding):
    """The prefill chunk program as the engine serves it: ``rows`` rows
    of ``chunk`` tokens, their rows of the ``table`` (lanes, pages)
    picked inside the call."""
    i32 = jnp.int32
    vec = _spec((rows,), i32, sharding)
    return jax.jit(partial(_on_rows, model.chunk_step)).lower(
        params, caches, _spec(table, i32, sharding), vec,
        _spec((rows, chunk), i32, sharding), vec, vec,
    ).compile()


def test_prefill_rows_fit_one_chip(served_model, one_chip, tpu_decision):
    model, params, caches = served_model
    c = _compile_prefill_rows(model, params, caches, (B, MP), P, CHUNK,
                              one_chip)
    _assert_fits(c)
    assert "tpu_custom_call" not in c.as_text()


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


# -- Moonlight-16B-A3B, as the benchmark serves it ---------------------------

BENCH = pathlib.Path(__file__).resolve().parents[1] / "perfbench"
MOONLIGHT = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                       .read_text())
ML_ENG = MOONLIGHT["engine"]
ML_B, ML_MP = ML_ENG["n_slots"], ML_ENG["max_len"] // ML_ENG["page_size"]
ML_P = min(EngineConfig(**ML_ENG).prefill_batch, ML_B)


def _moonlight_config():
    spec = importlib.util.spec_from_file_location(
        "deepseek_v3_adapter", BENCH / "adapters" / "deepseek_v3.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.model_config(MOONLIGHT)


@pytest.fixture(scope="module")
def moonlight(one_chip):
    """The served cut (1 dense + 10 expert layers of 8 held experts, 32
    lanes of 2048 tokens) in float32, as shapes on one chip: the record
    behind the depth and the lanes.  The largest program is the fused
    decode block, 13.08 GB (the served 4-row prefill chunk 11.18, the
    chunk over all 32 rows 12.18); 48 lanes take the block to 15.93 GB,
    past the chip."""
    model = build_model(_moonlight_config())
    params = _on(model.abstract_params(), one_chip)
    caches = _on(jax.eval_shape(lambda: model.init_paged_cache(
        ML_B, ML_ENG["max_len"], ML_ENG["page_size"])), one_chip)
    return model, params, caches


def test_mla_decode_kernel_compiles(one_chip):
    a = _moonlight_config().mla

    def call(q_c, q_r, pages, table, lens):
        return paged_mla_decode_attention(q_c, q_r, pages, table, lens,
                                          scale=1.0 / a.qk_head_dim ** 0.5)

    c = jax.jit(call).lower(
        _spec((ML_B, MOONLIGHT["num_attention_heads"], a.kv_lora_rank),
              jnp.float32, one_chip),
        _spec((ML_B, MOONLIGHT["num_attention_heads"], a.qk_rope_head_dim),
              jnp.float32, one_chip),
        _spec((ML_B * ML_MP, 1, ML_ENG["page_size"], a.pool_width),
              jnp.float32, one_chip),
        _spec((ML_B, ML_MP), jnp.int32, one_chip),
        _spec((ML_B,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("chunk", [1, ML_ENG["chunk_size"]])
def test_moonlight_chunk_step_fits_one_chip(moonlight, one_chip,
                                            tpu_decision, chunk):
    model, params, caches = moonlight
    i32 = jnp.int32
    c = jax.jit(model.chunk_step).lower(
        params, caches, _spec((ML_B, ML_MP), i32, one_chip),
        _spec((ML_B, chunk), i32, one_chip), _spec((ML_B,), i32, one_chip),
        _spec((ML_B,), i32, one_chip),
    ).compile()
    _assert_fits(c)
    # one-token decode runs the MLA kernel; prefill chunks run jnp (both
    # run the experts as ragged dots, custom calls of their own)
    assert ("paged_mla_decode_attention" in c.as_text()) == (chunk == 1)
    assert "ragged-dot" in c.as_text()


def test_moonlight_prefill_rows_fit_one_chip(moonlight, one_chip,
                                            tpu_decision):
    model, params, caches = moonlight
    c = _compile_prefill_rows(model, params, caches, (ML_B, ML_MP), ML_P,
                              ML_ENG["chunk_size"], one_chip)
    _assert_fits(c)
    assert "paged_mla_decode_attention" not in c.as_text()
    assert "ragged-dot" in c.as_text()


def test_moonlight_decode_block_fits_one_chip(moonlight, one_chip,
                                              tpu_decision):
    model, params, caches = moonlight
    i32, vec = jnp.int32, (ML_B,)
    c = jax.jit(lambda *a: model.decode_block(
        *a, k=ML_ENG["decode_block"])).lower(
        params, caches, _spec((ML_B, ML_MP), i32, one_chip),
        _spec(vec, i32, one_chip), _spec(vec, i32, one_chip),
        _spec(vec, jnp.bool_, one_chip), _spec(vec, i32, one_chip),
        _spec((), i32, one_chip), _spec((), i32, one_chip),
    ).compile()
    _assert_fits(c)
    assert "paged_mla_decode_attention" in c.as_text()
