"""The Moonlight-16B-A3B cell at a size a test run holds: its reference
and adapter through a whole run on the CPU, the counts behind its
per-layer metrics against hand counts, and its readers on a made-up
traced run."""

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import routing  # noqa: E402
import run  # noqa: E402
from harness import check, moe_counts, program_spans, serve  # noqa: E402
from harness.cell import Cell, load_module  # noqa: E402
from harness.peaks import peaks  # noqa: E402
from harness.serve import StepRecord  # noqa: E402

LIMIT = 1e-3    # the CPU computes float32 products exactly in float32
NAME = "moonlight-16b-a3b.chat-math-sat"


def config() -> dict:
    return json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                      .read_text())


def tiny_cell() -> Cell:
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cfg = config()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=32, intermediate_size=128,
               moe_intermediate_size=32, vocab_size=256, num_hidden_layers=3,
               n_routed_experts=4, initializer_range=0.1,
               held_experts={"first": 8, "router_width": 16},
               engine={"n_slots": 4, "max_len": 64, "page_size": 8,
                       "chunk_size": 16, "decode_block": 4},
               check={"max_logit_gap": LIMIT, "sample_tokens": 150})
    mix = json.loads((BENCH / "traffic" / "chat-math-sat.json").read_text())
    mix.update(rate_rps=3.0, lead_s=1.0, drain_cap_s=10.0)
    return Cell(name=NAME, chips=1, config=cfg, traffic=mix,
                end_to_end=[m for m in spec["end_to_end"]
                            if NAME in m.get("workloads", [NAME])],
                per_layer=[])


def test_served_run_is_correct_and_its_fp8_control_is_not(monkeypatch):
    """On the CPU the program's experts are the reference's at every
    checked position and layer (``routing.py`` counts them on the
    chip)."""
    cell = tiny_cell()
    seen = {}
    gaps = check.gaps

    def gaps_with_control(cell_, weights, requests, **kw):
        seen.update(gaps(cell_, weights, requests, control=True))
        seen.update(routing.flips(cell_, weights, requests))
        return gaps(cell_, weights, requests, **kw)

    monkeypatch.setattr(check, "gaps", gaps_with_control)
    out = run.run_cell(cell, 2 ** 31 + 7, 3.0, False, time.perf_counter())
    assert out["correct"] is True
    assert out["attempted"] == 9 and out["failed"] == 0
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert seen["positions"] >= 150
    assert seen["max_logit_gap"] <= LIMIT < seen["control_max_logit_gap"]
    assert seen["pairs"] == 2 * seen["positions"] and seen["differ"] == 0


def test_weights_reach_the_program_as_the_reference_makes_them():
    """Experts, router and bias pass through; norms are shifted by the
    program's ``1 + w``; the rotary columns are reordered to halves."""
    import numpy as np

    cell = tiny_cell()
    ours = serve.make_weights(cell, 3, program=False)
    theirs = serve.make_weights(cell, 3, program=True)
    moe = theirs["segments"][1]["moe"]
    np.testing.assert_array_equal(moe["w_gate"], ours["moe"]["e_gate"])
    np.testing.assert_array_equal(moe["router_bias"],
                                  ours["moe"]["router_bias"])
    np.testing.assert_array_equal(
        1.0 + theirs["segments"][0]["attn"]["kv_norm"],
        ours["dense"]["kv_norm"])
    wkv_a = ours["dense"]["wkv_a"]
    np.testing.assert_array_equal(
        theirs["segments"][0]["attn"]["wkv_a"][..., -8:],
        np.concatenate([wkv_a[..., -8::2], wkv_a[..., -7::2]], -1))


# -- counts -------------------------------------------------------------------

def dims(**kw) -> moe_counts.MoeDims:
    base = dict(d=64, h=4, nope=16, rope=8, vd=16, lora=32, f=128, fe=32,
                shared=2, experts=16, held=4, v=100, layers=3, dense=1)
    return moe_counts.MoeDims(**{**base, **kw})


def test_dims_of_the_served_configuration():
    m = moe_counts.MoeDims.of(config())
    assert (m.d, m.h, m.lora, m.rope, m.nope, m.vd) == (2048, 16, 512, 64,
                                                        128, 128)
    assert (m.experts, m.held, m.fe, m.shared) == (64, 8, 1408, 2)
    assert (m.layers, m.dense, m.moe_layers) == (11, 1, 10)


def test_latent_decode_reads_each_live_row_once_for_all_heads():
    m = dims()
    flops, nbytes = moe_counts.mla_decode_work(m, [5, 17, 0], kv_bytes=4,
                                               q_bytes=4)
    rows = (5 + 17) * (32 + 8) * 4          # latent + rotary key, once
    qo = 2 * 4 * (40 + 32) * 4              # q in, out; two lanes
    assert nbytes == rows + qo
    assert flops == 2 * 4 * (40 + 32) * (5 + 17)


def test_token_flops_by_hand():
    m = dims()
    proj = (2 * 64 * 4 * 24 + 2 * 64 * 40 + 2 * 4 * 16 * 32
            + 2 * 4 * 32 * 16 + 2 * 4 * 16 * 64)
    attn = proj + 2 * 4 * 72 * 9
    ffn = 6 * 64 * 128 + 2 * (2 * 64 * 16 + 6 * 64 * 2 * 32)
    assert moe_counts.token_flops(m, 9) == 3 * attn + ffn
    assert moe_counts.head_flops(m) == 2 * 64 * 100
    assert moe_counts.prefill_flops(m, 4, 2) == (
        moe_counts.token_flops(m, 5) + moe_counts.token_flops(m, 6))
    assert moe_counts.decode_flops(m, 10, 2) == (
        moe_counts.token_flops(m, 11) + moe_counts.token_flops(m, 12)
        + 2 * 2 * 64 * 100)
    assert moe_counts.expert_work(m, 10, 3) == (10 * 6 * 64 * 32,
                                                3 * 3 * 64 * 32 * 2)


# -- readers, on a made-up traced run ----------------------------------------

def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py").read


@pytest.fixture
def ctx(monkeypatch):
    """Two decode steps (one fused, K = 2) and a prefill chunk, their
    spans with the held experts' load, and a trace of 1 s."""
    spans = [
        program_spans.Span("hfx.engine.step", 0.0, 0.1,
                           {"kind": "decode", "k": 2, "expert_tokens": 48,
                            "experts_hit": 16}),
        program_spans.Span("hfx.engine.step", 0.2, 0.3,
                           {"kind": "decode", "k": 1, "expert_tokens": 16,
                            "experts_hit": 8}),
        program_spans.Span("hfx.engine.step", 0.4, 0.5,
                           {"kind": "prefill_chunk", "expert_tokens": 40,
                            "experts_hit": 8}),
    ]
    monkeypatch.setattr(program_spans, "load",
                        lambda *a: SimpleNamespace(spans=spans))
    cfg = config()
    steps = [StepRecord(0.1, 0.2, 0, "decode", 2, [(100, 2), (50, 1)], 0),
             StepRecord(0.3, 0.4, 0, "decode", 1, [(102, 1)], 0),
             StepRecord(0.5, 0.6, 0, "prefill", 1, [(0, 30)], 1)]
    summary = SimpleNamespace(
        window_s=1.0, op_s={"paged_mla_decode_attention": 1e-3,
                            "ragged-dot-none": 2e-3,
                            "ragged-dot-metadata": 1e-4})
    return SimpleNamespace(cell=SimpleNamespace(config=cfg), summary=summary,
                           traced=(0.0, 1.0), kv_bytes=4, q_bytes=4,
                           peaks=peaks("TPU v5 lite"), replica_devices=1,
                           steps_in=lambda span, kind=None: [
                               s for s in steps
                               if kind is None or s.kind == kind])


def test_expert_tokens_per_step(ctx):
    # (48 + 16) assignments over 3 iterations, 8 held x 10 layers
    assert reader("expert_tokens_per_step")(ctx) == pytest.approx(
        64 / (3 * 8 * 10))


def test_mla_roofline_counts_each_iteration_and_layer(ctx):
    m = moe_counts.MoeDims.of(config())
    p = peaks("TPU v5 lite")
    need = 0.0
    for lens in ([101, 51], [102], [103]):
        f, b = moe_counts.mla_decode_work(m, lens, 4, 4)
        need += 11 * max(f / p["flops_bf16"], b / p["hbm_bytes_per_s"])
    assert reader("mla_decode_attention_roofline")(ctx) == pytest.approx(
        100 * need / 1e-3)


def test_expert_matmul_roofline_sums_the_ragged_dots(ctx):
    m = moe_counts.MoeDims.of(config())
    p = peaks("TPU v5 lite")
    need = sum(max(f / p["flops_bf16"], b / p["hbm_bytes_per_s"])
               for f, b in (moe_counts.expert_work(m, t, h)
                            for t, h in ((48, 16), (16, 8), (40, 8))))
    assert reader("moe_expert_matmul_roofline")(ctx) == pytest.approx(
        100 * need / 2.1e-3)


def test_moe_step_mfu_counts_tokens_experts_and_heads(ctx):
    m = moe_counts.MoeDims.of(config())
    flops = (moe_counts.expert_work(m, 104, 0)[0]
             + moe_counts.decode_flops(m, 100, 2)
             + moe_counts.decode_flops(m, 50, 1)
             + moe_counts.decode_flops(m, 102, 1)
             + moe_counts.prefill_flops(m, 0, 30) + moe_counts.head_flops(m))
    assert reader("moe_step_mfu")(ctx) == pytest.approx(
        100 * flops / 197e12)


def test_readers_find_nothing_where_steps_carry_no_expert_load(ctx,
                                                               monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda *a: SimpleNamespace(
        spans=[program_spans.Span("hfx.engine.step", 0, 1,
                                  {"kind": "decode", "k": 1})]))
    for name in ("expert_tokens_per_step", "moe_step_mfu",
                 "moe_expert_matmul_roofline"):
        assert reader(name)(ctx) is None
