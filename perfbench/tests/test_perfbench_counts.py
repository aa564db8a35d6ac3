"""The benchmark's operation and byte counters, against hand counts."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import counts  # noqa: E402
from harness.peaks import peaks  # noqa: E402


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_decode_attention_bytes_count_live_tokens_once_per_kv_head(hq, hkv):
    m = counts.Dims(d=64, hq=hq, hkv=hkv, hd=16, f=128, v=100, layers=1)
    # two lanes decode at 5 and 17 live tokens; an idle lane (0) is free
    flops, nbytes = counts.decode_attention_work(m, [5, 17, 0], kv_bytes=2,
                                                 q_bytes=4)
    kv = (5 + 17) * hkv * 16 * 2 * 2        # K and V, bf16, per KV head
    qo = 2 * (hq * 16 * 4 * 2)              # q in and out, two lanes
    assert nbytes == kv + qo
    assert flops == 4 * hq * 16 * (5 + 17)


def test_gqa_reads_fewer_bytes_than_mha_at_equal_query_heads():
    mha = counts.Dims(d=64, hq=8, hkv=8, hd=16, f=1, v=1, layers=1)
    gqa = counts.Dims(d=64, hq=8, hkv=2, hd=16, f=1, v=1, layers=1)
    _, b_mha = counts.decode_attention_work(mha, [100], 4, 4)
    _, b_gqa = counts.decode_attention_work(gqa, [100], 4, 4)
    assert b_mha - b_gqa == 2 * 100 * (8 - 2) * 16 * 4


@pytest.mark.parametrize("name", ["qwen7b", "qwen2.5-14b"])
def test_flops_per_token_match_the_dense_formula(name):
    cfg = config(name)
    m = counts.Dims.of(cfg)
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    per_layer = 2 * d * (q + 2 * kv) + 2 * q * d + 6 * d * f
    L = cfg["num_hidden_layers"]
    assert counts.matmul_flops_per_token(m) == L * per_layer
    ctx = 300
    assert counts.token_flops(m, ctx) == L * (per_layer + 4 * q * ctx)
    assert counts.head_flops(m) == 2 * d * v
    # a prompt of n tokens from 0: every token's context, one head
    n = 7
    want = n * L * per_layer + L * 4 * q * sum(range(1, n + 1)) + 2 * d * v
    assert counts.prefill_flops(m, 0, n, True) == pytest.approx(want)
    # a decode lane at 10 emitting 3: contexts 11, 12, 13 and 3 heads
    want = sum(counts.token_flops(m, c) for c in (11, 12, 13)) + 3 * 2 * d * v
    assert counts.decode_flops(m, 10, 3) == pytest.approx(want)


def test_peaks_know_the_v5e_and_refuse_other_devices():
    p = peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in p["source"]
    with pytest.raises(KeyError):
        peaks("cpu")
