"""Fused decode blocks — dispatch-amortized decode throughput.

Pure-decode micro-bench on the real engine (CPU smoke config): short
prompts decode long outputs with an empty queue, swept over the
``decode_block`` ceiling K.  Every decode runs as a block of K
iterations, the same scan at every K: each block pays one jit dispatch
and one host sync, so K=1 (a block of one per token) is the baseline
and a K-block pays them once per K tokens.

Reports decode tokens/s, jitted dispatches (= host syncs) per token,
the block-size histogram, and greedy token-identity vs the K=1 run.
Rows carry a machine-readable ``json`` payload that
``benchmarks/run.py --json`` collects into ``BENCH_decode.json`` (the
perf-trajectory artifact CI uploads).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import row


def _run_engine(model, params, ecfg_kw, k, prompts, n_new, fn_cache):
    from repro.core.request import Request
    from repro.serving.engine import EngineConfig, InferenceEngine

    reqs = [Request.from_prompt(i, p, max_new=n_new)
            for i, p in enumerate(prompts)]
    # one fn_cache across the K sweep: chunk/prefill jits are identical
    # at every K (block fns key per K), so compile cost is paid once
    eng = InferenceEngine(model, params, EngineConfig(
        decode_block=k, **ecfg_kw), fn_cache=fn_cache)
    eng.warm_decode_blocks()
    warm = Request.from_prompt(-1, np.arange(1, 9, dtype=np.int32),
                               max_new=3)
    eng.submit(warm)
    eng.run_until_done()
    for r in reqs:
        eng.submit(r)
    # drain prefill so the timed region is pure decode (the regime
    # blocks target; under queue pressure K collapses to 1 by design)
    for _ in range(10_000):
        if not eng.queue and not eng.prefilling:
            break
        eng.step()
    tok0, disp0 = eng.n_decode_tokens, eng.n_dispatches
    hist0 = dict(eng.decode_block_hist)
    t0 = time.perf_counter()
    eng.run_until_done()
    wall = time.perf_counter() - t0
    tokens = eng.n_decode_tokens - tok0
    disp = eng.n_dispatches - disp0
    hist = {b: n - hist0.get(b, 0)
            for b, n in eng.decode_block_hist.items()
            if n - hist0.get(b, 0) > 0}
    assert all(r.finish_time is not None for r in reqs)
    return {
        "tokens": tokens,
        "wall_s": wall,
        "tokens_per_s": tokens / max(wall, 1e-9),
        "dispatches": disp,
        "dispatches_per_token": disp / max(tokens, 1),
        "block_hist": hist,
        "generated": [list(r.generated) for r in reqs],
    }


def run(quick: bool = True) -> list[dict]:
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = get_smoke_config("qwen7b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    n_new = 32 if quick else 96
    ecfg_kw = dict(n_slots=4, max_len=16 + n_new + 8, prefill_batch=4,
                   page_size=8, chunk_size=16)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(4)]

    rows = []
    base = None
    fn_cache: dict = {}
    for k in (1, 2, 4, 8):
        res = _run_engine(model, params, ecfg_kw, k, prompts, n_new,
                          fn_cache)
        if base is None:
            base = res
        identical = res["generated"] == base["generated"]
        speedup = res["tokens_per_s"] / base["tokens_per_s"]
        payload = {
            "bench": "decode_block",
            "k": k,
            "tokens": res["tokens"],
            "tokens_per_s": round(res["tokens_per_s"], 2),
            "dispatches_per_token": round(res["dispatches_per_token"], 4),
            "block_hist": res["block_hist"],
            "speedup_vs_k1": round(speedup, 3),
            "identical_to_k1": identical,
        }
        rows.append({
            **row(
                f"decode_block/K={k}",
                res["wall_s"] * 1e6 / max(res["tokens"], 1),
                f"tok_s={res['tokens_per_s']:.1f} "
                f"disp_per_tok={res['dispatches_per_token']:.3f} "
                f"speedup={speedup:.2f}x identical={identical} "
                f"hist={res['block_hist']}",
            ),
            "json": payload,
        })
    return rows
