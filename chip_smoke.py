"""Bring-up check: serve qwen7b at its published widths on a TPU chip.

    python chip_smoke.py [--seed N]   # one chip: device, serve, correctness
    python chip_smoke.py --chips 4    # four chips: P/D replicas, one per
                                      # device, plus a d2d provision, vs
                                      # the one-replica collocated run

Drives the served path through the entry points a user calls:
``Cluster`` -> ``EngineWorker`` -> ``InferenceEngine`` on the paged plane,
under a wall-clock ``ServingSession``.  Weights are random, from
``--seed``.  The model keeps every published width of ``qwen7b``; only
its depth is cut, to 3 of 32 layers (the model is dense, so one layer is
a whole period).  4 layers do not fit: served in float32, the fused
decode block needs 15.9 GB of the chip's 15.75 GB, because XLA keeps a
bf16 copy of every float32 weight for the MXU beside the weights
themselves (``tests/test_chip_compile.py`` holds the 3-layer programs
to the chip's memory).

Every phase passes or raises, in one process that holds the chip.  The
last line of standard output is the result, printed only when every
phase passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

With no TPU the script exits non-zero before serving: there is no CPU
fallback.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.request import TASKS, RequestState  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import paged_decode_attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.cluster import Cluster, ClusterConfig  # noqa: E402
from repro.serving.engine import EngineConfig  # noqa: E402
from repro.serving.session import ServingSession  # noqa: E402

# A v5e chip's HBM as XLA accounts for it ("... of 15.75G hbm").
HBM_BYTES = 15.75e9
N_LAYERS = 3
ENGINE = EngineConfig(n_slots=8, max_len=1024, page_size=16,
                      chunk_size=256, decode_block=8)
TRAFFIC = dict(n=8, tasks=("wikisql", "sharegpt"), prompt=(128, 768),
               out=(32, 64))
# Greedy tokens are compared with the float32 reference only where the
# reference's top-1 logit leads its top-2 by more than this fraction of
# the logits' spread (their standard deviation over the vocabulary at
# that position; about 0.16 with these random weights, whose head is
# initialized with std 1/sqrt(vocab)).  The served path runs float32
# matmuls at the TPU's default precision (one bf16 pass, unit roundoff
# 2^-8), the reference at "highest": rounding moves each matmul's output
# by well under 1% of its scale, compounding over the layers to about
# 1% of the logit spread.  A tenth of the spread leaves several times
# that and still checks over half the positions; a wrong cache page,
# mask or position flips tokens at any margin.  The check also reports
# the measured drift between a default- and a highest-precision forward.
LOGIT_MARGIN_FRAC = 0.1
# The paged kernel against its jnp oracle, as a fraction of the
# oracle's largest output: one bf16 rounding step (2^-8 ~ 0.4%) with
# headroom.  A wrong page, head or length mask errs by the full scale.
KERNEL_RTOL = 1e-2


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def describe_device() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def qwen7b_cut():
    return dataclasses.replace(get_config("qwen7b"), n_layers=N_LAYERS)


def make_traffic(seed: int, vocab: int, *, n: int, tasks, prompt,
                 out) -> list[dict]:
    """``n`` requests alternating over two Table-1 tasks: lengths drawn
    from each task's distribution and clipped to ``prompt``/``out``,
    prompt tokens uniform over the vocabulary, SLOs from Table 1."""
    rng = np.random.default_rng(seed)
    traffic = []
    for i in range(n):
        spec = TASKS[tasks[i % len(tasks)]]
        l_in, l_out = spec.sample_lengths(rng)
        l_in = int(np.clip(l_in, *prompt))
        traffic.append(dict(
            prompt=rng.integers(1, vocab, size=l_in, dtype=np.int32),
            task=spec.name, l_out=int(np.clip(l_out, *out)),
            ttft_slo=spec.ttft_slo, tpot_slo=spec.tpot_slo,
        ))
    return traffic


@dataclasses.dataclass
class Served:
    cluster: Cluster
    requests: list
    result: object        # ClusterResult
    streaming: dict       # StreamingStats.row()
    setup_s: float        # Cluster construction, compiles included
    serve_s: float        # first submit -> drained

    @property
    def tokens(self) -> list[list[int]]:
        return [list(r.generated) for r in self.requests]


def serve(model_cfg, engine_cfg: EngineConfig, traffic: list[dict], *,
          seed: int, mode: str = "collocated") -> Served:
    """One wall-clock session over a fresh cluster; every request must
    finish with its full ``l_out``."""
    t0 = time.perf_counter()
    cluster = Cluster(ClusterConfig(
        model=model_cfg, backend="engine", mode=mode, n_workers=1,
        n_prefill=1, n_decode=1, engine=engine_cfg, seed=seed,
    ))
    setup_s = time.perf_counter() - t0
    session = ServingSession(cluster, clock="wall", admission="none")
    t1 = time.perf_counter()
    handles = [session.submit(**r) for r in traffic]
    session.drain()
    serve_s = time.perf_counter() - t1
    result = session.close()
    reqs = [h.request for h in handles]
    for r in reqs:
        if r.state != RequestState.FINISHED or len(r.generated) != r.l_out:
            raise RuntimeError(
                f"request {r.rid} ended {r.state.value} with "
                f"{len(r.generated or [])}/{r.l_out} tokens")
    return Served(cluster, reqs, result, session.streaming.row(),
                  setup_s, serve_s)


def serve_report(s: Served) -> dict:
    n_tok = sum(len(r.generated) for r in s.requests)
    return {
        "setup_s": s.setup_s, "serve_s": s.serve_s,
        "n_requests": len(s.requests), "n_tokens": n_tok,
        "tokens_per_s": n_tok / s.serve_s,
        "ttft_p50_s": s.streaming["p50_ttfb"],
        "itl_p50_s": s.streaming["p50_itl"],
        "n_dispatches": s.result.n_dispatches,
        "decode_block_hist": s.result.decode_block_hist,
        "n_failed": s.result.metrics.n_failed,
        "n_rejected": s.result.metrics.n_rejected,
    }


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def reference_scorer(model, n_out: int):
    """Jitted ``(params, tokens (1, S), start) -> (top-1 minus top-2
    margin, argmax, logit spread, |default - highest| drift)`` of the
    reference logits at the ``n_out`` positions from ``start``."""
    def score(params, tokens, start):
        with jax.default_matmul_precision("highest"):
            ref_logits = model.forward(params, {"tokens": tokens})[0]
        logits = model.forward(params, {"tokens": tokens})[0]
        ref_logits, logits = (jax.lax.dynamic_slice_in_dim(x, start, n_out)
                              for x in (ref_logits, logits))
        top2, idx = jax.lax.top_k(ref_logits, 2)
        return (top2[:, 0] - top2[:, 1], idx[:, 0],
                jnp.std(ref_logits, axis=-1),
                jnp.max(jnp.abs(logits - ref_logits), axis=-1))

    return jax.jit(score)


def check_tokens(model, params, requests,
                 frac: float = LOGIT_MARGIN_FRAC) -> dict:
    """Re-score every request's prompt plus its generated tokens with
    ``Model.forward`` in float32 at "highest" matmul precision; where
    the reference's top-1 leads its top-2 by more than ``frac`` of the
    logits' spread, the served greedy token must be the reference
    argmax.  Sequences are right-padded to one length (causal attention
    keeps padding out of every scored position), so one program scores
    them all."""
    n_out = max(len(r.generated) for r in requests)
    length = max(r.l_in for r in requests) - 1 + n_out
    score = reference_scorer(model, n_out)
    margins, spreads, drifts, bad = [], [], [], []
    n_checked = 0
    for r in requests:
        gen = np.asarray(r.generated, np.int32)
        tokens = np.zeros((1, length), np.int32)
        seq = np.concatenate([np.asarray(r.prompt[: r.l_in]), gen])[:-1]
        tokens[0, : len(seq)] = seq
        margin, top1, spread, drift = (
            np.asarray(x)[: len(gen)]
            for x in score(params, jnp.asarray(tokens), r.l_in - 1))
        checked = margin > frac * spread
        n_checked += int(checked.sum())
        bad += [(r.rid, int(i)) for i in
                np.nonzero(checked & (top1 != gen))[0]]
        margins.append(margin)
        spreads.append(spread)
        drifts.append(drift)
    margins, spreads, drifts = (np.concatenate(x)
                                for x in (margins, spreads, drifts))
    out = {"requests": len(requests), "positions": len(margins),
           "checked": n_checked, "frac_of_spread": frac,
           "median_margin": float(np.median(margins)),
           "median_spread": float(np.median(spreads)),
           "max_drift_default_vs_highest": float(drifts.max()),
           "mismatched": bad}
    if bad:
        raise AssertionError(f"served tokens disagree with the reference "
                             f"where its margin is clear: {out}")
    if 2 * n_checked < len(margins):
        raise AssertionError(f"fewer than half the positions clear the "
                             f"margin, so the check says little: {out}")
    return out


def check_paged_kernel(model_cfg, engine_cfg: EngineConfig, seed: int, *,
                       interpret: bool = False) -> dict:
    """The paged-decode kernel at the served shapes against its jnp
    oracle (float32, "highest" precision), over a shuffled page pool
    with ragged lengths."""
    b, hq = engine_cfg.n_slots, model_cfg.n_heads
    hkv, d = model_cfg.n_kv_heads, model_cfg.resolved_head_dim
    ps = engine_cfg.page_size
    mp = -(-engine_cfg.max_len // ps)
    rng = np.random.default_rng(seed)
    kv_len = rng.integers(1, mp * ps + 1, size=b).astype(np.int32)
    table = np.full((b, mp), -1, np.int32)
    perm = rng.permutation(b * mp)
    for i, n in enumerate(-(-kv_len // ps)):
        table[i, :n] = perm[i * mp: i * mp + n]
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, hq, d), jnp.float32)
    pages = (b * mp, hkv, ps, d)
    kp = jax.random.normal(kk, pages, jnp.float32)
    vp = jax.random.normal(kv, pages, jnp.float32)
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(kv_len))
    got = paged_decode_attention(*args, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.paged_decode_attention_ref)(*args)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    out = {"shape_q": [b, hq, d], "shape_pages": list(pages),
           "max_abs_err": err, "ref_max_abs": scale, "rtol": KERNEL_RTOL}
    if not err <= KERNEL_RTOL * scale:
        raise AssertionError(f"paged kernel disagrees with its oracle: "
                             f"{out}")
    return out


def weights_report(params, n_layers: int, total_layers: int) -> dict:
    """What the depth cut does to the weight mix: the embedding and head
    keep their full size, so the vocabulary weighs more here than it
    would at full depth."""
    vocab = sum(params[k].nbytes for k in ("embed", "head") if k in params)
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    full = vocab + (total - vocab) * total_layers / n_layers
    return {"weight_bytes": total, "embed_head_bytes": vocab,
            "embed_head_share": vocab / total,
            "embed_head_share_at_full_depth": vocab / full,
            "note": f"embedding + head are {vocab / 1e9:.1f} GB of "
                    f"{total / 1e9:.1f} GB: {n_layers} of {total_layers} "
                    f"layers leave the vocabulary heavier than at full "
                    f"depth"}


def placement(engine) -> set:
    return {d for tree in (engine.params, engine.caches)
            for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def one_chip(seed: int) -> None:
    model_cfg = qwen7b_cut()
    full = get_config("qwen7b")
    emit("model", name=full.name, d_model=model_cfg.d_model,
         n_heads=model_cfg.n_heads, n_kv_heads=model_cfg.n_kv_heads,
         head_dim=model_cfg.resolved_head_dim, d_ff=model_cfg.d_ff,
         vocab=model_cfg.vocab_size, dtype="float32",
         cut=f"dataclasses.replace(get_config('qwen7b'), "
             f"n_layers={N_LAYERS}) of {full.n_layers} layers")
    traffic = make_traffic(seed, model_cfg.vocab_size, **TRAFFIC)
    s = serve(model_cfg, ENGINE, traffic, seed=seed)
    eng = s.cluster.workers[0].engine
    emit("serve", **serve_report(s))
    check_peak("after serving")
    emit("weights", **weights_report(eng.params, model_cfg.n_layers,
                                     full.n_layers))
    emit("kernel", **check_paged_kernel(model_cfg, ENGINE, seed))
    emit("correctness", **check_tokens(eng.model, eng.params, s.requests))
    check_peak("at exit")


def check_peak(when: str) -> None:
    """The device's peak bytes so far must stay under the chip's HBM."""
    peak = peak_bytes(jax.devices()[0])
    emit("memory", when=when, peak_bytes_in_use=peak, limit_bytes=HBM_BYTES)
    if peak is None or peak >= HBM_BYTES:
        raise AssertionError(f"peak device bytes {peak} {when} not under "
                             f"{HBM_BYTES:.0f}")


def pd_across_chips(model_cfg, engine_cfg: EngineConfig,
                    traffic: list[dict], seed: int) -> dict:
    """P/D replicas on devices 0 and 1 and a d2d provision onto device
    2, against the one-replica collocated run of the same requests."""
    devs = jax.devices()
    base = serve(model_cfg, engine_cfg, traffic, seed=seed)
    want = base.tokens
    emit("collocated", **serve_report(base))
    del base
    gc.collect()

    pd = serve(model_cfg, engine_cfg, traffic, seed=seed, mode="pd")
    emit("pd", **serve_report(pd), kv_transfers=pd.result.kv_transfers,
         n_kv_moves=pd.result.metrics.n_kv_moves)
    if pd.tokens != want:
        raise AssertionError("P/D tokens differ from the collocated run")
    if pd.result.metrics.n_migrated != len(traffic):
        raise AssertionError(f"only {pd.result.metrics.n_migrated} of "
                             f"{len(traffic)} requests crossed to decode")
    homes = [placement(w.engine) for w in pd.cluster.workers]
    if homes != [{devs[0]}, {devs[1]}]:
        raise AssertionError(f"replicas not one per device: {homes}")

    wm = pd.cluster.weights
    params, dt = wm.provision(2, "d2d", donor=0)
    got = {d for leaf in jax.tree.leaves(params) for d in leaf.devices()}
    if got != {devs[2]}:
        raise AssertionError(f"d2d replica landed on {got}, not {devs[2]}")
    for x, h in zip(jax.tree.leaves(params), jax.tree.leaves(wm.host)):
        if not np.array_equal(np.asarray(x), h):
            raise AssertionError("d2d copy differs from the seed weights")
    wm.release(2)
    return {"replica_devices": [str(d) for h in homes for d in h],
            "d2d_device": str(devs[2]), "d2d_s": dt,
            "d2d_bytes": wm.nbytes, "tokens_equal": True}


def four_chips(seed: int) -> None:
    model_cfg = qwen7b_cut()
    traffic = make_traffic(seed, model_cfg.vocab_size, **TRAFFIC)
    emit("pd_across_chips",
         **pd_across_chips(model_cfg, ENGINE, traffic, seed))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    dev = describe_device()
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev['platform']!r}); this check runs only on a chip")
    if dev["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{dev['count']} device(s)")
    emit("device", **dev, compile_cache=cache_dir)
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
