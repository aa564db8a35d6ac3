"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 host placeholder devices, lowers the jitted
train/prefill/decode step with full-size ShapeDtypeStruct inputs and
explicit NamedShardings, compiles, and extracts

- memory_analysis()        -> bytes/device (fits or not),
- cost_analysis()          -> per-device HLO FLOPs / bytes,
- the compiled HLO's collective ops -> bytes over the interconnect,

which EXPERIMENTS.md §Dry-run / §Roofline consume.
"""

# The VERY FIRST lines — before any other import — because jax locks the
# device count at first init.
import os  # noqa: E402

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, mfu_flops  # noqa: E402
from repro.configs.base import ModelConfig, ShapeSpec  # noqa: E402
from repro.distributed.sharding import arch_rules, plan_arch, use_rules  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.build import build_model  # noqa: E402
from repro.training.data import DataConfig, batch_spec  # noqa: E402
from repro.training.optimizer import adamw_init  # noqa: E402
from repro.training.train_loop import TrainConfig, build_train_step  # noqa: E402

# ---------------------------------------------------------------------------
# Hardware constants (TPU v5e) for the roofline terms
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12     # bf16 / chip
HBM_BW = 819e9          # bytes/s / chip
LINK_BW = 50e9          # bytes/s / ICI link

_DTYPE_BYTES = {
    "f64": 8, "u64": 8, "s64": 8, "f32": 4, "u32": 4, "s32": 4,
    "bf16": 2, "f16": 2, "u16": 2, "s16": 2,
    "u8": 1, "s8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_COLL_RE = re.compile(
    r"=\s*(.*?)\s*(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\("
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum collective payload bytes (per-device module) by op kind."""
    out = {k: {"bytes": 0, "count": 0} for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # async pair: count the -start only
        result_type, kind = m.group(1), m.group(2)
        out[kind]["bytes"] += _shape_bytes(result_type)
        out[kind]["count"] += 1
    # effective on-link bytes: ring all-reduce moves ~2x payload
    link_bytes = sum(
        v["bytes"] * (2.0 if k == "all-reduce" else 1.0)
        for k, v in out.items()
    )
    out["link_bytes"] = link_bytes
    return out


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


def _shardings_of(rules, axes_tree):
    return jax.tree.map(
        lambda axes: rules.sharding(tuple(axes)),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def build_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
               compress: bool = False, fsdp: bool = True,
               shard_residual=None, remat: bool = True,
               q_chunk: int = 512, unroll: bool = True,
               train_kv_repeat: bool = False):
    """Returns (lower_fn) which produces the jax lowered object."""
    plan = plan_arch(cfg, mesh)
    stage = ("train" if shape.kind == "train" else
             "prefill" if shape.kind == "prefill" else
             ("decode_long" if shape.seq_len > 100_000 else "decode"))
    rules = arch_rules(
        cfg, mesh, stage=stage, fsdp=fsdp,
        exclude_pod=compress and shape.kind == "train",
        shard_residual=shard_residual,
        batch_size=shape.global_batch,
    )
    p = jax.sharding.PartitionSpec

    def repl():
        return jax.sharding.NamedSharding(mesh, p())

    if shape.kind == "train":
        model = build_model(
            cfg, param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
            kv_repeat=plan["kv_repeat"] if train_kv_repeat else 1,
            remat=remat, q_chunk=q_chunk,
            vocab_pad=plan["vocab_pad"], unroll=unroll,
        )
        params_abs = model.abstract_params()
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        p_sh = _shardings_of(rules, model.param_axes())
        opt_sh = type(opt_abs)(
            step=repl(),
            mu=p_sh, nu=p_sh,
            norm_ema=repl(),
        )
        data = DataConfig(batch=shape.global_batch, seq_len=shape.seq_len)
        batch_abs = batch_spec(cfg, data)
        b_axes = {
            k: (("batch", "seq", "embed") if k == "frames"
                else ("batch", "seq"))
            for k in batch_abs
        }
        b_sh = {k: rules.sharding(v) for k, v in b_axes.items()}
        step_fn = build_train_step(
            model, TrainConfig(grad_compression=compress), mesh
        )
        jitted = jax.jit(step_fn, in_shardings=(p_sh, opt_sh, b_sh),
                         donate_argnums=(0, 1))

        def lower():
            with use_rules(rules):
                return jitted.lower(params_abs, opt_abs, batch_abs)

        return lower

    # serving stages: bf16 params
    model = build_model(
        cfg, param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
        kv_repeat=plan["kv_repeat"], remat=False, q_chunk=q_chunk,
        vocab_pad=plan["vocab_pad"], unroll=unroll,
    )
    params_abs = model.abstract_params()
    p_sh = _shardings_of(rules, model.param_axes())

    if shape.kind == "prefill":
        b, s = shape.global_batch, shape.seq_len
        if cfg.frontend == "frames":
            tok_abs = jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                           jnp.bfloat16)
            tok_sh = rules.sharding(("batch", "seq", "embed"))
        else:
            tok_abs = jax.ShapeDtypeStruct((b, s), jnp.int32)
            tok_sh = rules.sharding(("batch", "seq"))
        lens_abs = jax.ShapeDtypeStruct((b,), jnp.int32)

        def prefill_fn(params, tokens, lens):
            return model.prefill(params, tokens, lens)

        jitted = jax.jit(
            prefill_fn,
            in_shardings=(p_sh, tok_sh, rules.sharding(("batch",))),
        )

        def lower():
            with use_rules(rules):
                return jitted.lower(params_abs, tok_abs, lens_abs)

        return lower

    # decode
    b, s = shape.global_batch, shape.seq_len
    cache_abs = jax.eval_shape(lambda: model.init_cache(b, s))
    c_sh = _shardings_of(rules, model.cache_logical_axes())
    tok_abs = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos_abs = jax.ShapeDtypeStruct((b,), jnp.int32)
    b_sh = rules.sharding(("batch",))

    jitted = jax.jit(
        model.decode_step,
        in_shardings=(p_sh, c_sh, b_sh, b_sh),
        donate_argnums=(1,),
    )

    def lower():
        with use_rules(rules):
            return jitted.lower(params_abs, cache_abs, tok_abs, pos_abs)

    return lower


# ---------------------------------------------------------------------------
# Analysis
# ---------------------------------------------------------------------------


def _cost_of(compiled) -> dict:
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["link_bytes"]),
        "coll_detail": {k: v for k, v in coll.items()
                        if k != "link_bytes"},
    }


def measure_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, **kw) -> dict:
    """Two-phase measurement.

    Phase 1 (memory): compile the full-depth scan-over-layers program —
    compact HLO, exact buffer accounting -> memory_analysis().

    Phase 2 (cost): XLA's cost model counts loop bodies once, so
    per-device FLOPs/bytes/collective-bytes come from *block
    extrapolation*: compile a 0-layer variant (embed+head+loss+opt) and
    a 1-layer variant per block kind with all loops unrolled; the exact
    per-layer increment is the difference, and the cell total is
    f0 + sum_k count_k * delta_k.  (The weight-shared zamba2 block
    over-counts its optimizer update 12x — negligible.)
    """
    import dataclasses as dc

    rec: dict = {}
    # ---- phase 1: memory (scan, full depth) ----
    t0 = time.time()
    lowered = build_cell(cfg, shape, mesh, unroll=False, **kw)()
    compiled = lowered.compile()
    rec["compile_scan_s"] = round(time.time() - t0, 1)
    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "code_bytes": int(mem.generated_code_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": repr(e)}
    rec["scan_flops_per_device"] = float(
        compiled.cost_analysis().get("flops", 0.0)
    )

    # ---- phase 2: block-extrapolated cost ----
    counts: dict[str, int] = {}
    for kind, cnt in cfg.layer_pattern():
        counts[kind] = counts.get(kind, 0) + cnt

    def variant_cost(pattern):
        vcfg = dc.replace(cfg, pattern_override=tuple(pattern))
        lw = build_cell(vcfg, shape, mesh, unroll=True, **kw)()
        return _cost_of(lw.compile())

    t1 = time.time()
    f0 = variant_cost(())
    deltas = {}
    for kind in counts:
        f1 = variant_cost(((kind, 1),))
        deltas[kind] = {
            m: max(0.0, f1[m] - f0[m])
            for m in ("flops", "bytes", "coll_bytes")
        }
    rec["cost_passes_s"] = round(time.time() - t1, 1)

    totals = {
        m: f0[m] + sum(counts[k] * deltas[k][m] for k in counts)
        for m in ("flops", "bytes", "coll_bytes")
    }
    rec["cost_method"] = "block-extrapolated"
    rec["base_cost"] = {m: f0[m] for m in ("flops", "bytes",
                                           "coll_bytes")}
    rec["per_layer"] = deltas
    rec["layer_counts"] = counts
    rec["flops_per_device"] = totals["flops"]
    rec["bytes_per_device"] = totals["bytes"]
    rec["collective_bytes_per_device"] = totals["coll_bytes"]
    rec["collectives"] = f0["coll_detail"]
    return rec


def analyze_terms(rec: dict, cfg: ModelConfig, shape: ShapeSpec,
                  mesh) -> None:
    chips = mesh.size
    rec["chips"] = chips
    rec["compute_term_s"] = rec["flops_per_device"] / PEAK_FLOPS
    rec["memory_term_s"] = rec["bytes_per_device"] / HBM_BW
    rec["collective_term_s"] = (
        rec["collective_bytes_per_device"] / LINK_BW
    )
    terms = {
        "compute": rec["compute_term_s"],
        "memory": rec["memory_term_s"],
        "collective": rec["collective_term_s"],
    }
    rec["bottleneck"] = max(terms, key=terms.get)
    model_flops = mfu_flops(cfg, shape)
    rec["model_flops"] = model_flops
    total_hlo = rec["flops_per_device"] * chips
    rec["useful_flops_ratio"] = (
        model_flops / total_hlo if total_hlo > 0 else 0.0
    )
    # roofline fraction: ideal time of the dominant resource over the
    # sum of all three (a serial, no-overlap pessimistic bound)
    tsum = sum(terms.values())
    rec["roofline_fraction"] = (
        max(terms.values()) / tsum if tsum > 0 else 0.0
    )


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             pad_heads: int = 0, **kw) -> dict:
    import dataclasses as dc

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    kw.pop("unroll", None)  # phase-controlled inside measure_cell

    # hillclimb knob: pad attention heads to the TP degree
    run_cfg = cfg
    if pad_heads:
        run_cfg = dc.replace(run_cfg, n_heads=cfg.n_heads + pad_heads)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(zip(mesh.axis_names,
                               [mesh.shape[a] for a in mesh.axis_names])),
        "options": {k: v for k, v in kw.items()},
    }
    if pad_heads:
        rec["options"]["pad_heads"] = pad_heads
    t0 = time.time()
    try:
        rec.update(measure_cell(run_cfg, shape, mesh, **kw))
        # model_flops / useful ratio always judged against the
        # *published* config — padding counts as overhead
        analyze_terms(rec, cfg, shape, mesh)
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="runs/dryrun.jsonl")
    ap.add_argument("--compress", action="store_true",
                    help="int8 cross-pod gradient sync (train cells)")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-residual-shard", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=512)
    ap.add_argument("--pad-heads", type=int, default=0)
    ap.add_argument("--train-kv-repeat", action="store_true",
                    help="repeat KV heads to the TP degree in train "
                         "cells (fixes uneven GQA head sharding)")
    ap.add_argument("--scan-layers", action="store_true",
                    help="keep lax.scan over layers (compact HLO, but "
                         "cost analysis undercounts loop bodies)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = (list(ASSIGNED_ARCHS) if args.arch == "all"
             else args.arch.split(","))
    meshes = (["single", "multi"] if args.mesh == "both"
              else [args.mesh])

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    seen = set()
    if args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") == "ok":
                        seen.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    kw = dict(
        compress=args.compress,
        fsdp=not args.no_fsdp,
        remat=not args.no_remat,
        shard_residual=(False if args.no_residual_shard else None),
        q_chunk=args.q_chunk,
        unroll=not args.scan_layers,
        pad_heads=args.pad_heads,
        train_kv_repeat=args.train_kv_repeat,
    )
    n_ok = n_fail = 0
    with open(args.out, "a") as f:
        for arch in archs:
            cfg = get_config(arch)
            shapes = ([s.name for s in cfg.shapes()]
                      if args.shape == "all" else args.shape.split(","))
            for shape_name in shapes:
                for mesh_kind in meshes:
                    key = (arch, shape_name, mesh_kind)
                    if key in seen:
                        continue
                    rec = run_cell(arch, shape_name, mesh_kind, **kw)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    ok = rec["status"] == "ok"
                    n_ok += ok
                    n_fail += not ok
                    msg = (
                        f"[{'OK' if ok else 'FAIL'}] {arch} x {shape_name}"
                        f" x {mesh_kind} ({rec['total_s']}s)"
                    )
                    if ok:
                        msg += (
                            f" bottleneck={rec['bottleneck']}"
                            f" c={rec['compute_term_s']:.3e}"
                            f" m={rec['memory_term_s']:.3e}"
                            f" x={rec['collective_term_s']:.3e}"
                            f" useful={rec['useful_flops_ratio']:.2f}"
                        )
                    else:
                        msg += " " + rec.get("error", "")[:200]
                    print(msg, flush=True)
            # documented skips
            for sname, why in cfg.skipped_shapes():
                if args.shape == "all":
                    print(f"[SKIP] {arch} x {sname}: {why}", flush=True)
    print(f"dry-run complete: {n_ok} ok, {n_fail} fail")


if __name__ == "__main__":
    main()
