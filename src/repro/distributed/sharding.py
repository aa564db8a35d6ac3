"""Logical-axis sharding rules.

Models annotate activations/params with *logical* axis names; the
launcher installs an :class:`AxisRules` mapping logical names to physical
mesh axes.  Outside a rules context every annotation is a no-op, so the
same model code runs unsharded on CPU and fully sharded on the
production mesh.  Keeping the mapping in one place is also the main
hill-climbing knob: changing `batch/seq/ff/...` bindings re-shards the
whole system without touching model code.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Sequence[str], None]

# Logical axes used across the codebase:
#   batch      — request/sequence batch dim
#   seq        — token position dim (activations)
#   heads      — attention query heads
#   kv_heads   — attention kv heads (post TP-replication)
#   embed      — d_model activation dim
#   ff         — MLP hidden dim
#   vocab      — vocabulary dim
#   experts    — MoE expert dim
#   layers     — stacked-layer leading dim of scanned params
#   fsdp       — the dim of each weight sharded ZeRO-style (params only)
#   ssm_heads  — mamba heads
#   ssm_inner  — mamba d_inner channel dim


class AxisRules:
    def __init__(self, mapping: dict[str, Axis], mesh: Optional[Mesh] = None):
        self.mapping = dict(mapping)
        self.mesh = mesh

    def spec(self, logical: Sequence[Optional[str]]) -> P:
        phys = []
        used: set[str] = set()
        for name in logical:
            ax = self.mapping.get(name) if name else None
            if ax is None:
                phys.append(None)
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            axes = tuple(a for a in axes if a not in used)
            used.update(axes)
            phys.append(axes if len(axes) > 1 else (axes[0] if axes else None))
        # trim trailing Nones for tidiness
        while phys and phys[-1] is None:
            phys.pop()
        return P(*phys)

    def sharding(self, logical: Sequence[Optional[str]]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(logical))


_tls = threading.local()


def current_rules() -> Optional[AxisRules]:
    return getattr(_tls, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[AxisRules]):
    prev = getattr(_tls, "rules", None)
    _tls.rules = rules
    try:
        yield rules
    finally:
        _tls.rules = prev


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a sharding constraint by logical axis names (no-op w/o rules).

    Inside a partial-manual ``shard_map`` (e.g. the compressed pod-axis
    gradient sync) the trace context carries an AbstractMesh whose
    manual axes differ from the rules' concrete mesh — constraints must
    then be expressed against the context mesh.
    """
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    spec = rules.spec(logical)
    try:
        ctx = jax.sharding.get_abstract_mesh()
    except Exception:  # pragma: no cover
        ctx = None
    if ctx is not None and ctx.axis_names:
        used = {a for part in spec for a in (
            (part,) if isinstance(part, str) else (part or ())
        )}
        if used <= set(ctx.axis_names):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(ctx, spec)
            )
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec)
    )


# ---------------------------------------------------------------------------
# Canonical rule sets
# ---------------------------------------------------------------------------


def baseline_rules(mesh: Mesh, *, fsdp: bool = True,
                   shard_seq: bool = False,
                   exclude_pod: bool = False) -> AxisRules:
    """Paper-faithful baseline: DP over ('pod','data'), TP over 'model'.

    ``fsdp=True`` additionally shards one non-TP dim of every weight over
    the data axis (ZeRO-3 style); ``shard_seq`` moves activation sequence
    sharding onto the data axis (used when batch < data axis size, e.g.
    long_500k decode, and for sequence-parallel prefill).
    ``exclude_pod`` removes 'pod' from the data axes — required when the
    pod-axis gradient sync runs manually (compressed cross-pod DP).
    """
    names = ("data",) if exclude_pod else ("pod", "data")
    data_axes = tuple(a for a in names if a in mesh.axis_names)
    mapping: dict[str, Axis] = {
        "batch": data_axes,
        "seq": data_axes if shard_seq else None,
        "heads": "model",
        "kv_heads": "model",
        "embed": None,
        "residual": None,
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "layers": None,
        "fsdp": data_axes if fsdp else None,
        "ssm_heads": "model",
        "ssm_inner": "model",
        "heads_fused": "model",
        "kv_fused": "model",
        "qblocks": None,
        "cache_seq": None,
    }
    return AxisRules(mapping, mesh)


def _axis_size(mesh: Mesh, names) -> int:
    if names is None:
        return 1
    names = (names,) if isinstance(names, str) else names
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def plan_arch(cfg, mesh: Mesh) -> dict:
    """Divisibility-driven sharding decisions for one architecture.

    Returns {kv_repeat, heads_sharded, vocab_pad} — the knobs the
    launcher must apply consistently to the Model and the AxisRules.
    """
    m = mesh.shape["model"]
    heads_ok = cfg.n_heads > 0 and cfg.n_heads % m == 0
    kv_repeat = 1
    if heads_ok and cfg.n_kv_heads > 0:
        if cfg.n_kv_heads % m == 0:
            kv_repeat = 1
        elif m % cfg.n_kv_heads == 0:
            kv_repeat = m // cfg.n_kv_heads
            # GQA grouping must stay integral after repetition
            if cfg.n_heads % (cfg.n_kv_heads * kv_repeat) != 0:
                kv_repeat = 1
    kv_eff = max(cfg.n_kv_heads, 1) * kv_repeat
    kv_sharded = heads_ok and kv_eff % m == 0
    vocab_pad = 0
    if cfg.vocab_size % m != 0 and cfg.vocab_size > 10_000:
        vocab_pad = (-cfg.vocab_size) % m
    return {
        "heads_sharded": heads_ok,
        "kv_repeat": kv_repeat,
        "kv_sharded": kv_sharded,
        "vocab_pad": vocab_pad,
    }


def arch_rules(cfg, mesh: Mesh, *, stage: str = "train",
               fsdp: bool = True, exclude_pod: bool = False,
               shard_residual: Optional[bool] = None,
               batch_size: Optional[int] = None) -> AxisRules:
    """AxisRules specialized to one architecture + execution stage.

    stage: "train" | "prefill" | "decode" | "decode_long".
    Every mapping degrades to None when the dimension does not divide
    the target axis, so lowering always succeeds; the roofline then
    shows the replication cost (e.g. gemma3's 8 heads on a 16-way model
    axis fall back to sequence-parallel attention via 'qblocks').
    """
    plan = plan_arch(cfg, mesh)
    m = mesh.shape["model"]
    rules = baseline_rules(mesh, fsdp=fsdp, exclude_pod=exclude_pod)
    mp = rules.mapping
    data_axes = mp["batch"]

    heads = "model" if plan["heads_sharded"] else None
    mp["heads"] = heads
    mp["heads_fused"] = heads
    mp["kv_heads"] = "model" if plan["kv_sharded"] else None
    mp["kv_fused"] = ("model" if (plan["kv_sharded"]
                                  and plan["kv_repeat"] == 1) else None)
    mp["qblocks"] = None if plan["heads_sharded"] else "model"
    mp["vocab"] = ("model"
                   if (cfg.vocab_size + plan["vocab_pad"]) % m == 0
                   else None)
    mp["ff"] = "model" if (cfg.d_ff == 0 or cfg.d_ff % m == 0) else None
    if cfg.moe is not None:
        mp["experts"] = ("model" if cfg.moe.num_experts % m == 0 else None)
        mp["ff"] = ("model" if cfg.moe.expert_d_ff % m == 0 else mp["ff"])
    if cfg.ssm is not None:
        di = cfg.ssm.d_inner(cfg.d_model)
        h = cfg.ssm.n_heads(cfg.d_model)
        mp["ssm_inner"] = "model" if di % m == 0 else None
        mp["ssm_heads"] = "model" if h % m == 0 else None
    # fsdp viability: every fsdp'd dim here is d_model or expert d_model
    if cfg.d_model % _axis_size(mesh, mp["fsdp"]) != 0:
        mp["fsdp"] = None

    if batch_size is not None and data_axes:
        if batch_size % _axis_size(mesh, data_axes) != 0:
            # drop pod first, then give up on batch sharding
            if (len(data_axes) > 1
                    and batch_size % mesh.shape[data_axes[-1]] == 0):
                mp["batch"] = (data_axes[-1],)
            else:
                mp["batch"] = None

    if shard_residual is None:
        shard_residual = stage == "train"
    mp["residual"] = ("model" if (shard_residual and cfg.d_model % m == 0)
                      else None)

    if stage == "decode_long":
        # batch=1: shard the KV/state sequence dim instead
        mp["batch"] = None
        mp["cache_seq"] = tuple(
            a for a in (("data",) if exclude_pod else ("pod", "data"))
            if a in mesh.axis_names
        ) + ("model",)
    elif stage == "decode":
        mp["cache_seq"] = "model"  # dropped per-tensor when kv uses it
        mp["residual"] = None
    return rules

