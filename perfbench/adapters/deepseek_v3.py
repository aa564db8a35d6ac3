"""The program's view of a DeepSeek-V3-family configuration and weights.

``model_config`` maps the benchmark's configuration file (Hugging Face
keys) onto the program's ``ModelConfig``: latent attention in every
layer, the first ``first_k_dense_replace`` with a dense FFN, the rest
with the dropless expert layer holding ``n_routed_experts`` experts from
``held_experts.first`` of a router ``held_experts.router_width`` wide.
``program_params`` lays the reference's weights out as the program's
``Model.init`` tree.  Both are harness code: the reference never
imports them.
"""

from __future__ import annotations

import jax.numpy as jnp


def model_config(cfg: dict):
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

    if (cfg["scoring_func"], cfg["topk_method"]) != ("sigmoid", "noaux_tc"):
        raise NotImplementedError("router other than sigmoid / noaux_tc")
    if (cfg["n_group"], cfg["topk_group"], cfg.get("moe_layer_freq", 1),
            bool(cfg["norm_topk_prob"]), cfg.get("q_lora_rank")) != (
                1, 1, 1, True, None):
        raise NotImplementedError("group-limited routing, sparse MoE layers, "
                                  "unnormalized weights or a query latent")
    held = cfg.get("held_experts", {})
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=hq,
        n_kv_heads=hq, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), source=cfg["source"],
        first_dense=cfg["first_k_dense_replace"],
        # at one bf16 pass upstream, the router's top-k flips against the
        # float32 reference often enough to move served tokens as far as
        # an fp8 forward does (PERF.md, §6)
        matmul_precision="highest",
        mla=MLAConfig(
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
        ),
        moe=MoEConfig(
            num_experts=held.get("router_width", cfg["n_routed_experts"]),
            top_k=cfg["num_experts_per_tok"],
            expert_d_ff=cfg["moe_intermediate_size"], router="sigmoid",
            routed_scaling=float(cfg["routed_scaling_factor"]),
            n_shared=cfg["n_shared_experts"],
            n_held=cfg["n_routed_experts"],
            held_offset=held.get("first", 0),
        ),
    )


def _halves(w, rope: int):
    """Reorder the last ``rope`` columns of ``w`` from the checkpoint's
    interleaved pairs to the rotate-half layout of the program's RoPE
    (``(x0, x2, ..., x1, x3, ...)``), as the HF code does at run time."""
    perm = jnp.concatenate([jnp.arange(0, rope, 2), jnp.arange(1, rope, 2)])
    return jnp.concatenate([w[..., :-rope], w[..., -rope:][..., perm]], -1)


def _attn(p: dict) -> dict:
    """Sizes from the shapes: wq (L, d, H, nope + rope), wkv_a
    (L, d, lora + rope), wkv_b (L, lora, H, nope + v), wo (L, H, v, d)."""
    nl, d, _, qk = p["wq"].shape
    lora = p["kv_norm"].shape[-1]
    rope = p["wkv_a"].shape[-1] - lora
    nope = qk - rope
    kvb = p["wkv_b"]
    return {
        "wq": _halves(p["wq"], rope).reshape(nl, d, -1),
        "wkv_a": _halves(p["wkv_a"], rope),
        "kv_norm": p["kv_norm"] - 1.0,
        "wk_b": kvb[..., :nope].transpose(0, 2, 3, 1),  # (L, H, nope, lora)
        "wv_b": kvb[..., nope:].transpose(0, 2, 1, 3),  # (L, H, lora, v)
        "wo": p["wo"].reshape(nl, -1, p["wo"].shape[-1]),
    }


def program_params(w: dict) -> dict:
    """Reference weights -> the program's tree: a segment of dense
    latent-attention blocks, then one of expert blocks (either left out
    where it has no layers).  The program's RMSNorm scales by ``1 + p``,
    so it gets ``w - 1`` (exact: the reference's norm weights are
    multiples of 2^-10 near 1)."""
    dn, mo = w["dense"], w["moe"]
    dense = {"attn": _attn(dn), "ln1": dn["ln1"] - 1.0,
             "ln2": dn["ln2"] - 1.0,
             "ffn": {k: dn[k] for k in ("w_gate", "w_up", "w_down")}}
    moe = {"attn": _attn(mo), "ln1": mo["ln1"] - 1.0,
           "ln2": mo["ln2"] - 1.0,
           "moe": {"w_router": mo["router"], "router_bias": mo["router_bias"],
                   "w_gate": mo["e_gate"], "w_up": mo["e_up"],
                   "w_down": mo["e_down"], "ws_gate": mo["s_gate"],
                   "ws_up": mo["s_up"], "ws_down": mo["s_down"]}}
    segments = [s for s, p in ((dense, dn), (moe, mo))
                if p["ln1"].shape[0]]
    return {"embed": w["embed"], "head": w["head"],
            "final_norm": w["final_norm"] - 1.0, "segments": segments}
