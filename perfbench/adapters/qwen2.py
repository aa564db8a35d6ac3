"""The program's view of a Qwen2-family configuration and its weights.

``model_config`` maps the benchmark's configuration file (Hugging Face
keys) onto the program's ``ModelConfig``; ``program_params`` lays the
reference's weights out as the program's ``Model.init`` tree.  Both are
harness code: the reference never imports them.
"""

from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=hq,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // hq,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), source=cfg["source"],
    )


def program_params(w: dict) -> dict:
    """Reference weights -> the program's tree (one uniform segment of
    dense blocks).  The program's RMSNorm scales by ``1 + p``, so it
    gets ``w - 1``: exact, as the reference's norm weights are
    multiples of 2^-10 near 1, so ``1 + (w - 1)`` gives ``w`` back."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")}
    ffn = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    return {
        "embed": w["embed"], "head": w["head"],
        "final_norm": w["final_norm"] - 1.0,
        "segments": [{"attn": attn, "ffn": ffn, "ln1": w["ln1"] - 1.0,
                      "ln2": w["ln2"] - 1.0}],
    }
