"""Model / shape / mesh configuration for the HyperFlexis reproduction.

Every assigned architecture is expressed as a :class:`ModelConfig`.  The
config captures the exact published hyper-parameters plus the *layer
pattern* used by the generic model builders in ``repro.models``:

- ``dense``        — standard pre-norm GQA transformer block
- ``moe``          — GQA attention + top-k mixture-of-experts FFN
- ``mla``          — latent attention (MLA) + mixture-of-experts FFN
- ``mla_dense``    — latent attention (MLA) + dense SwiGLU FFN
- ``mamba``        — Mamba-2 SSD block (attention free)
- ``local``        — sliding-window (local) GQA attention block
- ``global``       — full (global) GQA attention block
- ``shared_attn``  — a *weight-shared* attention block (Zamba-2 style)
- ``encoder``      — bidirectional (non-causal) attention block

A model is a sequence of *segments* ``(kind, count)``; homogeneous
segments are stacked and executed with ``jax.lax.scan`` so the lowered
HLO stays compact even for 64+ layer models.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Shapes (assigned input-shape suite, identical for every LM architecture)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One (seq_len, global_batch) evaluation cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """A dropless top-k expert layer.

    The router scores all ``num_experts``; this chip holds the
    ``n_held`` experts from ``held_offset`` on (0 = all of them) and
    computes every assignment to them, with no capacity.  What the
    experts held elsewhere would add is not computed here: under expert
    parallelism it arrives from the chips that hold them.
    """

    num_experts: int    # router width: every expert is routed over
    top_k: int
    expert_d_ff: int
    # "softmax": weights are the chosen softmax probabilities;
    # "sigmoid": DeepSeek-V3's noaux_tc, selection on sigmoid scores
    # plus a per-expert bias, weights from the unbiased scores
    router: str = "softmax"
    routed_scaling: float = 1.0     # scales the k renormalized weights
    # shared experts, run for every token and added ungated: one SwiGLU
    # of width n_shared * expert_d_ff
    n_shared: int = 0
    n_held: int = 0
    held_offset: int = 0

    @property
    def held(self) -> int:
        return self.n_held or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3).  Each token keeps
    one ``kv_lora_rank`` latent and one ``qk_rope_head_dim`` rotary key
    shared by all heads; keys and values are per-head up-projections of
    the latent."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """A cached row: the latent and the rotary key, zero-padded to
        the TPU's 128-lane tiling (576 -> 640), which a page's DMA
        needs."""
        return -(-self.latent_dim // 128) * 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) block hyper-parameters."""

    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    n_groups: int = 1
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    causal: bool = True
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    # Sliding-window attention (gemma3-style local:global interleave).
    window: int = 0  # 0 -> no local attention
    local_global_ratio: int = 0  # e.g. 5 -> 5 local : 1 global
    # MoE / SSM extensions.
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # Latent attention in every attention block; the first
    # ``first_dense`` layers carry a dense FFN of width d_ff, the rest
    # the MoE (DeepSeek-V3's first_k_dense_replace).
    mla: Optional[MLAConfig] = None
    first_dense: int = 0
    # Precision of every matmul the model's programs trace
    # (``jax.default_matmul_precision``); None keeps JAX's default, one
    # bf16 pass on a TPU.  A router that picks experts on near-ties
    # needs "highest": one bf16 pass upstream flips its choices.
    matmul_precision: Optional[str] = None
    # Hybrid (zamba2): one weight-shared attention block invoked every
    # ``shared_attn_period`` SSM layers.
    shared_attn_period: int = 0
    # Modality frontend stub: "token" (LM), "frames" (audio encoder
    # consumes precomputed frame embeddings), "token+vq" (chameleon:
    # early-fusion VQ image tokens share the text vocab).
    frontend: str = "token"
    source: str = ""
    # Direct layer-pattern override (dry-run block-cost measurement
    # builds 0- and 1-layer variants of the same config).
    pattern_override: Optional[Tuple[Tuple[str, int], ...]] = None

    # -- derived -----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def kv_width(self) -> int:
        """Cached values per token and attention layer: K and V of every
        KV head, or latent attention's one latent and rotary key."""
        if self.mla is not None:
            return self.mla.latent_dim
        return 2 * self.n_kv_heads * self.resolved_head_dim

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def sub_quadratic(self) -> bool:
        """Whether the arch supports very-long-context decode (long_500k).

        SSM and hybrid architectures keep O(1) state per token; gemma3's
        5:1 local:global pattern bounds the quadratic portion to 1/6 of
        layers, so we run it too.  Pure full-attention archs are skipped
        (documented in DESIGN.md §Arch-applicability).
        """
        return self.family in ("ssm", "hybrid") or self.local_global_ratio > 0

    def layer_pattern(self) -> Tuple[Tuple[str, int], ...]:
        """Return the segment list ``((kind, count), ...)``."""
        if self.pattern_override is not None:
            return self.pattern_override
        if self.family == "ssm":
            return (("mamba", self.n_layers),)
        if self.family == "hybrid":
            # zamba2: groups of `shared_attn_period` mamba layers followed by
            # one shared attention invocation; remainder mamba layers at the
            # end so the *total* (mamba + shared invocations) == n_layers.
            p = self.shared_attn_period
            n_groups = self.n_layers // (p + 1)
            tail = self.n_layers - n_groups * (p + 1)
            segs: list[Tuple[str, int]] = []
            for _ in range(n_groups):
                segs.append(("mamba", p))
                segs.append(("shared_attn", 1))
            if tail:
                segs.append(("mamba", tail))
            return tuple(segs)
        if self.local_global_ratio > 0:
            r = self.local_global_ratio
            n_groups = self.n_layers // (r + 1)
            tail = self.n_layers - n_groups * (r + 1)
            segs = []
            for _ in range(n_groups):
                segs.append(("local", r))
                segs.append(("global", 1))
            if tail:
                segs.append(("local", tail))
            return tuple(segs)
        if self.mla is not None:
            if self.moe is None:
                return (("mla_dense", self.n_layers),)
            segs = [("mla_dense", self.first_dense),
                    ("mla", self.n_layers - self.first_dense)]
            return tuple((k, c) for k, c in segs if c)
        if self.moe is not None:
            return (("moe", self.n_layers),)
        if self.is_encoder_only:
            return (("encoder", self.n_layers),)
        return (("dense", self.n_layers),)

    # -- parameter count (exact, from shapes) ------------------------------
    def _mla_attn_params(self) -> int:
        a, d, h = self.mla, self.d_model, self.n_heads
        q = d * h * a.qk_head_dim
        kv = d * a.latent_dim + a.kv_lora_rank + a.kv_lora_rank * h * (
            a.qk_nope_head_dim + a.v_head_dim)
        return q + kv + h * a.v_head_dim * d

    def _moe_ffn_params(self, held: bool = False) -> int:
        m, d = self.moe, self.d_model
        n = m.held if held else m.num_experts
        router = d * m.num_experts + (m.num_experts
                                      if m.router == "sigmoid" else 0)
        shared = 3 * d * m.n_shared * m.expert_d_ff
        return router + n * 3 * d * m.expert_d_ff + shared

    def param_count(self) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        if self.mla is not None:
            embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
            body = 0
            for kind, count in self.layer_pattern():
                ffn = (self._moe_ffn_params() if kind == "mla"
                       else 3 * d * self.d_ff)
                body += count * (self._mla_attn_params() + ffn + 2 * d)
            return body + embed + d
        q_dim = self.n_heads * hd
        kv_dim = self.n_kv_heads * hd
        attn = d * q_dim + 2 * d * kv_dim + q_dim * d  # wq, wk, wv, wo
        if self.qkv_bias:
            attn += q_dim + 2 * kv_dim
        if self.moe is not None:
            m = self.moe
            ffn = d * m.num_experts + m.num_experts * 3 * d * m.expert_d_ff
        else:
            ffn = 3 * d * self.d_ff  # swiglu: w_gate, w_up, w_down
        norms = 2 * d
        per_attn_layer = attn + ffn + norms

        if self.family == "ssm":
            per_layer = self._mamba_params() + d
            body = self.n_layers * per_layer
        elif self.family == "hybrid":
            pattern = self.layer_pattern()
            n_mamba = sum(c for k, c in pattern if k == "mamba")
            body = n_mamba * (self._mamba_params() + d) + per_attn_layer
        else:
            body = self.n_layers * per_attn_layer

        embed = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        final_norm = d
        return body + embed + head + final_norm

    def _mamba_params(self) -> int:
        assert self.ssm is not None
        s, d = self.ssm, self.d_model
        di, n, h = s.d_inner(d), s.d_state, s.n_heads(d)
        conv_ch = di + 2 * s.n_groups * n
        return (
            d * di  # z (gate) proj
            + d * di  # x proj
            + 2 * d * s.n_groups * n  # B, C proj
            + d * h  # dt proj
            + conv_ch * s.conv_width  # depthwise conv
            + 3 * h  # A_log, D, dt_bias
            + di  # gated rmsnorm
            + di * d  # out proj
        )

    def active_param_count(self) -> int:
        """Params touched per token (MoE top-k instead of all experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        d = self.d_model
        n_moe = sum(c for k, c in self.layer_pattern() if k in ("moe", "mla"))
        full_ffn = n_moe * m.num_experts * 3 * d * m.expert_d_ff
        act_ffn = n_moe * m.top_k * 3 * d * m.expert_d_ff
        return self.param_count() - full_ffn + act_ffn

    def shapes(self) -> Sequence[ShapeSpec]:
        """The shape cells that apply to this architecture (with skips)."""
        out = [SHAPES["train_4k"], SHAPES["prefill_32k"]]
        if not self.is_encoder_only:
            out.append(SHAPES["decode_32k"])
            if self.sub_quadratic:
                out.append(SHAPES["long_500k"])
        return out

    def skipped_shapes(self) -> Sequence[Tuple[str, str]]:
        out = []
        if self.is_encoder_only:
            out.append(("decode_32k", "encoder-only: no decode step"))
            out.append(("long_500k", "encoder-only: no decode step"))
        elif not self.sub_quadratic:
            out.append(
                ("long_500k", "pure full-attention arch: 500k decode skipped")
            )
        return out


# ---------------------------------------------------------------------------
# Reduced ("smoke") configs — same family, tiny sizes, runnable on CPU.
# ---------------------------------------------------------------------------


def reduce_config(cfg: ModelConfig) -> ModelConfig:
    """Shrink a config to a CPU-runnable smoke variant of the same family."""
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(
            cfg.moe,
            num_experts=min(cfg.moe.num_experts, 8),
            top_k=min(cfg.moe.top_k, 2),
            expert_d_ff=64,
            n_held=min(cfg.moe.n_held, 4),
            held_offset=0,
        )
    mla = None
    if cfg.mla is not None:
        mla = dataclasses.replace(
            cfg.mla, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16,
        )
    ssm = None
    if cfg.ssm is not None:
        ssm = SSMConfig(
            d_state=16, head_dim=8, expand=2, conv_width=cfg.ssm.conv_width,
            n_groups=1, chunk_size=16,
        )
    n_heads = min(cfg.n_heads, 4)
    n_kv = min(cfg.n_kv_heads, n_heads)
    if cfg.n_kv_heads == cfg.n_heads:
        n_kv = n_heads  # keep MHA archs MHA
    # keep the local:global / shared-attn structure but fewer layers
    if cfg.family == "hybrid":
        n_layers, period = 7, 2  # 2 groups of (2 mamba + 1 shared) + 1 tail
    elif cfg.local_global_ratio > 0:
        n_layers = (cfg.local_global_ratio + 1) + 1  # one group + tail
    elif cfg.mla is not None and cfg.first_dense:
        n_layers = min(cfg.first_dense, 1) + 2
    else:
        n_layers = 2
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=64,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=16 if cfg.head_dim else 0,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=256,
        window=8 if cfg.window else 0,
        moe=moe,
        ssm=ssm,
        mla=mla,
        first_dense=min(cfg.first_dense, 1),
        shared_attn_period=2 if cfg.family == "hybrid" else 0,
    )


def mfu_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """MODEL_FLOPS for the roofline 'useful flops' ratio.

    train:   6 * N_active * tokens  (+3x fwd attention flops)
    prefill: 2 * N_active * tokens  (+1x fwd attention flops)
    decode:  2 * N_active * batch   (one token per sequence)
    """
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        base = 6.0 * n_active * shape.tokens
        attn_mult = 3.0
    elif shape.kind == "prefill":
        base = 2.0 * n_active * shape.tokens
        attn_mult = 1.0
    else:
        base = 2.0 * n_active * shape.global_batch
        attn_mult = 1.0

    # attention flops (QK^T + PV), causal halving for causal archs
    attn = 0.0
    hd = cfg.resolved_head_dim
    for kind, count in cfg.layer_pattern():
        if kind in ("dense", "moe", "global", "encoder", "shared_attn",
                    "mla", "mla_dense"):
            s_eff = shape.seq_len
        elif kind == "local":
            s_eff = min(cfg.window, shape.seq_len)
        else:  # mamba: linear state update, counted via param flops + state
            continue
        if shape.kind == "decode":
            # one query token attends to the full cache
            flops = 4.0 * shape.global_batch * shape.seq_len * cfg.n_heads * hd
        else:
            causal = 0.5 if cfg.causal else 1.0
            flops = (
                4.0 * shape.global_batch * shape.seq_len * s_eff
                * cfg.n_heads * hd * causal
            )
        attn += count * flops
    return base + attn_mult * attn
