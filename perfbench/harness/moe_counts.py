"""Operations and bytes of the DeepSeek-V3 block, counted from shapes.

The rules of ``counts.py`` hold: only required, live work counts, so
that a faster program never reads above 100% of a roofline or a peak.

- Latent attention is counted in its absorbed form, the least work at
  decode: per lane and layer, each live token's latent and rotary key
  once (kv_lora + rope values, never a per-head expansion and never the
  pool row's zero padding), the query in and the output; scores over
  kv_lora + rope and the weighted sum over kv_lora, per query head.
- The experts count the assignments the program computed on the experts
  held here (the ``expert_tokens`` of its step spans), each a SwiGLU of
  ``moe_intermediate_size``; each expert hit reads its three matrices
  once per layer and iteration, in bfloat16, the precision its products
  run at.
- A token's other work: the attention projections, the router, the
  shared experts in each expert layer, the dense FFN in each leading
  dense layer, and the output head once per emitted token.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MoeDims:
    d: int
    h: int          # query heads
    nope: int
    rope: int
    vd: int         # value head size
    lora: int       # latent width
    f: int          # dense FFN width
    fe: int         # expert width
    shared: int     # shared experts
    experts: int    # router width
    held: int       # experts held here
    v: int
    layers: int
    dense: int      # leading dense layers

    @classmethod
    def of(cls, cfg: dict) -> "MoeDims":
        held = cfg.get("held_experts", {})
        return cls(
            d=cfg["hidden_size"], h=cfg["num_attention_heads"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            vd=cfg["v_head_dim"], lora=cfg["kv_lora_rank"],
            f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
            shared=cfg["n_shared_experts"],
            experts=held.get("router_width", cfg["n_routed_experts"]),
            held=cfg["n_routed_experts"], v=cfg["vocab_size"],
            layers=cfg["num_hidden_layers"],
            dense=cfg["first_k_dense_replace"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense


def mla_decode_work(m: MoeDims, kv_lens, kv_bytes: int,
                    q_bytes: int) -> tuple[float, float]:
    """(flops, bytes) of one latent decode attention call (one layer)
    over the lanes that decode, each with ``kv_len`` live tokens
    including the new one."""
    row = m.lora + m.rope
    flops = bytes_ = 0.0
    for n in kv_lens:
        if n <= 0:
            continue
        bytes_ += n * row * kv_bytes + m.h * (row + m.lora) * q_bytes
        flops += 2 * m.h * (row + m.lora) * n
    return flops, bytes_


def attention_flops(m: MoeDims, context: int) -> float:
    """One token's latent attention in one layer, at ``context`` live
    tokens (itself included)."""
    row = m.lora + m.rope
    proj = (2 * m.d * m.h * (m.nope + m.rope) + 2 * m.d * row
            + 2 * m.h * m.nope * m.lora + 2 * m.h * m.lora * m.vd
            + 2 * m.h * m.vd * m.d)
    return proj + 2 * m.h * (row + m.lora) * context


def ffn_flops(m: MoeDims) -> float:
    """One token's FFN work outside the routed experts, over all layers:
    the dense layers' SwiGLU, and the router and shared experts of each
    expert layer."""
    dense = 6 * m.d * m.f
    moe = 2 * m.d * m.experts + 6 * m.d * m.shared * m.fe
    return m.dense * dense + m.moe_layers * moe


def head_flops(m: MoeDims) -> float:
    return 2.0 * m.d * m.v


def token_flops(m: MoeDims, context: int) -> float:
    """One token at a position that attends ``context`` tokens, without
    the routed experts and the head."""
    return m.layers * attention_flops(m, context) + ffn_flops(m)


def prefill_flops(m: MoeDims, start: int, n: int) -> float:
    """``n`` prompt tokens from position ``start`` (no head, no routed
    experts)."""
    return sum(token_flops(m, start + j + 1) for j in range(n))


def decode_flops(m: MoeDims, pos: int, emitted: int) -> float:
    """A lane at ``pos`` that emits ``emitted`` tokens, each a forward of
    the previous token and a head (no routed experts)."""
    return sum(token_flops(m, pos + i + 1) + head_flops(m)
               for i in range(emitted))


def expert_work(m: MoeDims, expert_tokens: int, experts_hit: int,
                weight_bytes: int = 2) -> tuple[float, float]:
    """(flops, bytes) of the routed experts' matmuls for ``expert_tokens``
    assignments and ``experts_hit`` (expert, layer, iteration) triples
    that had at least one."""
    per = 3 * m.d * m.fe
    return 2.0 * per * expert_tokens, float(per * weight_bytes * experts_hit)
