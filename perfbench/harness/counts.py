"""Operations and bytes that the work requires, counted from shapes.

Only required, live work counts, so that a faster program can never read
above 100% of a roofline or a peak:

- the paged decode kernel reads each live token's K and V once per KV
  head, plus the query and writes the output, for every lane that
  decodes; never the pool's ``max_len`` pages, never a page per query
  head (a GQA group's query heads share their KV head's pages);
- a model step needs the matmuls of every token it processes, its
  attention over the live context, and the output head once per
  emitted token: for a prompt, once at its last token.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    hq: int
    hkv: int
    hd: int
    f: int
    v: int
    layers: int

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(d=d, hq=hq, hkv=cfg["num_key_value_heads"],
                   hd=cfg.get("head_dim") or d // hq,
                   f=cfg["intermediate_size"], v=cfg["vocab_size"],
                   layers=cfg["num_hidden_layers"])


def decode_attention_work(m: Dims, kv_lens, kv_bytes: int,
                          q_bytes: int) -> tuple[float, float]:
    """(flops, bytes) of one paged decode attention call (one layer)
    over the lanes that decode, each with ``kv_len`` live tokens
    including the new one.  K and V: ``kv_len * hkv * hd`` elements
    each; q in and out: ``hq * hd`` each; QK^T and PV: ``2 * hq * hd *
    kv_len`` flops each."""
    flops = bytes_ = 0.0
    for n in kv_lens:
        if n <= 0:
            continue
        bytes_ += 2 * n * m.hkv * m.hd * kv_bytes + 2 * m.hq * m.hd * q_bytes
        flops += 4 * m.hq * m.hd * n
    return flops, bytes_


def matmul_flops_per_token(m: Dims) -> float:
    """Every layer's projections and MLP for one token."""
    attn = 2 * m.d * (m.hq * m.hd + 2 * m.hkv * m.hd) + 2 * m.hq * m.hd * m.d
    mlp = 3 * 2 * m.d * m.f
    return m.layers * (attn + mlp)


def head_flops(m: Dims) -> float:
    return 2.0 * m.d * m.v


def token_flops(m: Dims, context: int) -> float:
    """One token at a position that attends ``context`` tokens (itself
    included), without the head."""
    return matmul_flops_per_token(m) + m.layers * 4 * m.hq * m.hd * context


def prefill_flops(m: Dims, start: int, n: int, completes: bool) -> float:
    """``n`` prompt tokens from position ``start``; the head once if the
    prompt ends here."""
    ctx = n * start + n * (n + 1) / 2          # sum of (start + j + 1)
    flops = n * matmul_flops_per_token(m) + m.layers * 4 * m.hq * m.hd * ctx
    return flops + (head_flops(m) if completes else 0.0)


def decode_flops(m: Dims, pos: int, emitted: int) -> float:
    """A lane at ``pos`` that emits ``emitted`` tokens: each one is a
    forward of the previous token at context ``pos + i + 1``, and a
    head."""
    return sum(token_flops(m, pos + i + 1) + head_flops(m)
               for i in range(emitted))
