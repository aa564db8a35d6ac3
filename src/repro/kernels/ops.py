"""The one kernel decision: compiled Pallas kernels on TPU, jnp elsewhere.

Two kernels sit on the served path, and both run compiled exactly when
JAX's default backend is a TPU:

- ``paged_decode_attention`` — the paged plane's one-token decode
  (``Model._attn_block``: every K=1 decode and every ``decode_block``
  iteration);
- ``page_gather`` — linearizing a request's pages for the P/D hand-off
  (``kv_manager.gather_slot_kv``).

The slot plane's contiguous ``decode_attention`` and ``flash_attention``
kernels need shapes padded prefill breaks (``S % block == 0``, no right
padding), so the slot plane always runs jnp and those kernels, ``ssd``
and ``rmsnorm`` stay off the served path: only the kernel tests (in
interpret mode) and ``benchmarks/bench_kernels.py`` call them.  No
kernel interprets unless its caller passes ``interpret=True``; called
on another backend without it, Pallas raises.
"""

from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.page_gather import page_gather as _pl_page_gather


def kernels_enabled() -> bool:
    """True when the served path runs the compiled Pallas kernels."""
    return jax.default_backend() == "tpu"


def page_gather(pages, page_ids):
    if kernels_enabled():
        return _pl_page_gather(pages, page_ids)
    return ref.page_gather_ref(pages, page_ids)


__all__ = ["kernels_enabled", "page_gather", "paged_decode_attention"]
