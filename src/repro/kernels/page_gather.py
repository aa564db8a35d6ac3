"""Pallas TPU page gather: linearize one sequence's paged KV cache.

P/D disaggregation moves a request's KV cache from the prefill engine
to the decode engine (paper §6).  The source cache lives scattered
across a shared page pool, so the export path must first materialize
the sequence contiguously — a pure data-movement kernel: grid (H, M)
with the page id for step ``mi`` scalar-prefetched, so each grid step
DMAs one physical (ps, D) page tile straight into its logical position
of the output.  No compute, one pass over the payload; the transfer
then streams the contiguous buffer over ICI.

The inverse (scatter into the destination pool) is a jnp ``.at[].set``
on the allocator-chosen pages — see
:func:`repro.serving.kv_manager.scatter_slot_kv`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(pt_ref, pages_ref, o_ref):
    # the index maps did all the work: copy one page tile through VMEM
    o_ref[...] = pages_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_gather(pages, page_ids, *, interpret: bool = False) -> jax.Array:
    """pages: (NP, H, ps, D); page_ids: (M,) int32 (-1 = unallocated,
    clamped — callers slice the output to the valid token count).
    Returns the sequence's cache linearized to (H, M*ps, D)."""
    n_pages, h, ps, d = pages.shape
    m = page_ids.shape[0]
    pt = jnp.clip(page_ids, 0, n_pages - 1).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, m),
        in_specs=[
            pl.BlockSpec(
                (1, 1, ps, d), lambda hi, mi, pt: (pt[mi], hi, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, ps, d), lambda hi, mi, pt: (hi, mi, 0, 0)
        ),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, m, ps, d), pages.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pt, pages)
    return out.reshape(h, m * ps, d)
