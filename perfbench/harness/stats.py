"""Client-side latency arithmetic over the requests sent in the window.

The percentile is numpy's linear one and attainment is "met both SLOs
over all requests sent", as ``repro.serving.metrics.compute_metrics``
computes them; here both are taken from the client's own stamps.  A
request that is rejected, fails or does not finish by the end of
observation misses every limit; for the TTFT percentile its wait is
counted up to that end, the least it can have been.
"""

from __future__ import annotations

import numpy as np


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q))


def ttfts(clients, end: float) -> list[float]:
    return [(c.first_t if c.first_t is not None else end) - c.due_t
            for c in clients]


def tpots(clients) -> list[float]:
    """(last - first) / (n - 1) over requests with two tokens or more."""
    return [(c.last_t - c.first_t) / (c.n_tok - 1)
            for c in clients if c.n_tok >= 2]


def attained(c) -> bool:
    if not c.finished or c.first_t is None:
        return False
    tpot = (c.last_t - c.first_t) / (c.n_tok - 1) if c.n_tok >= 2 else 0.0
    return (c.first_t - c.due_t <= c.req.ttft_slo + 1e-9
            and tpot <= c.req.tpot_slo + 1e-9)


def attainment(clients) -> float:
    return sum(attained(c) for c in clients) / max(len(clients), 1)
