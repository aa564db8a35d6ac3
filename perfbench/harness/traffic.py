"""The one traffic generator: open-loop arrivals over HFX Table 1 tasks.

A mix is a JSON file of parameters (``traffic/<mix>.json``):

- ``tasks``: Table 1 task names, sent in equal shares;
- ``rate_rps``: the fixed offered rate (requests/s), found once by a
  sweep on the chip;
- ``lead_s``: seconds of the schedule before the measured window opens;
- ``base_seed``: draws the mix's fixed set of requests and gaps;
- ``admission``, ``drain_cap_s``: how the run serves it
  (see ``harness/serve.py``).

Arrivals are Poisson conditioned on their count: ``rate * seconds``
requests in the window (and ``rate * lead_s`` in the lead), with
exponential gaps scaled to fill it.  The requests' sizes, tasks and
arrival times are drawn once from ``base_seed``, in an order drawn from
it too; the run's seed draws only the prompt tokens (and the weights).
So every seed offers the same work at the same moments.  Near the knee
the order alone moves a p90 over a hundred requests by more than any
bound could allow: on the chip, six orders of one set of requests gave
``ttft_p90_s`` from 0.58 to 1.50 s, where two runs of one order
differed by 5-16%.

Lengths and SLOs are Table 1 of the paper (mean +- std over 300
requests per task, SLOs in seconds), as ``repro.core.request.TASKS``
holds them; lengths are drawn as there, ``max(1, int(normal))``, then
clipped so that prompt plus output fits the engine's slot.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from harness.cell import seed_stream

# name: (ttft_slo, tpot_slo, in_mean, in_std, out_mean, out_std)
TABLE1 = {
    "medical_qa": (0.7, 0.5, 32.57, 10.32, 38.92, 16.83),
    "tldr_content_gen": (1.0, 0.7, 44.38, 6.58, 96.04, 35.03),
    "tldr_headline_gen": (2.0, 0.9, 121.82, 35.04, 13.59, 6.55),
    "wikisql": (20.0, 1.0, 643.22, 337.01, 27.82, 4.84),
    "gsm8k": (0.7, 0.2, 51.44, 15.78, 90.13, 26.73),
    "sharegpt": (2.0, 0.5, 259.19, 324.88, 207.79, 234.99),
}


@dataclasses.dataclass
class Req:
    due: float          # seconds after the schedule's origin
    task: str
    prompt: np.ndarray  # int32 token ids
    l_out: int
    ttft_slo: float
    tpot_slo: float
    in_window: bool     # due inside the measured window


def sizes_and_gaps(mix: dict, rng, n: int, max_len: int):
    """``n`` Table 1 requests in equal task shares and ``n + 1`` gaps."""
    tasks = mix["tasks"]
    sizes = []
    for i in range(n):
        name = tasks[i % len(tasks)]
        _, _, im, isd, om, osd = TABLE1[name]
        l_in = max(1, int(rng.normal(im, isd)))
        l_out = min(max(1, int(rng.normal(om, osd))), max_len // 2)
        sizes.append((name, min(l_in, max_len - 1 - l_out), l_out))
    return sizes, rng.exponential(1.0, size=n + 1)


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             max_len: int) -> list[Req]:
    """The run's requests in due order: the lead's, then the window's."""
    base = np.random.default_rng(mix["base_seed"])
    tokens = seed_stream(seed, "traffic.tokens")
    reqs = []
    for start, span, in_window in ((0.0, mix["lead_s"], False),
                                   (mix["lead_s"], seconds, True)):
        n = int(round(mix["rate_rps"] * span))
        sizes, gaps = sizes_and_gaps(mix, base, n, max_len)
        due = start + span * np.cumsum(gaps)[:n] / gaps.sum()
        for t, i in zip(due, base.permutation(n)):
            name, l_in, l_out = sizes[i]
            ttft, tpot = TABLE1[name][:2]
            reqs.append(Req(
                due=float(t), task=name,
                prompt=tokens.integers(1, vocab, size=l_in, dtype=np.int32),
                l_out=l_out, ttft_slo=ttft, tpot_slo=tpot,
                in_window=in_window,
            ))
    return reqs
