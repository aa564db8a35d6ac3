"""The comparison that decides ``correct``, on what the window served.

Once the window has closed and the program is freed, a sample of the
requests it finished (drawn from the seed, always with the one that
served the most tokens) is run once through the plain float32 reference
over each prompt followed by its served tokens.  At every served
position the reference's best logit minus its logit for the served
token is that token's gap; 0 where they agree.  The number compared is
the widest gap, ``max_logit_gap``, against the configuration's limit.

Only greedy tokens can be judged this way, and the engine serves
greedily.  The control (``control_gaps``) reads, at the same positions,
the reference's gap for the token that the reference computed in fp8
puts first; it is for the control runs and the tests, not for the
benchmark's own runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.cell import Cell, seed_stream


def sample(clients, seed: int, n_tokens: int) -> list:
    """Finished requests with their full output: the longest, then
    others in an order drawn from the seed, until ``n_tokens`` served
    tokens are in."""
    done = [c.request for c in clients
            if c.finished and c.request is not None
            and len(c.request.generated) == c.request.l_out]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.generated), r.rid))
    rest = done[1:]
    order = seed_stream(seed, "check.sample").permutation(len(rest))
    picked, n = [done[0]], len(done[0].generated)
    for i in order:
        if n >= n_tokens:
            break
        picked.append(rest[i])
        n += len(rest[i].generated)
    return picked


def _scorer(ref, cfg: dict, control: bool):
    """Jitted ``(weights, tokens (S,), targets (S,)) ->
    per-row (gap of the target, gap of the fp8 argmax)`` where row ``j``
    holds the logits after ``tokens[: j + 1]``."""
    def score(w, tokens, targets):
        logits = ref.logits(w, ref.hidden(w, cfg, tokens))
        best = logits.max(-1)
        gap = best - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        if not control:
            return gap, jnp.zeros_like(gap)
        low = ref.logits(w, ref.hidden(w, cfg, tokens, quant="fp8"),
                         quant="fp8")
        top = jnp.argmax(low, -1)
        return gap, best - jnp.take_along_axis(logits, top[:, None], -1)[:, 0]

    return jax.jit(score)


def gaps(cell: Cell, weights, requests, *, control: bool = False) -> dict:
    """Widest reference gap of the served tokens (and, with ``control``,
    of the fp8 reference's tokens) over ``requests``."""
    ref, cfg = cell.reference(), cell.config
    length = cell.config["engine"]["max_len"]
    score = _scorer(ref, cfg, control)
    served, ctrl, n_pos = [], [], 0
    for r in requests:
        gen = np.asarray(r.generated, np.int32)
        seq = np.concatenate([np.asarray(r.prompt[: r.l_in], np.int32), gen])
        tokens = np.zeros(length, np.int32)
        targets = np.zeros(length, np.int32)
        tokens[: len(seq) - 1] = seq[:-1]
        targets[: len(seq) - 1] = seq[1:]
        g, c = score(weights, jnp.asarray(tokens), jnp.asarray(targets))
        rows = slice(r.l_in - 1, len(seq) - 1)   # the served positions
        served.append(np.asarray(g)[rows])
        ctrl.append(np.asarray(c)[rows])
        n_pos += len(gen)
    out = {"requests": len(requests), "positions": n_pos,
           "max_logit_gap": float(np.max(np.concatenate(served)))}
    if control:
        out["control_max_logit_gap"] = float(np.max(np.concatenate(ctrl)))
    return out
