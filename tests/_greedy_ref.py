"""Cache-free greedy reference for the engine's token-identity tests.

Each next token is the argmax of a full ``Model.forward`` over the
prompt and the tokens generated so far: no KV cache, no pages, no
decode program, so the reference shares nothing with the engine path
under test but the weights and the layers.
"""

import jax
import jax.numpy as jnp
import numpy as np


def greedy_reference(model, params, prompts, max_new, eos=None):
    """``max_new`` greedy tokens after each of ``prompts``, cut after
    the first ``eos``.  The prompts run as one right-padded batch: a
    causal model's logits at a position never see the padding after
    it."""
    n = [len(p) for p in prompts]
    toks = np.zeros((len(prompts), max(n) + max_new), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : n[i]] = p
    forward = jax.jit(model.forward)
    rows = np.arange(len(prompts))
    out = [[] for _ in prompts]
    for step in range(max_new):
        logits = forward(params, {"tokens": jnp.asarray(toks)})
        at = np.asarray(n) + step - 1    # each row's last token
        nxt = np.asarray(jnp.argmax(logits[rows, at], axis=-1))
        toks[rows, at + 1] = nxt
        for i, t in enumerate(nxt):
            out[i].append(int(t))
    if eos is not None:
        out = [o[: o.index(eos) + 1] if eos in o else o for o in out]
    return out
