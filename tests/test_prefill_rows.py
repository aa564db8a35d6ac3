"""Prefill chunks run only the prefilling rows.

A model whose state lives wholly in the pages (dense, GQA, expert and
latent-attention models) runs each prefill chunk as a program over
``prefill_batch`` rows, one per prefilling slot, with the slots' page
table rows picked inside the jitted call.  Its served tokens are the
ones the program over every slot's row serves (``chunk_rows = None``),
in every path a chunk takes: prompts of several chunks beside decoding
lanes, a prefix-cache hit that resumes mid-prompt, a prefill-role (P/D)
engine, and recompute preemption under a dry pool.  A model with
slot-resident SSM/conv state keeps the program over every row.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.request import Request
from repro.models import build_model
from repro.serving.engine import EngineConfig, InferenceEngine
from test_engine import _reference_generate
from test_moonlight import MODEL as MLA_MODEL
from test_moonlight import PARAMS as MLA_PARAMS

N_SLOTS, PREFILL_BATCH, CHUNK = 4, 2, 8


def _smoke(name, **over):
    model = build_model(dataclasses.replace(get_smoke_config(name), **over))
    return model, model.init(jax.random.key(0))


MODELS = {
    "gqa": lambda: _smoke("qwen2.5-14b", n_kv_heads=2),
    "moe": lambda: _smoke("olmoe-1b-7b"),
    "mla": lambda: (MLA_MODEL, MLA_PARAMS),
}
_BUILT: dict = {}


def _model(name):
    """(model, params, fn_cache): one model's engines share its jits."""
    if name not in _BUILT:
        _BUILT[name] = (*MODELS[name](), {})
    return _BUILT[name]


class _Spy:
    """Wraps a jitted step; keeps the shape of each call's tokens."""

    def __init__(self, fn, tokens_at):
        self.fn, self.tokens_at, self.shapes = fn, tokens_at, []

    def __call__(self, *args):
        self.shapes.append(tuple(args[self.tokens_at].shape))
        return self.fn(*args)


def _engine(model, params, fns, rows: bool, **kw):
    cfg = dict(n_slots=N_SLOTS, max_len=48, prefill_batch=PREFILL_BATCH,
               page_size=4, chunk_size=CHUNK, decode_block=4)
    cfg.update(kw)
    eng = InferenceEngine(model, params, EngineConfig(**cfg), fn_cache=fns)
    assert eng.chunk_rows == PREFILL_BATCH
    if not rows:
        eng.chunk_rows = None   # the program over every slot's row
    eng._prefill_chunk = _Spy(eng._prefill_chunk, 4)
    return eng


def _prompts(vocab, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).astype(np.int32) for n in lens]


def _interleaved(model, params, fns, rows):
    """Prompts of up to four chunks join while earlier lanes decode."""
    eng = _engine(model, params, fns, rows)
    vocab = model.cfg.vocab_size
    reqs = [Request.from_prompt(i, p, max_new=6) for i, p in
            enumerate(_prompts(vocab, (5, 27, 19, 12, 30, 9)))]
    for r in reqs[:2]:
        eng.submit(r)
    while not eng.active:
        eng.step()
    for r in reqs[2:]:
        eng.submit(r)
    eng.run_until_done()
    assert eng.decode_block_hist   # lanes decoded between the chunks
    return eng, [r.generated for r in reqs]


def _prefix_hit(model, params, fns, rows):
    """The second and third prompts hit the first's published pages and
    resume prefill mid-prompt, beside a decoding lane."""
    eng = _engine(model, params, fns, rows, prefix_cache=True)
    vocab = model.cfg.vocab_size
    shared = _prompts(vocab, (13,), seed=4)[0]
    tails = _prompts(vocab, (6, 11), seed=5)
    reqs = [Request.from_prompt(0, shared, max_new=4)] + [
        Request.from_prompt(i + 1, np.concatenate([shared, t]), max_new=8)
        for i, t in enumerate(tails)]
    eng.submit(reqs[0])
    eng.run_until_done()
    for r in reqs[1:]:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.prefix_hit_tokens == 12 for r in reqs[1:])
    return eng, [r.generated for r in reqs]


def _pd(model, params, fns, rows):
    """A prefill-role engine parks each prompt; its pages move to a
    decode engine, which finishes the request."""
    eng = _engine(model, params, fns, rows)
    eng.park_on_prefill = True
    vocab = model.cfg.vocab_size
    reqs = [Request.from_prompt(i, p, max_new=6) for i, p in
            enumerate(_prompts(vocab, (17, 6, 22)))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert len(eng.parked) == len(reqs) and not eng.active
    dec = InferenceEngine(model, params, EngineConfig(
        n_slots=N_SLOTS, max_len=48, page_size=8, chunk_size=CHUNK),
        fn_cache=fns)
    for r in reqs:
        payload = eng.export_kv(r.rid)
        eng.evict(r.slot)
        assert dec.import_kv(payload, r)
    dec.run_until_done()
    return eng, [r.generated for r in reqs]


def _preempt(model, params, fns, rows):
    """Four pages hold one request: the first to decode runs the pool
    dry, the younger request is preempted, re-prefills its prompt and
    output in chunks, and both finish."""
    eng = _engine(model, params, fns, rows, max_len=16, n_pages=4)
    preempted = []
    keep = eng._preempt_youngest

    def spy(exclude):
        preempted.append(exclude)
        return keep(exclude)

    eng._preempt_youngest = spy
    vocab = model.cfg.vocab_size
    reqs = [Request.from_prompt(i, p, max_new=8) for i, p in
            enumerate(_prompts(vocab, (6, 6)))]
    for r in reqs:
        eng.submit(r)
    assert len(eng.run_until_done(max_steps=500)) == 2
    assert preempted
    assert eng.kv.n_free_pages == eng.kv.n_pages
    return eng, [r.generated for r in reqs]


CASES = {"interleaved": _interleaved, "prefix_hit": _prefix_hit,
         "pd": _pd, "preempt": _preempt}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("name", list(MODELS))
def test_prefill_rows_serve_the_all_rows_tokens(name, case):
    model, params, fns = _model(name)
    assert model.supports_prefix_cache
    eng, tokens = CASES[case](model, params, fns, rows=True)
    ref_eng, ref = CASES[case](model, params, fns, rows=False)
    assert tokens == ref
    assert all(tokens)
    assert set(eng._prefill_chunk.shapes) == {(PREFILL_BATCH, CHUNK)}
    assert set(ref_eng._prefill_chunk.shapes) == {(N_SLOTS, CHUNK)}


@pytest.mark.parametrize("name", ["mamba2-2.7b", "zamba2-7b"])
def test_slot_resident_state_keeps_every_row(name):
    """SSM/conv state has a slot axis: a mamba or hybrid model's chunks
    run over every slot's row, and serve the reference's tokens."""
    model, params = _smoke(name)
    eng = InferenceEngine(model, params, EngineConfig(
        n_slots=N_SLOTS, max_len=48, prefill_batch=PREFILL_BATCH,
        page_size=4, chunk_size=CHUNK, decode_block=4))
    assert eng.paged and eng.chunk_rows is None
    eng._prefill_chunk = _Spy(eng._prefill_chunk, 4)
    reqs = [Request.from_prompt(i, p, max_new=4) for i, p in
            enumerate(_prompts(model.cfg.vocab_size, (5, 19)))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    for r in reqs:
        assert r.generated == _reference_generate(model, params,
                                                  r.prompt, 4)
    assert set(eng._prefill_chunk.shapes) == {(N_SLOTS, CHUNK)}
