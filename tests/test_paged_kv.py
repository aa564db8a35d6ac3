"""Paged KV plane: allocator invariants, page-table gather, and the
paged decode-attention kernel vs its oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (
    paged_decode_attention,
    paged_mla_decode_attention,
    pages_per_block,
)
from repro.models import attention as attn
from repro.serving.kv_manager import PageAllocator, PagedKVManager


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def test_alloc_no_double_allocation():
    a = PageAllocator(n_pages=16, page_size=8)
    seen = set()
    for owner in range(4):
        pages = a.alloc(4, owner=owner)
        assert pages is not None and len(pages) == 4
        assert not (set(pages) & seen)
        seen |= set(pages)
    assert a.n_free == 0
    assert a.alloc(1) is None          # exhausted, existing intact
    assert seen == set(range(16))


def test_alloc_atomic_on_failure():
    a = PageAllocator(n_pages=4, page_size=8)
    got = a.alloc(3, owner="x")
    assert a.alloc(2) is None          # only 1 left: nothing allocated
    assert a.n_free == 1
    a.free(got)
    assert a.n_free == 4


def test_full_reclamation_cycles():
    a = PageAllocator(n_pages=8, page_size=4)
    for _ in range(10):
        p1 = a.alloc(5, owner=1)
        p2 = a.alloc(3, owner=2)
        assert p1 is not None and p2 is not None
        a.free(p1)
        a.free(p2)
    assert a.n_free == 8
    assert a.n_used == 0


def test_double_free_asserts():
    a = PageAllocator(n_pages=2, page_size=4)
    p = a.alloc(1)
    a.free(p)
    with pytest.raises(AssertionError):
        a.free(p)


def test_kv_manager_ensure_grow_and_release():
    kv = PagedKVManager(n_slots=2, max_len=32, page_size=8)
    assert kv.max_pages == 4 and kv.n_pages == 8
    assert kv.ensure(0, 1)             # 1 token -> 1 page
    assert len(kv.pages_of(0)) == 1
    assert kv.ensure(0, 8)             # exact page boundary: still 1
    assert len(kv.pages_of(0)) == 1
    assert kv.ensure(0, 9)             # crosses into page 2
    assert len(kv.pages_of(0)) == 2
    assert kv.ensure(0, 32) and len(kv.pages_of(0)) == 4
    assert not kv.ensure(0, 33)        # beyond max_len
    assert kv.ensure(1, 32)
    assert kv.n_free_pages == 0
    kv.release(0)
    assert kv.n_free_pages == 4
    assert (kv.table[0] == -1).all()
    kv.release(1)
    assert kv.n_free_pages == kv.n_pages


def test_kv_manager_tables_disjoint():
    kv = PagedKVManager(n_slots=4, max_len=16, page_size=4)
    for s in range(4):
        assert kv.ensure(s, 16)
    used = [p for s in range(4) for p in kv.pages_of(s)]
    assert len(used) == len(set(used)) == 16


# ---------------------------------------------------------------------------
# Gather / kernel vs contiguous reference
# ---------------------------------------------------------------------------


def _paged_fixture(seed, b, h, s, d, ps, kv_len=None):
    """Build a contiguous cache and its paged twin via a PagedKVManager
    (``kv_len`` drawn from ``seed`` unless given)."""
    rng = np.random.default_rng(seed)
    kv = PagedKVManager(n_slots=b, max_len=s, page_size=ps)
    if kv_len is None:
        kv_len = rng.integers(1, s + 1, size=b)
    kv_len = np.asarray(kv_len, np.int32)
    k_cont = rng.standard_normal((b, h, s, d)).astype(np.float32)
    v_cont = rng.standard_normal((b, h, s, d)).astype(np.float32)
    k_pages = np.zeros((kv.n_pages, h, ps, d), np.float32)
    v_pages = np.zeros((kv.n_pages, h, ps, d), np.float32)
    for i in range(b):
        assert kv.ensure(i, int(kv_len[i]))
        for t in range(int(kv_len[i])):
            pg = kv.table[i, t // ps]
            k_pages[pg, :, t % ps] = k_cont[i, :, t]
            v_pages[pg, :, t % ps] = v_cont[i, :, t]
        k_cont[i, :, kv_len[i]:] = 0  # masked region: match zeros
        v_cont[i, :, kv_len[i]:] = 0
    return kv, map(jnp.asarray, (k_cont, v_cont, k_pages, v_pages, kv_len))


def _poison_dead(kv, k_pages, v_pages, kv_len):
    """NaN in every slot of the pool but the live tokens, plus one NaN
    page that every table entry past a lane's live pages names: the dead
    tail of each last page, whole blocks past it and idle lanes' tables
    all point at garbage that must not reach the output."""
    ps = kv.page_size
    live = np.zeros((kv.n_pages + 1, 1, ps, 1), bool)
    table = kv.table.copy()
    for i, n in enumerate(np.asarray(kv_len)):
        for t in range(int(n)):
            live[table[i, t // ps], 0, t % ps, 0] = True
        table[i, -(-int(n) // ps):] = kv.n_pages
    live = jnp.asarray(live)

    def poison(pages):
        pages = jnp.concatenate([pages, jnp.zeros_like(pages[:1])])
        return jnp.where(live, pages, jnp.nan)

    return poison(k_pages), poison(v_pages), jnp.asarray(table)


def _check_lanes(got, want, kv_len):
    """Live lanes match the reference; idle lanes (kv_len 0) are zero."""
    on = np.asarray(kv_len) > 0
    got = np.asarray(got)
    np.testing.assert_allclose(got[on], np.asarray(want)[on],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(got[~on], 0.0)


def test_page_table_gather_matches_contiguous():
    b, h, s, d, ps = 3, 2, 32, 16, 8
    kv, (k_cont, _, k_pages, _, kv_len) = _paged_fixture(0, b, h, s, d, ps)
    got = ref.paged_gather(k_pages, jnp.asarray(kv.table))
    for i in range(b):
        n = int(kv_len[i])
        np.testing.assert_array_equal(
            np.asarray(got[i, :, :n]), np.asarray(k_cont[i, :, :n])
        )


# The served paged plane, as both benchmark configurations run it: 8
# lanes of 1024 tokens in 16-token pages, head size 128.
SERVED_B, SERVED_S, SERVED_D, SERVED_PS = 8, 1024, 128, 16


def _served_lens(hkv):
    """A lane per boundary: idle (its table all -1), one token, a page,
    a page and one, a block, a block and one, the whole table, and two
    blocks and a part."""
    bt = SERVED_PS * pages_per_block(hkv, SERVED_PS, SERVED_D, jnp.float32,
                                     SERVED_S // SERVED_PS)
    return [0, 1, SERVED_PS, SERVED_PS + 1, bt, bt + 1, SERVED_S,
            min(2 * bt + 5, SERVED_S - 1)]


@pytest.mark.parametrize("shape", [
    *(pytest.param((3, 2, 2, 32, 16, ps), id=str(ps)) for ps in (4, 8, 16)),
    *(pytest.param((SERVED_B, hq, hkv, SERVED_S, SERVED_D, SERVED_PS),
                   id=f"served-{hq}-{hkv}") for hq, hkv in ((32, 32), (40, 8))),
])
def test_paged_decode_attention_matches_contiguous_ref(shape):
    """Small pools with drawn lengths, and the served shapes (MHA 32/32
    and GQA 40/8) with a lane at each block boundary over a pool
    poisoned outside the live tokens."""
    b, hq, hkv, s, d, ps = shape
    served = b == SERVED_B
    kv, (k_cont, v_cont, k_pages, v_pages, kv_len) = _paged_fixture(
        ps, b, hkv, s, d, ps, _served_lens(hkv) if served else None
    )
    q = jax.random.normal(jax.random.key(7), (b, hq, d))
    g = hq // hkv
    want = ref.decode_attention_ref(
        q, jnp.repeat(k_cont, g, axis=1), jnp.repeat(v_cont, g, axis=1),
        kv_len,
    )
    pt = jnp.asarray(kv.table)
    got_ref = ref.paged_decode_attention_ref(q, k_pages, v_pages, pt, kv_len)
    on = np.asarray(kv_len) > 0  # the oracle's idle lanes average page 0
    np.testing.assert_allclose(np.asarray(got_ref)[on], np.asarray(want)[on],
                               rtol=2e-5, atol=2e-5)
    if served:
        k_pages, v_pages, pt = _poison_dead(kv, k_pages, v_pages, kv_len)
        pt = pt.at[0].set(-1)  # the idle lane's table all -1
    got_pl = paged_decode_attention(q, k_pages, v_pages, pt, kv_len,
                                    interpret=True)
    _check_lanes(got_pl, want, kv_len)


def test_paged_decode_attention_gqa_heads():
    """Hq > Hkv: query head hi reads KV head hi // g (the kernel serves a
    KV head's g query heads together) — must match the broadcast
    contiguous reference."""
    b, hq, hkv, s, d, ps = 2, 6, 2, 16, 16, 4
    kv, (k_cont, v_cont, k_pages, v_pages, kv_len) = _paged_fixture(
        11, b, hkv, s, d, ps
    )
    q = jax.random.normal(jax.random.key(5), (b, hq, d))
    g = hq // hkv
    want = ref.decode_attention_ref(
        q, jnp.repeat(k_cont, g, axis=1), jnp.repeat(v_cont, g, axis=1),
        kv_len,
    )
    pt = jnp.asarray(kv.table)
    got_ref = ref.paged_decode_attention_ref(q, k_pages, v_pages, pt, kv_len)
    got_pl = paged_decode_attention(q, k_pages, v_pages, pt, kv_len,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_ignores_stale_pages():
    """Reclaimed-page garbage must not leak into outputs: NaN at every
    allocated-but-unused offset, in all free pages, and behind every
    table entry past a lane's live pages (an idle lane's too)."""
    b, h, s, d, ps = 3, 2, 16, 8, 4
    kv, (k_cont, v_cont, k_pages, v_pages, kv_len) = _paged_fixture(
        3, b, h, s, d, ps, kv_len=[6, 13, 0]
    )
    k_pois, v_pois, pt = _poison_dead(kv, k_pages, v_pages, kv_len)
    q = jax.random.normal(jax.random.key(9), (b, h, d))
    want = ref.decode_attention_ref(q, k_cont, v_cont, kv_len)
    got = paged_decode_attention(q, k_pois, v_pois, pt, kv_len,
                                 interpret=True)
    _check_lanes(got, want, kv_len)


# Latent attention's pool as Moonlight serves it: a 512-wide latent and
# a 64-wide rotary key per token in a 640-wide row, 16 query heads,
# 2048-token lanes.
MLA_H, MLA_C, MLA_R, MLA_S = 16, 512, 64, 2048


@pytest.mark.parametrize("shape", [
    pytest.param((3, 4, 32, 8, 48, 40, 4), id="small"),
    pytest.param((SERVED_B, MLA_H, MLA_C, MLA_R, 640, MLA_S, SERVED_PS),
                 id="served"),
])
def test_paged_mla_decode_matches_expanded(shape):
    """The MLA kernel (interpret mode) and the served jnp path against
    attention written out over contiguous latents; the served shape has
    a lane at each block boundary over a pool poisoned outside the live
    tokens."""
    b, h, dc, dr, width, s, ps = shape
    served = b == SERVED_B
    lens = None
    if served:
        bt = ps * pages_per_block(1, ps, width, jnp.float32, s // ps)
        lens = [0, 1, ps, ps + 1, bt, bt + 1, s, min(2 * bt + 5, s - 1)]
    kv, (lat, _, pages, _, kv_len) = _paged_fixture(
        5, b, 1, s, width, ps, lens)
    pages = pages.at[..., dc + dr:].set(0.0)   # the row's zero padding
    k0, k1 = jax.random.split(jax.random.key(8))
    q_c = jax.random.normal(k0, (b, h, dc)) * 0.05
    q_r = jax.random.normal(k1, (b, h, dr)) * 0.05
    scale = 1.0 / (dc ** 0.5)
    c, r = lat[:, 0, :, :dc], lat[:, 0, :, dc:dc + dr]
    sc = (jnp.einsum("bhc,bkc->bhk", q_c, c)
          + jnp.einsum("bhr,bkr->bhk", q_r, r)) * scale
    sc = jnp.where(jnp.arange(s)[None, None] < kv_len[:, None, None], sc,
                   -jnp.inf)
    want = jnp.einsum("bhk,bkc->bhc", jax.nn.softmax(sc, -1), c)
    pt = jnp.asarray(kv.table)
    got_jnp = attn.paged_mla_decode_attention(q_c, q_r, pages, pt, kv_len,
                                              scale=scale)
    on = np.asarray(kv_len) > 0
    np.testing.assert_allclose(np.asarray(got_jnp)[on], np.asarray(want)[on],
                               rtol=2e-5, atol=2e-5)
    pages, _, pt = _poison_dead(kv, pages, pages, kv_len)
    if served:
        pt = pt.at[0].set(-1)  # the idle lane's table all -1
    got = paged_mla_decode_attention(q_c, q_r, pages, pt, kv_len,
                                     scale=scale, interpret=True)
    _check_lanes(got, want, kv_len)


# ---------------------------------------------------------------------------
# Property test (hypothesis optional)
# ---------------------------------------------------------------------------


def test_allocator_migration_traffic_property():
    """P/D migration traffic: interleaved grow (prefill), export
    (release on src + ensure on dst), import-fail rollback, and evict
    across TWO pools.  Invariants on both: no double-free (PageAllocator
    asserts), no leaked pages, free + used == total (conservation),
    tables disjoint."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    N_SLOTS, MAX_LEN, PS = 3, 24, 4

    @hyp.settings(max_examples=50, deadline=None)
    @hyp.given(ops=st.lists(
        st.tuples(st.sampled_from(["grow", "migrate", "evict"]),
                  st.integers(0, 1),           # which pool is src
                  st.integers(0, N_SLOTS - 1), # slot
                  st.integers(1, 9)),          # tokens to grow
        max_size=80))
    def inner(ops):
        pools = [PagedKVManager(N_SLOTS, MAX_LEN, PS),
                 PagedKVManager(N_SLOTS, MAX_LEN, PS)]
        lens = [[0] * N_SLOTS, [0] * N_SLOTS]
        for kind, pi, slot, n in ops:
            src, dst = pools[pi], pools[1 - pi]
            if kind == "grow":
                want = min(lens[pi][slot] + n, MAX_LEN)
                if src.ensure(slot, want):
                    lens[pi][slot] = want
            elif kind == "migrate" and lens[pi][slot] > 0:
                # export: install the same token count on some dst
                # slot, then release the source (transfer landed)
                t = lens[pi][slot]
                free = [s for s in range(N_SLOTS)
                        if lens[1 - pi][s] == 0]
                if free and dst.ensure(free[0], t):
                    lens[1 - pi][free[0]] = t
                    src.release(slot)
                    lens[pi][slot] = 0
                # else: import failed — ensure() rolled back, src keeps
                # its pages (nothing moved, nothing leaked)
            elif kind == "evict":
                src.release(slot)
                lens[pi][slot] = 0
            for j, kv in enumerate(pools):
                used = [p for s in range(N_SLOTS) for p in kv.pages_of(s)]
                assert len(used) == len(set(used))          # disjoint
                assert kv.n_free_pages + len(used) == kv.n_pages
                for s in range(N_SLOTS):                    # no leaks
                    assert len(kv.pages_of(s)) == -(-lens[j][s] // PS)
        for kv in pools:
            for s in range(N_SLOTS):
                kv.release(s)
            assert kv.n_free_pages == kv.n_pages   # full reclamation

    inner()


def test_allocator_random_workload_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=50, deadline=None)
    @hyp.given(ops=st.lists(st.tuples(st.integers(0, 3),
                                      st.integers(0, 6)),
                            max_size=60))
    def inner(ops):
        kv = PagedKVManager(n_slots=4, max_len=24, page_size=4)
        lens = [0, 0, 0, 0]
        for slot, n in ops:
            if n == 0:
                kv.release(slot)
                lens[slot] = 0
            else:
                want = min(lens[slot] + n, 24)
                if kv.ensure(slot, want):
                    lens[slot] = want
            # invariants: tables disjoint, free + used == total
            used = [p for s in range(4) for p in kv.pages_of(s)]
            assert len(used) == len(set(used))
            assert kv.n_free_pages + len(used) == kv.n_pages
            for s in range(4):
                assert len(kv.pages_of(s)) == -(-lens[s] // 4) or lens[s] == 0
        for s in range(4):
            kv.release(s)
        assert kv.n_free_pages == kv.n_pages

    inner()
