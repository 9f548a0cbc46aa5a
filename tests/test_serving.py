"""Serving subsystem: paged KV allocator, paged-attention kernels, the
continuous-batching engine vs the static reference path, and the paged
schedule's ride through the tuner cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _oracle import reference_tokens
from repro.core import flags
from repro.core.config import GemminiConfig
from repro.kernels import attention as ak
from repro.kernels import ref
from repro.models import attention as mattn
from repro.models import transformer as tf
from repro.serving import ContinuousScheduler, PagedKVAllocator, Request
from repro.serving.engine import ServingEngine
from repro.serving.paged_cache import pages_for


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------
def test_alloc_free_reuse():
    al = PagedKVAllocator(n_pages=8, page_size=4, max_pages_per_seq=4)
    a = al.alloc_slot(0, 9)                    # 3 pages
    assert a is not None and len(a) == 3
    assert al.used_pages == 3 and al.free_pages == 5
    b = al.alloc_slot(1, 4)                    # 1 page
    assert len(b) == 1 and set(a).isdisjoint(b)
    assert al.free_slot(0) == 3
    assert al.free_pages == 7
    # LIFO free list: the just-freed pages are handed out next (reuse)
    c = al.alloc_slot(2, 12)
    assert set(c) == set(a)


def test_alloc_capacity_exhaustion():
    al = PagedKVAllocator(n_pages=4, page_size=4, max_pages_per_seq=4)
    assert al.alloc_slot(0, 12) is not None    # 3 of 4 pages
    assert not al.can_admit(8)
    assert al.alloc_slot(1, 8) is None         # needs 2, only 1 free
    assert al.alloc_slot(1, 3) is not None     # 1 page still fits
    assert al.free_pages == 0
    assert al.extend_slot(1) is None           # arena dry
    # per-sequence cap is a distinct failure: pages exist but the request
    # is at its context limit
    al2 = PagedKVAllocator(n_pages=8, page_size=4, max_pages_per_seq=2)
    al2.alloc_slot(0, 8)
    assert al2.extend_slot(0) is None and al2.free_pages == 6
    assert pages_for(0, 4) == 0 and pages_for(1, 4) == 1


def test_defrag_compacts_and_rewrites_tables():
    al = PagedKVAllocator(n_pages=8, page_size=2, max_pages_per_seq=4)
    al.alloc_slot(0, 4)
    al.alloc_slot(1, 4)
    al.alloc_slot(2, 2)
    al.free_slot(1)                            # hole in the middle
    before = {s: al.slot_pages(s) for s in (0, 2)}
    perm = al.defrag()
    after = {s: al.slot_pages(s) for s in (0, 2)}
    # live pages now occupy [0, used) and tables follow the permutation
    live = sorted(p for pages in after.values() for p in pages)
    assert live == list(range(al.used_pages))
    for s in (0, 2):
        assert [int(perm[p]) for p in before[s]] == after[s]
    # allocator still functional post-defrag
    assert al.alloc_slot(3, 6) is not None


def test_alloc_hold_release():
    """hold_pages withholds free pages from every admission/alloc path
    (the fault injector's arena-pressure lever) and release_held restores
    them exactly; holds clamp to the free list and stack."""
    al = PagedKVAllocator(n_pages=8, page_size=4, max_pages_per_seq=8)
    al.alloc_slot(0, 8)                        # 2 pages live, 6 free
    assert al.hold_pages(4) == 4
    assert al.held_pages == 4 and al.free_pages == 2
    assert not al.can_admit(12)                # 3 pages > 2 visible
    assert al.alloc_slot(1, 12) is None
    assert al.alloc_slot(1, 8) is not None     # 2 pages still fit
    # stacking + clamping: only 0 free left, an oversized hold is bounded
    assert al.hold_pages(5) == 0 == al.free_pages
    assert al.extend_slot(1) is None           # arena looks dry
    assert al.release_held() == 4
    assert al.held_pages == 0 and al.free_pages == 4
    assert al.extend_slot(1) is not None       # pressure gone
    # no page was lost or duplicated across the hold cycle
    live = {p for s in (0, 1) for p in al.slot_pages(s)}
    assert len(live) == al.used_pages == 5
    assert al.free_pages + al.used_pages == 8


def test_alloc_defrag_releases_holds():
    """Defrag mid-pressure: held pages are returned before the free list
    is rebuilt (a surviving hold would alias re-issued pages), the
    permutation stays valid for the live slots, and the whole arena is
    accounted for afterwards."""
    al = PagedKVAllocator(n_pages=8, page_size=2, max_pages_per_seq=4)
    al.alloc_slot(0, 4)
    al.alloc_slot(1, 4)
    al.alloc_slot(2, 2)
    al.free_slot(1)                            # hole mid-arena
    assert al.hold_pages(2) == 2               # eviction-era pressure
    before = {s: al.slot_pages(s) for s in (0, 2)}
    perm = al.defrag()
    assert al.held_pages == 0                  # holds released, not leaked
    after = {s: al.slot_pages(s) for s in (0, 2)}
    live = sorted(p for pages in after.values() for p in pages)
    assert live == list(range(al.used_pages))
    for s in (0, 2):
        assert [int(perm[p]) for p in before[s]] == after[s]
    assert al.free_pages + al.used_pages == 8
    # every formerly-held page is allocatable again
    assert al.alloc_slot(3, 8) is not None     # needs 4 of the 5 free


def test_defrag_with_held_and_refcount_shared_pages():
    """PR-6 alias-safe defrag/free-list rebuild, now against the ISSUE-9
    lifecycle state: a physical page shared CoW across two tables (and the
    prefix index) must move exactly ONCE -- not be split into two copies
    or double-counted -- and a held page must not be resurrected into the
    rebuilt free list while pressure is on the old ids."""
    al = PagedKVAllocator(n_pages=8, page_size=4, max_pages_per_seq=8)
    a = al.alloc_slot(0, 12)                   # 3 pages
    assert al.publish_prefix(b"k0", a[0]) and al.publish_prefix(b"k1", a[1])
    hits = al.match_prefix([b"k0", b"k1"])
    assert hits == a[:2]
    b = al.alloc_slot_shared(1, 16, hits)      # shares 2, allocs 2 fresh
    assert b is not None and b[:2] == a[:2]
    al.free_slot(0)                            # a[2] freed; a[:2] survive
    al.check()
    assert al.hold_pages(1) == 1               # pressure during defrag
    before = al.slot_pages(1)
    perm = al.defrag()
    al.check()                                 # partition + refcounts exact
    assert al.held_pages == 0                  # released, never resurrected
    after = al.slot_pages(1)
    # the shared pages moved once: table follows the permutation, stays
    # a single physical page per logical position (no split, no dupe)
    assert [int(perm[p]) for p in before] == after
    assert len(set(after)) == 4
    assert sorted(after) == list(range(4))     # compacted to the front
    # the prefix index was remapped with the same permutation: a match
    # still lands on the (moved) shared pages
    assert al.match_prefix([b"k0", b"k1"]) == after[:2]
    # eviction refuses to free the still-indexed pages; index retains them
    al.free_slot(1)
    al.check()
    assert al.prefix_index_pages == 2
    assert al.free_pages == al.n_pages - 2
    # and they remain reclaimable: a full-arena ask flushes the index
    assert al.can_admit(8 * 4)
    assert al.alloc_slot(2, 8 * 4) is not None
    al.check()
    assert al.prefix_index_pages == 0


# ---------------------------------------------------------------------------
# paged attention numerics
# ---------------------------------------------------------------------------
def _scattered_case(rng, b, h, kvh, d, page, mp, lens, poison=np.nan,
                    n_layers=1):
    """Contiguous per-request K/V of every layer plus the equivalent
    shuffled page pools, stacked as the paged steps carry them: kc/vc
    (L, B, MP*page, KVH, D), pools (L, KVH, NP, page, D), one block table
    for all layers. Each layer holds values of its own."""
    n_pool = b * mp + 2
    pool_k = np.full((n_layers, kvh, n_pool, page, d), poison, np.float32)
    pool_v = np.full((n_layers, kvh, n_pool, page, d), poison, np.float32)
    tables = np.zeros((b, mp), np.int32)
    free = list(rng.permutation(n_pool))
    kc = rng.standard_normal((n_layers, b, mp * page, kvh, d)).astype(
        np.float32)
    vc = rng.standard_normal((n_layers, b, mp * page, kvh, d)).astype(
        np.float32)
    for bb in range(b):
        for j in range(pages_for(int(lens[bb]), page)):
            pid = free.pop()
            tables[bb, j] = pid
            sl = slice(j * page, (j + 1) * page)
            pool_k[:, :, pid] = kc[:, bb, sl].transpose(0, 2, 1, 3)
            pool_v[:, :, pid] = vc[:, bb, sl].transpose(0, 2, 1, 3)
    return kc, vc, pool_k, pool_v, tables


# partial / tiny / full; whole pages beside an empty slot
_LENS = (37, 1, 80)
_WHOLE_PAGES = (32, 0, 48)
# a scratchpad that holds the K and V pages of 2 of 8 f32 heads, doubled
_SPAD_2_HEADS = 20 * 1024


@pytest.mark.parametrize("h,kvh,win,cap,n_layers,lens,spad", [
    pytest.param(4, 2, None, None, 1, _LENS, None, id="4-2-None-None"),
    pytest.param(4, 1, 24, None, 1, _LENS, None, id="4-1-24-None"),
    pytest.param(8, 8, None, 30.0, 1, _LENS, None, id="8-8-None-30.0"),
    pytest.param(4, 2, None, None, 3, _LENS, None,
                 id="4-2-None-None-3layers"),
    pytest.param(20, 20, None, None, 2, _LENS, None, id="20-20-mha"),
    pytest.param(8, 2, None, None, 2, _LENS, None, id="8-2-gqa-rep4"),
    pytest.param(16, 8, 24, None, 2, _LENS, _SPAD_2_HEADS,
                 id="16-8-head-blocks"),
    pytest.param(4, 2, None, None, 1, _WHOLE_PAGES, None,
                 id="4-2-whole-pages-empty-slot")])
def test_paged_kernel_vs_oracle(rng, h, kvh, win, cap, n_layers, lens, spad):
    """The Pallas paged-decode kernel (interpret mode) matches the dense
    oracle on scattered, NaN-poisoned pools: dead pages are skipped, the
    partial tail page is masked. On a stack of layers, layer li's output
    is the oracle's on layer li's values alone. A grid step takes a page
    of every KV head, or of as many as the config's scratchpad holds (the
    head-block case splits 8 heads into 4 blocks); an empty slot reads
    zeros."""
    from repro.kernels.contracts import paged_heads_per_block
    b, d, page, mp = 3, 32, 16, 5
    lens = np.array(lens, np.int32)
    cfg = None if spad is None else GemminiConfig(dim=16,
                                                  scratchpad_bytes=spad)
    hb = paged_heads_per_block(cfg or GemminiConfig(), kvh=kvh,
                               rep=h // kvh, page=page, d=d, itemsize=4)
    assert hb == (2 if spad else kvh)
    kc, vc, pk, pv, tables = _scattered_case(rng, b, h, kvh, d, page, mp,
                                             lens, n_layers=n_layers)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    for li in range(n_layers):
        y = ak.paged_decode_attention(q, jnp.asarray(pk), jnp.asarray(pv),
                                      jnp.asarray(tables), jnp.asarray(lens),
                                      jnp.int32(li), window=win, softcap=cap,
                                      cfg=cfg, interpret=True)
        for bb in range(b):
            L = int(lens[bb])
            if L == 0:
                np.testing.assert_array_equal(np.asarray(y[bb]), 0.0)
                continue
            yr = ref.mha_ref(q[bb:bb + 1],
                             jnp.asarray(kc[li, bb:bb + 1, :L]),
                             jnp.asarray(vc[li, bb:bb + 1, :L]), causal=True,
                             window=win, softcap=cap)
            np.testing.assert_allclose(np.asarray(y[bb]), np.asarray(yr[0]),
                                       rtol=2e-5, atol=2e-5)


def test_paged_xla_equals_dense_decode(rng):
    """The explicit-gather XLA path is exactly the dense decode_attention
    computation (same einsums/mask/softmax), request by request and layer
    by layer of a stack -- zeros in unwritten pool entries, as the engine
    allocates them."""
    b, h, kvh, d, page, mp, n_layers = 2, 4, 2, 16, 8, 4, 3
    lens = np.array([19, 27], np.int32)
    kc, vc, pk, pv, tables = _scattered_case(rng, b, h, kvh, d, page, mp,
                                             lens, poison=0.0,
                                             n_layers=n_layers)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    for li in range(n_layers):
        cache = mattn.PagedKVCache(jnp.asarray(pk), jnp.asarray(pv),
                                   jnp.asarray(tables), jnp.asarray(lens),
                                   page, jnp.int32(li))
        y = mattn.paged_decode_attention_xla(q, cache, window=8)
        for bb in range(b):
            dense = mattn.KVCache(jnp.asarray(kc[li, bb:bb + 1]),
                                  jnp.asarray(vc[li, bb:bb + 1]))
            yd = mattn.decode_attention(q[bb:bb + 1], dense,
                                        jnp.int32(int(lens[bb]) - 1),
                                        window=8)
            np.testing.assert_array_equal(np.asarray(y[bb]),
                                          np.asarray(yd[0]))


def _roundtrip(rng, n_layers, li):
    """Prefill scatter, then a decode scatter with slot 1 inactive, into
    layer ``li`` of a stack of ``n_layers`` distinct layers. Returns the
    stack before, the two results, the written values and the geometry."""
    kvh, d, page, np_pages = 2, 8, 4, 6
    pool = jnp.asarray(rng.standard_normal((n_layers, kvh, np_pages + 1,
                                            page, d)), jnp.float32)
    cache = mattn.PagedKVCache(pool, pool, jnp.asarray([[3, 1, 0], [2, 4, 0]],
                                                       jnp.int32),
                               jnp.asarray([5, 0], jnp.int32), page,
                               jnp.int32(li), jnp.asarray([True, False]),
                               np_pages)
    kc = jnp.asarray(rng.standard_normal((1, 6, kvh, d)), jnp.float32)
    up = mattn.paged_update_prefill(cache, kc, kc, cache.tables[0])
    # decode write: slot0 at len=5 -> page 1 offset 1; slot1 inactive ->
    # trash page (id np_pages), lengths frozen
    k1 = jnp.asarray(rng.standard_normal((2, 1, kvh, d)), jnp.float32)
    dec = mattn.paged_update_decode(cache, k1, k1, cache.active, cache.trash)
    return pool, up, dec, kc, k1, np_pages


def test_paged_update_roundtrip(rng):
    """Prefill scatter + decode scatter land tokens at the right logical
    positions of the named layer (layer 1 of three); inactive slots spill
    to the trash page only."""
    _, up, dec, kc, k1, np_pages = _roundtrip(rng, 3, 1)
    # position 5 -> page tables[0][1]=1, offset 1
    np.testing.assert_array_equal(np.asarray(up.k[1, :, 1, 1]),
                                  np.asarray(kc[0, 5]))
    np.testing.assert_array_equal(np.asarray(dec.k[1, :, 1, 1]),
                                  np.asarray(k1[0, 0]))
    np.testing.assert_array_equal(np.asarray(dec.k[1, :, np_pages, 0]),
                                  np.asarray(k1[1, 0]))
    assert list(np.asarray(dec.lengths)) == [6, 0]


@pytest.mark.parametrize("li", [0, 1, 2])
def test_paged_update_lands_in_layer(rng, li):
    """On a stack of three layers, the prefill and decode writes of layer
    li land in layer li alone -- an inactive slot's token in li's trash
    page and in no other layer's -- and leave every other value as it
    was."""
    pool, up, dec, kc, k1, np_pages = _roundtrip(rng, 3, li)
    before = np.asarray(pool)
    want = before.copy()
    for pos in range(6):                     # table [3, 1, 0], page 4
        want[li, :, [3, 1][pos // 4], pos % 4] = np.asarray(kc[0, pos])
    np.testing.assert_array_equal(np.asarray(up.k), want)
    np.testing.assert_array_equal(np.asarray(up.v), want)
    want = before.copy()
    want[li, :, 1, 1] = np.asarray(k1[0, 0])
    want[li, :, np_pages, 0] = np.asarray(k1[1, 0])      # li's trash page
    np.testing.assert_array_equal(np.asarray(dec.k), want)
    np.testing.assert_array_equal(np.asarray(dec.v), want)
    assert list(np.asarray(dec.lengths)) == [6, 0]


# ---------------------------------------------------------------------------
# scheduler policy
# ---------------------------------------------------------------------------
def _mk_req(rid, plen, gen=4):
    return Request(rid=rid, prompt=np.zeros((plen,), np.int32),
                   max_new_tokens=gen)


def test_admission_token_budget():
    al = PagedKVAllocator(n_pages=64, page_size=4, max_pages_per_seq=16)
    sc = ContinuousScheduler(al, n_slots=4, prefill_token_budget=10)
    for i in range(4):
        sc.submit(_mk_req(i, 8))
    admitted = sc.admissions()
    # first admission always lands; the second (8 + 8 > 10) must wait
    assert [r.rid for (r, _, _) in admitted] == [0, ]
    assert len(sc.queue) == 3
    assert [r.rid for (r, _, _) in sc.admissions()] == [1, ]


def test_preemption_evicts_youngest_and_requeues():
    al = PagedKVAllocator(n_pages=4, page_size=4, max_pages_per_seq=4)
    sc = ContinuousScheduler(al, n_slots=2, prefill_token_budget=1 << 20)
    sc.submit(_mk_req(0, 8))                   # 2 pages
    sc.submit(_mk_req(1, 8))                   # 2 pages
    (r0, s0, _), (r1, s1, _) = sc.admissions()
    r0.cache_len, r1.cache_len = 8, 8          # both at a page boundary
    new_pages, evicted, truncated = sc.ensure_decode_capacity()
    # arena dry: the youngest (r1) is evicted so the oldest can grow
    assert evicted == [r1] and not truncated
    assert r1.state == "queued" and r1.n_preempted == 1
    assert [slot for (slot, _) in new_pages] == [s0]
    assert al.slot_pages(s0) and len(al.slot_pages(s0)) == 3


def test_unservable_request_rejected_not_livelocked():
    """A request whose recompute prompt regrew past the arena is rejected
    at admission (engine finishes it truncated) instead of head-of-line
    blocking the queue forever."""
    al = PagedKVAllocator(n_pages=2, page_size=4, max_pages_per_seq=8)
    sc = ContinuousScheduler(al, n_slots=2, prefill_token_budget=1 << 20)
    grown = _mk_req(0, 4)
    grown.generated = [1] * 8              # preempted after 8 tokens: 12 > 8
    ok = _mk_req(1, 4)
    sc.submit(grown)
    sc.submit(ok)
    admitted = sc.admissions()
    assert [r.rid for (r, _, _) in admitted] == [1]
    assert sc.rejected == [grown]


def test_sole_runner_truncates_at_capacity():
    al = PagedKVAllocator(n_pages=2, page_size=4, max_pages_per_seq=8)
    sc = ContinuousScheduler(al, n_slots=2, prefill_token_budget=1 << 20)
    sc.submit(_mk_req(0, 8))
    (req, _, _), = sc.admissions()
    req.cache_len = 8
    _, evicted, truncated = sc.ensure_decode_capacity()
    assert truncated == [req] and not evicted


def test_admission_policy_priority_order():
    """priority admits highest class first (deadline, then age tiebreak);
    FIFO (default) is untouched."""
    al = PagedKVAllocator(n_pages=64, page_size=4, max_pages_per_seq=16)
    sc = ContinuousScheduler(al, n_slots=4, prefill_token_budget=1 << 20,
                             admission_policy="priority")
    r0, r1, r2 = _mk_req(0, 4), _mk_req(1, 4), _mk_req(2, 4)
    r1.priority, r2.priority = 5, 5
    r2.deadline = 10.0                       # same class, tighter SLO
    for r in (r0, r1, r2):
        sc.submit(r)
    assert [r.rid for (r, _, _) in sc.admissions()] == [2, 1, 0]


def test_admission_policy_deadline_edf_and_preempted_first():
    """deadline = earliest-deadline-first, deadline-less requests last;
    a preempted request outranks every queued one under any policy."""
    al = PagedKVAllocator(n_pages=64, page_size=4, max_pages_per_seq=16)
    sc = ContinuousScheduler(al, n_slots=4, prefill_token_budget=1 << 20,
                             admission_policy="deadline")
    r0, r1, r2 = _mk_req(0, 4), _mk_req(1, 4), _mk_req(2, 4)
    r0.deadline, r1.deadline = 50.0, 20.0    # r2: best-effort
    for r in (r0, r1, r2):
        sc.submit(r)
    (a, _, _), (b, _, _), (c, _, _) = sc.admissions()
    assert [a.rid, b.rid, c.rid] == [1, 0, 2]
    sc.preempt(c)                            # best-effort, but holds debt
    order = sc.admissions()
    assert [r.rid for (r, _, _) in order] == [2]


def test_admission_tie_break_deterministic_rid_order():
    """Equal-deadline EDF and equal-priority classes tie-break on rid:
    with identical logical timestamps (the model checker's LogicalClock
    makes timestamp collisions the common case, and batch submitters hit
    it in production too) the admission order must be invariant under
    queue permutation."""
    rng = np.random.default_rng(1234)
    for policy in ("priority", "deadline"):
        for _ in range(5):
            al = PagedKVAllocator(n_pages=64, page_size=4,
                                  max_pages_per_seq=16)
            sc = ContinuousScheduler(al, n_slots=8,
                                     prefill_token_budget=1 << 20,
                                     admission_policy=policy,
                                     clock=lambda: 0.0)
            reqs = [_mk_req(i, 4) for i in range(6)]
            for r in reqs:
                r.priority, r.deadline = 3, 42.0
            for i in rng.permutation(6):
                sc.submit(reqs[i])
            assert [r.rid for (r, _, _) in sc.admissions()] == list(range(6))


def test_admission_policy_unknown_rejected():
    al = PagedKVAllocator(n_pages=8, page_size=4, max_pages_per_seq=4)
    with pytest.raises(ValueError):
        ContinuousScheduler(al, n_slots=2, admission_policy="sjf")


def test_engine_priority_admission_end_to_end(rng):
    """Under a 1-slot engine a high-priority late submission decodes first
    and produces exactly the same tokens as its FIFO run (admission order
    changes scheduling, never numerics)."""
    params = tf.init_params(jax.random.PRNGKey(3), _TINY)
    prompts = [rng.integers(0, _TINY.vocab, (12,)).astype(np.int32)
               for _ in range(3)]
    by_policy = {}
    for policy in ("fifo", "priority"):
        eng = ServingEngine(_TINY, max_slots=1, max_context=64,
                            page_size=8, params=params,
                            admission_policy=policy)
        reqs = [eng.submit(p, 4, priority=i) for i, p in enumerate(prompts)]
        eng.run()
        by_policy[policy] = {r.rid: list(np.asarray(r.generated).ravel())
                             for r in reqs}
        if policy == "priority":
            # highest priority (last submitted) finished first
            finish = sorted(reqs, key=lambda r: r.t_finished)
            assert [r.rid for r in finish] == [2, 1, 0]
    assert by_policy["fifo"] == by_policy["priority"]


# ---------------------------------------------------------------------------
# engine end-to-end vs the static reference path
# ---------------------------------------------------------------------------
_TINY = tf.ModelConfig(name="tiny-serve", family="dense", n_layers=2,
                       d_model=32, vocab=64, n_heads=2, n_kv_heads=1,
                       head_dim=16, d_ff=64, dtype=jnp.float32)


def _run_vs_reference(eng, prompts, gens):
    for p, g in zip(prompts, gens):
        eng.submit(p, g)
    rep = eng.run()
    for r, p, g in zip(rep["requests"], prompts, gens):
        want = reference_tokens(eng.model_cfg, eng.params, p, g)
        np.testing.assert_array_equal(np.asarray(r["tokens"]).ravel(), want)
    return rep


@pytest.mark.slow
def test_engine_matches_reference_greedy(rng):
    eng = ServingEngine(_TINY, max_slots=2, max_context=48, page_size=8,
                        n_pages=16, temperature=0.0, seed=0)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in (5, 11, 3)]
    rep = _run_vs_reference(eng, prompts, [4, 2, 6])
    s = rep["summary"]
    assert s["requests"] == 3 and s["tokens_per_s"] > 0
    assert s["p50_latency_s"] <= s["p99_latency_s"] + 1e-9
    assert s["p50_ttft_s"] <= s["p50_latency_s"] + 1e-9


@pytest.mark.slow
def test_engine_correct_under_eviction(rng):
    """A starved arena forces preemption-by-eviction mid-decode; the
    recompute restart must still produce the exact reference stream."""
    eng = ServingEngine(_TINY, max_slots=2, max_context=32, page_size=8,
                        n_pages=4, temperature=0.0, seed=0)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in (7, 9, 6)]
    rep = _run_vs_reference(eng, prompts, [10, 9, 8])
    assert rep["summary"]["preemptions"] > 0
    assert rep["summary"]["truncated"] == 0


def test_engine_static_policy_matches_reference(rng):
    eng = ServingEngine(_TINY, max_slots=2, max_context=48, page_size=8,
                        n_pages=16, temperature=0.0, seed=0,
                        policy="static")
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in (5, 8, 4)]
    _run_vs_reference(eng, prompts, [3, 6, 2])


def test_engine_interpret_backend_routes_pallas(rng):
    """backend="interpret" drives the Pallas flash-attention (prefill) and
    paged-decode kernels end-to-end; greedy tokens agree with the xla
    engine (f32 model, identical masked math)."""
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (5, 9)]
    reps = {}
    for backend in ("xla", "interpret"):
        eng = ServingEngine(_TINY, max_slots=2, max_context=32, page_size=8,
                            n_pages=8, temperature=0.0, seed=0,
                            backend=backend)
        for p in prompts:
            eng.submit(p, 3)
        reps[backend] = [np.asarray(r["tokens"])
                         for r in eng.run()["requests"]]
    for a, b in zip(reps["xla"], reps["interpret"]):
        np.testing.assert_array_equal(a, b)


def test_engine_defrag_preserves_live_requests(rng):
    """Defrag mid-flight: pools permute, tables rewrite, decode continues
    to the exact reference stream."""
    eng = ServingEngine(_TINY, max_slots=2, max_context=48, page_size=8,
                        n_pages=16, temperature=0.0, seed=0)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (9, 6)]
    for p in prompts:
        eng.submit(p, 5)
    eng.step()                                  # prefill + first decode
    eng.defrag()
    while eng.sched.has_work:
        eng.step()
    for r, p in zip(eng.requests, prompts):
        want = reference_tokens(_TINY, eng.params, p, 5)
        np.testing.assert_array_equal(
            np.asarray([int(t) for t in r.generated]), want)


@pytest.mark.slow
def test_engine_defrag_under_arena_pressure(rng):
    """Defrag interleaved with injected arena exhaustion (plus the
    eviction pressure a small arena already produces): holds never leak
    into the rebuilt free list, and every stream still matches the
    fault-free reference exactly."""
    eng = ServingEngine(_TINY, max_slots=2, max_context=32, page_size=8,
                        n_pages=6, temperature=0.0, seed=0,
                        faults="seed=7;arena:pages=2,start=1,max=4")
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (9, 6, 7)]
    for p in prompts:
        eng.submit(p, 6)
    eng.step()
    eng.defrag()                               # between pressured steps
    steps = 0
    while eng.sched.has_work:
        eng.step()
        steps += 1
        if steps == 2:
            eng.defrag()
        assert eng.alloc.held_pages == 0       # pressure is per-step only
    assert eng.faults.report().get("arena@arena", 0) == 4
    for r, p in zip(eng.requests, prompts):
        assert r.state == "finished"
        want = reference_tokens(_TINY, eng.params, p, 6)
        np.testing.assert_array_equal(
            np.asarray([int(t) for t in r.generated]), want)


# ---------------------------------------------------------------------------
# chunked prefill: kernel / twin numerics
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("start,tq,h,kvh,win,n_layers", [
    pytest.param(16, 8, 4, 2, None, 1, id="16-8-4-2-None"),
    pytest.param(24, 11, 4, 1, 16, 1, id="24-11-4-1-16"),
    pytest.param(0, 7, 8, 8, None, 1, id="0-7-8-8-None"),
    pytest.param(16, 8, 4, 2, None, 3, id="16-8-4-2-None-3layers")])
def test_paged_prefill_kernel_vs_oracle(rng, start, tq, h, kvh, win,
                                        n_layers):
    """The chunked-prefill Pallas kernel (interpret mode) matches the dense
    oracle on scattered, NaN-poisoned pools: a chunk of queries at
    [start, start+tq) attends exactly the live prefix, dead pages beyond
    the frontier are skipped. On a stack of layers, layer li's output is
    the oracle's on layer li's values alone."""
    d, page, mp = 32, 8, 6
    lens = np.array([start + tq], np.int32)
    kc, vc, pk, pv, tables = _scattered_case(rng, 1, h, kvh, d, page, mp,
                                             lens, n_layers=n_layers)
    q = jnp.asarray(rng.standard_normal((1, tq, h, d)), jnp.float32)
    ln = int(lens[0])
    for li in range(n_layers):
        y = ak.paged_prefill_attention(q, jnp.asarray(pk), jnp.asarray(pv),
                                       jnp.asarray(tables[0]),
                                       jnp.int32(start), jnp.int32(li),
                                       window=win, interpret=True)
        yr = ref.mha_ref(q, jnp.asarray(kc[li, :, :ln]),
                         jnp.asarray(vc[li, :, :ln]), causal=True, window=win)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-5, atol=2e-5)


def test_paged_prefill_xla_twin_bitwise(rng):
    """The explicit-gather XLA twin is bit-identical to the single-pass
    blockwise path for a continuation chunk's rows, in every layer of a
    stack: same KV blocking anchored at 0, same op staging (the
    exact-match gate in test_serving_families.py, which chunks its
    prompts, rests on this)."""
    h, kvh, d, page, mp, start, tq = 4, 2, 16, 8, 4, 13, 9
    n_layers = 3
    lens = np.array([start + tq], np.int32)
    kc, vc, pk, pv, tables = _scattered_case(rng, 1, h, kvh, d, page, mp,
                                             lens, poison=0.0,
                                             n_layers=n_layers)
    q = jnp.asarray(rng.standard_normal((1, tq, h, d)), jnp.float32)
    # the single-pass reference: full-prefix blockwise, rows [start, ...)
    ln = int(lens[0])
    qfull = jnp.asarray(
        np.concatenate([rng.standard_normal((1, start, h, d)),
                        np.asarray(q)], axis=1), jnp.float32)
    for li in range(n_layers):
        cache = mattn.PagedKVCache(jnp.asarray(pk), jnp.asarray(pv),
                                   jnp.asarray(tables), jnp.asarray(lens),
                                   page, jnp.int32(li))
        y = mattn.paged_prefill_attention_xla(q, cache, jnp.int32(start),
                                              window=8)
        yf = mattn.blockwise_attention_xla(qfull, jnp.asarray(kc[li, :, :ln]),
                                           jnp.asarray(vc[li, :, :ln]),
                                           causal=True, window=8)
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(yf[:, start:]))


def test_chunked_ssm_state_continuity(rng):
    """Resuming the SSD recurrent state across chunk boundaries reproduces
    the single-pass outputs and final state (tolerance: exp-of-sums
    reassociates across the boundary)."""
    from repro.models import ssm
    b, t, h, g, n, p = 2, 24, 4, 2, 8, 16
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 0.9, (b, t, h)), jnp.float32)
    a_log = jnp.asarray(rng.uniform(-1, 0.5, (h,)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, t, g, n)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((b, t, g, n)), jnp.float32)
    y_full = ssm.ssd_chunked_xla(x, dt, a_log, bb, cc, chunk=8)
    _, s_full = ssm._final_state(x, dt, a_log, bb, cc)
    state, ys = None, []
    for lo in (0, 9, 17):                  # non-aligned chunk boundaries
        hi = {0: 9, 9: 17, 17: t}[lo]
        sl = slice(lo, hi)
        ys.append(ssm.ssd_chunked_xla(x[:, sl], dt[:, sl], a_log,
                                      bb[:, sl], cc[:, sl], chunk=8,
                                      initial_state=state))
        _, state = ssm._final_state(x[:, sl], dt[:, sl], a_log, bb[:, sl],
                                    cc[:, sl], initial_state=state)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, axis=1)),
                               np.asarray(y_full), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_full),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# chunked prefill: scheduler chunk queue
# ---------------------------------------------------------------------------
def test_prefill_schedule_chunk_queue_budget_and_order():
    """Continuation chunks come before new admissions; the token budget
    bounds the iteration's prefill work (first item always lands)."""
    al = PagedKVAllocator(n_pages=64, page_size=4, max_pages_per_seq=16)
    sc = ContinuousScheduler(al, n_slots=4, prefill_token_budget=8,
                             prefill_chunk=8)
    sc.submit(_mk_req(0, 20))
    w = sc.prefill_schedule()
    assert [(c.req.rid, c.start, c.true_end, c.first, c.last)
            for c in w] == [(0, 0, 8, True, False)]
    sc.submit(_mk_req(1, 4))
    w = sc.prefill_schedule()              # rid0's continuation wins the
    assert [(c.req.rid, c.start) for c in w] == [(0, 8)]   # whole budget
    # rid0's last chunk charges 4 of the 8-token budget; rid1's 4-token
    # prompt fits in the remainder and admits in the same iteration
    w = sc.prefill_schedule()
    assert [(c.req.rid, c.start, c.first, c.last) for c in w] == \
        [(0, 16, False, True), (1, 0, True, True)]
    for c in w:
        assert not sc.running[c.slot].prefilling
    assert sc.prefill_schedule() == []


def test_prefill_schedule_admit_new_false_still_continues():
    """The static barrier blocks admissions, never in-flight chunks.
    Admission always emits just the first chunk; continuations drain on
    later iterations (under a generous budget, several per iteration)."""
    al = PagedKVAllocator(n_pages=64, page_size=4, max_pages_per_seq=16)
    sc = ContinuousScheduler(al, n_slots=2, prefill_token_budget=1 << 20,
                             prefill_chunk=8)
    sc.submit(_mk_req(0, 20))
    w = sc.prefill_schedule()
    assert [(c.req.rid, c.start, c.first) for c in w] == [(0, 0, True)]
    sc.submit(_mk_req(1, 20))
    w = sc.prefill_schedule(admit_new=False)     # barrier: rid0 continues,
    assert [(c.req.rid, c.start) for c in w] == [(0, 8), (0, 16)]
    assert sc.prefill_schedule(admit_new=False) == []   # rid1 stays queued
    assert [(c.req.rid, c.start) for c in sc.prefill_schedule()] == [(1, 0)]


def test_chunk_spans_non_aligned_and_short():
    al = PagedKVAllocator(n_pages=8, page_size=8, max_pages_per_seq=8)
    sc = ContinuousScheduler(al, n_slots=1, pad_to=8, prefill_chunk=6)
    # shorter than one chunk: single span, classic bucket padding
    assert sc._chunk_spans(_mk_req(0, 5)) == [(0, 5, 8)]
    # non-page-aligned chunks; last span padded to the compile bucket,
    # capped at the single-pass footprint (roundup(14, 8) = 16, not 20)
    assert sc._chunk_spans(_mk_req(1, 14)) == [(0, 6, 6), (6, 12, 12),
                                               (12, 14, 16)]


# ---------------------------------------------------------------------------
# chunked prefill: engine end-to-end
# ---------------------------------------------------------------------------
_TINY_SSM = tf.ModelConfig(name="tiny-serve-ssm", family="ssm", n_layers=2,
                           d_model=32, vocab=64, d_state=8, ssm_head_dim=8,
                           ssm_chunk=8, dtype=jnp.float32)


@pytest.mark.slow
@pytest.mark.parametrize("chunk,page", [(8, 8), (6, 8)])
def test_chunked_engine_matches_reference(rng, chunk, page):
    """Exact token match vs the single-pass static reference with chunking
    on: prompts shorter than one chunk, exactly one chunk, and multi-chunk
    -- page-aligned and not."""
    eng = ServingEngine(_TINY, max_slots=2, max_context=48, page_size=page,
                        n_pages=16, temperature=0.0, seed=0,
                        prefill_chunk=chunk)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in (19, 3, chunk, 11)]
    rep = _run_vs_reference(eng, prompts, [4, 6, 3, 5])
    by_rid = {r["rid"]: r for r in rep["requests"]}
    assert by_rid[0]["prefill_chunks"] == -(-19 // chunk)
    assert by_rid[1]["prefill_chunks"] == 1          # short: classic path
    assert rep["summary"]["prefill_chunks"] >= 6
    assert rep["summary"]["p50_itl_s"] >= 0.0


def test_chunked_single_token_final_chunk(rng):
    """A final chunk of exactly ONE token (recurrent families never pad,
    so total % chunk == 1 happens) must route through the chunk path, not
    the t == 1 decode branch (whose cache has no active mask here)."""
    eng = ServingEngine(_TINY, max_slots=2, max_context=48, page_size=8,
                        n_pages=16, temperature=0.0, seed=0,
                        prefill_chunk=8)
    # force pad_to=1 so the last span is exactly one position long
    eng.sched.pad_to = 1
    prompts = [rng.integers(0, 64, (17,)).astype(np.int32)]
    rep = _run_vs_reference(eng, prompts, [4])
    assert rep["requests"][0]["prefill_chunks"] == 3


@pytest.mark.slow
def test_chunked_engine_ssm_matches_reference(rng):
    """SSM-family chunked prefill resumes the recurrent state per chunk (no
    padding, exact-length chunks) and still reproduces the reference
    stream."""
    eng = ServingEngine(_TINY_SSM, max_slots=2, max_context=48, page_size=8,
                        n_pages=16, temperature=0.0, seed=0,
                        prefill_chunk=7)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in (17, 4, 10)]
    rep = _run_vs_reference(eng, prompts, [5, 3, 4])
    assert rep["summary"]["prefill_chunks"] > 3


@pytest.mark.slow
def test_chunked_eviction_mid_prefill_recompute(rng):
    """A starved arena evicts the youngest runner MID-PREFILL (its pages
    and carried state are gone); the chunk-zero recompute restart still
    produces the exact reference stream."""
    eng = ServingEngine(_TINY, max_slots=2, max_context=32, page_size=8,
                        n_pages=3, temperature=0.0, seed=0,
                        prefill_chunk=8)
    prompts = [rng.integers(0, 64, (7,)).astype(np.int32),
               rng.integers(0, 64, (20,)).astype(np.int32)]
    rep = _run_vs_reference(eng, prompts, [10, 4])
    assert rep["summary"]["preemptions"] > 0
    assert rep["summary"]["truncated"] == 0
    assert rep["requests"][1]["prefill_chunks"] > 3   # restarted chunks


def test_chunked_engine_interpret_backend(rng):
    """backend="interpret" drives the chunked-prefill Pallas kernel
    (block-table gather) end-to-end; greedy tokens agree with the xla
    engine. The engine cfg is pinned f32 end-to-end, as in the SSM twin
    below: the xla backend's float projection shortcut and the kernel
    backends' engine datapath round differently in bf16, and greedy
    tokens of random weights flip on that rounding."""
    f32 = GemminiConfig(input_dtype="fp32", acc_dtype="fp32",
                        output_dtype="fp32")
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (13, 4)]
    reps = {}
    for backend in ("xla", "interpret"):
        eng = ServingEngine(_TINY, max_slots=2, max_context=32, page_size=8,
                            n_pages=8, temperature=0.0, seed=0,
                            backend=backend, prefill_chunk=8, engine_cfg=f32)
        for p in prompts:
            eng.submit(p, 3)
        reps[backend] = [np.asarray(r["tokens"])
                         for r in eng.run()["requests"]]
    for a, b in zip(reps["xla"], reps["interpret"]):
        np.testing.assert_array_equal(a, b)


def test_engine_ssm_interpret_backend_fused_kernel(rng):
    """backend="interpret" drives the fused SSD kernel on the SSM-family
    fresh-prefill path (d_skip + final recurrent state emitted in-kernel;
    SSMCache(conv, None) fresh marker); greedy tokens agree with the xla
    engine. The engine cfg is pinned f32 end-to-end so the two backends
    differ only by the kernel's (1e-7-level) reassociation -- the default
    bf16 engine would round every projection on the interpret path."""
    f32 = GemminiConfig(input_dtype="fp32", acc_dtype="fp32",
                        output_dtype="fp32")
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (9, 5)]
    reps = {}
    for backend in ("xla", "interpret"):
        eng = ServingEngine(_TINY_SSM, max_slots=2, max_context=32,
                            page_size=8, n_pages=8, temperature=0.0,
                            seed=0, backend=backend, engine_cfg=f32)
        for p in prompts:
            eng.submit(p, 3)
        reps[backend] = [np.asarray(r["tokens"])
                         for r in eng.run()["requests"]]
    for a, b in zip(reps["xla"], reps["interpret"]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# paged schedule through the tuner
# ---------------------------------------------------------------------------
@pytest.fixture
def tmp_cache(tmp_path):
    from repro.tune import cache as tcache
    path = str(tmp_path / "plans.json")
    prev_cache = flags.get("tune_cache")
    prev_mode = flags.get("tune_mode")
    flags.set_flag("tune_cache", path)
    tcache.reset_cache()
    yield path
    flags.set_flag("tune_cache", prev_cache)
    flags.set_flag("tune_mode", prev_mode)
    tcache.reset_cache()


def test_paged_schedule_lattice_legal():
    from repro.tune import schedules
    cfg = GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                        output_dtype="bf16")
    cands = schedules.enumerate_paged_schedules(cfg, 4, 8, 2, 64, 2048)
    assert cands
    default = schedules.default_paged_schedule().effective(2048)
    assert default in cands
    for s in cands:
        assert 8 <= s.page_size <= 2048
        assert schedules.paged_attn_cycles(
            s, cfg, 4, 8, 2, 64, 2048, window=None, in_bytes=2) > 0
    # a sliding window shrinks the live page count, never breaks ranking
    c1 = schedules.paged_attn_cycles(cands[0], cfg, 4, 8, 2, 64, 2048,
                                     window=128, in_bytes=2)
    c2 = schedules.paged_attn_cycles(cands[0], cfg, 4, 8, 2, 64, 2048,
                                     window=None, in_bytes=2)
    assert c1 <= c2


def test_paged_cycles_charge_per_head_block():
    """The fixed per-step cost is charged per (slot, head block, live
    page): a scratchpad that holds only 4 of 8 heads' K and V pages,
    double-buffered, costs the model 2 steps a live page where every head
    fits in one."""
    from repro.tune import schedules
    sched = schedules.default_paged_schedule()
    whole = GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                          output_dtype="bf16")
    b, kvh, d, page = 4, 8, 64, 64
    four_heads = 2 * 2 * 4 * page * d * 2         # buffers x (K, V) x bf16
    split = dataclasses.replace(whole, scratchpad_bytes=four_heads)
    live = 1024 // page                            # mean_len 2048 / 2
    cost = {name: schedules.paged_attn_cycles(
        sched, cfg, b, kvh, kvh, d, 2048, window=None, in_bytes=2)
        for name, cfg in (("whole", whole), ("split", split))}
    assert cost["split"] - cost["whole"] == pytest.approx(
        b * (2 - 1) * live * schedules.PAGE_STEP_CYCLES)


def test_paged_schedule_cache_roundtrip(tmp_cache):
    from repro.tune import cache as tcache
    from repro.tune import tuner
    cfg = GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                        output_dtype="bf16")
    flags.set_flag("tune_mode", "full")
    rep = tuner.tune_paged_attention(cfg, 2, 4, 2, 32, 256, iters=1)
    assert rep.cache_key
    # a fresh cache object resolves the persisted winner without measuring
    tcache.reset_cache()
    flags.set_flag("tune_mode", "cached")
    pc = tcache.get_cache()
    hits0 = pc.hits
    sched = tuner.resolve_paged_attn_schedule(cfg, 2, 4, 2, 32, 256)
    assert pc.hits == hits0 + 1
    assert sched == rep.sched
    # a different context misses and degrades to the static default
    from repro.tune import schedules
    other = tuner.resolve_paged_attn_schedule(cfg, 2, 4, 2, 32, 512)
    assert other == schedules.default_paged_schedule().effective(512)


def test_warm_model_plans_covers_paged(tmp_cache):
    from repro import tune
    flags.set_flag("tune_mode", "full")
    cfg = GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                        output_dtype="bf16")
    stats = tune.warm_model_plans(cfg, _TINY, 1, 16, include_decode=False,
                                  paged_slots=2, paged_max_context=64)
    assert stats["paged_shapes"] == 1          # one distinct window (global)
    # warm-then-serve: the engine's page-size resolution is a pure hit
    flags.set_flag("tune_mode", "cached")
    pc = tune.get_cache()
    hits0 = pc.hits
    tune.resolve_paged_attn_schedule(cfg, 2, _TINY.n_heads,
                                     _TINY.n_kv_heads, _TINY.head_dim, 64,
                                     dtype=_TINY.dtype)
    assert pc.hits == hits0 + 1
