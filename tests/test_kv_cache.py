"""PageAllocator / PagedKV invariants: refcounts, prefix index accounting,
chained-hash sharing, exhaustion, and no-double-allocation — property-based
where hypothesis is available, example-based otherwise."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: degrade property tests to skips
    from _hypothesis_stub import given, settings, st

from repro.serving.kv_cache import OutOfPages, PageAllocator, PagedKV


# ---------------------------------------------------------------------------
# PageAllocator
# ---------------------------------------------------------------------------


def test_out_of_pages_exactly_at_exhaustion():
    a = PageAllocator(4, page_size=8)
    got = [a.alloc() for _ in range(4)]
    assert sorted(got) == [0, 1, 2, 3]          # every page handed out once
    with pytest.raises(OutOfPages):
        a.alloc()
    a.release(got[0])
    assert a.alloc() == got[0]                   # freeing reopens exactly one
    with pytest.raises(OutOfPages):
        a.alloc()


def test_no_double_allocation_under_churn():
    rng = np.random.default_rng(0)
    a = PageAllocator(8, page_size=8)
    live = set()
    for _ in range(500):
        if live and rng.random() < 0.45:
            pid = live.pop()
            a.release(pid)
        else:
            try:
                pid = a.alloc()
            except OutOfPages:
                continue
            assert pid not in live, "page handed out twice"
            live.add(pid)
        assert a.in_use == len(live)
    for pid in live:
        assert a.refcount[pid] == 1


def test_refcount_share_release_cycle():
    a = PageAllocator(2, page_size=8)
    pid = a.alloc()
    a.retain(pid)
    a.retain(pid)
    assert a.refcount[pid] == 3
    a.release(pid)
    a.release(pid)
    assert a.refcount[pid] == 1 and pid not in [p for p in a.free]
    a.release(pid)
    assert a.refcount[pid] == 0 and pid in a.free


def test_prefix_index_hit_miss_accounting():
    a = PageAllocator(8, page_size=4)
    toks = list(range(11))                       # 2 full pages + 3 tail
    pages = [a.alloc(), a.alloc(), a.alloc()]
    a.publish_prefix(toks, pages)
    # only full pages are indexed
    assert len(a.prefix_index) == 2

    hit_pages, n = a.lookup_prefix(toks)
    assert hit_pages == pages[:2] and n == 8
    assert (a.hits, a.misses) == (1, 0)
    assert a.refcount[pages[0]] == 2             # lookup retains

    miss_pages, n = a.lookup_prefix([99, 98, 97, 96])
    assert miss_pages == [] and n == 0
    assert (a.hits, a.misses) == (1, 1)


def test_chained_hash_shares_identical_prefixes_only():
    """Prefixes equal through page k share exactly k pages: the chained
    hash makes page k+1's identity depend on everything before it."""
    a = PageAllocator(16, page_size=4)
    base = [1, 2, 3, 4, 5, 6, 7, 8]
    pages = [a.alloc(), a.alloc()]
    a.publish_prefix(base, pages)

    same_first = [1, 2, 3, 4, 9, 9, 9, 9]
    got, n = a.lookup_prefix(same_first)
    assert got == pages[:1] and n == 4

    # same page-2 CONTENT but different page 1: chained hash must miss
    diff_first = [9, 9, 9, 9, 5, 6, 7, 8]
    got, n = a.lookup_prefix(diff_first)
    assert got == [] and n == 0


def test_cached_pages_evicted_lazily_on_exhaustion():
    a = PageAllocator(2, page_size=4)
    toks = [1, 2, 3, 4]
    pid = a.alloc()
    a.publish_prefix(toks, [pid])
    a.release(pid)                               # resident, refcount 0
    assert pid not in a.free and a.available == 2
    # exhaustion evicts the unreferenced cached page instead of failing
    got = [a.alloc(), a.alloc()]
    assert sorted(got) == [0, 1]
    assert not a.prefix_index                    # index entry dropped


def test_eviction_is_lru_by_last_touch_not_dict_order():
    """Regression: ``_evict_unreferenced`` used to walk ``page_hash`` in
    dict-insertion order, so eviction (and tier-spill victim selection)
    depended on publication history rather than recency.  It must evict
    strictly by last-touch epoch (page id as the tie-break), one page per
    ``need`` — a hot prefix entry survives pressure longer than a cold one."""
    a = PageAllocator(4, page_size=4)
    pids = [a.alloc() for _ in range(4)]
    for i, pid in enumerate(pids[:3]):
        a.publish_prefix([10 + i] * 4, [pid])
        a.release(pid)                           # resident, refcount 0
    # touch in NON-insertion order: recency is now 0 < 2 < 1
    a.tick()
    a.touch(pids[0])
    a.tick()
    a.touch(pids[2])
    a.tick()
    a.touch(pids[1])
    # each exhausted alloc evicts exactly the least-recently-touched page
    got = [a.alloc() for _ in range(3)]
    assert got == [pids[0], pids[2], pids[1]], (
        "eviction followed insertion order, not last-touch LRU"
    )
    assert not a.prefix_index and not a.page_hash


def test_eviction_never_takes_referenced_pages():
    """A prefix-reachable page with refcount >= 1 (shared or live) is
    pinned: exhaustion evicts only unreferenced cached pages, and raises
    once none remain."""
    a = PageAllocator(2, page_size=4)
    hot = a.alloc()
    a.publish_prefix([1, 2, 3, 4], [hot])        # published AND referenced
    cold = a.alloc()
    a.publish_prefix([5, 6, 7, 8], [cold])
    a.release(cold)                              # only eviction candidate
    assert a.alloc() == cold
    with pytest.raises(OutOfPages):
        a.alloc()                                # hot page stays pinned
    assert a.page_hash.get(hot) is not None


@settings(max_examples=50, deadline=None)
@given(ops=st.lists(st.integers(0, 2), min_size=1, max_size=200),
       n_pages=st.integers(1, 6))
def test_allocator_invariants_random_ops(ops, n_pages):
    """Under any alloc/retain/release interleaving: refcounts stay >= 0,
    free pages have refcount 0, and live + free + resident == n_pages."""
    rng = np.random.default_rng(0)
    a = PageAllocator(n_pages, page_size=4)
    live = []
    for op in ops:
        if op == 0:
            try:
                live.append(a.alloc())
            except OutOfPages:
                pass
        elif op == 1 and live:
            a.retain(live[rng.integers(len(live))])
        elif op == 2 and live:
            pid = live.pop(rng.integers(len(live)))
            a.release(pid)
        assert (a.refcount >= 0).all()
        for pid in a.free:
            assert a.refcount[pid] == 0
        assert len(set(a.free)) == len(a.free)   # free list has no dupes
        assert set(live) <= set(range(n_pages)) - set(a.free)


# ---------------------------------------------------------------------------
# PagedKV
# ---------------------------------------------------------------------------


def _mk_kv(n_pages=8, page_size=4):
    return PagedKV(2, n_pages, 2, 4, page_size=page_size, dtype=jnp.float32)


def test_scratch_page_reserved():
    kv = _mk_kv()
    assert kv.scratch_page == 0
    assert 0 not in kv.allocator.free
    kv.open_seq(1, [1, 2, 3])
    kv.ensure_capacity(1, 3)
    assert 0 not in kv.tables[1].pages           # never handed to sequences


def test_open_close_releases_pages():
    kv = _mk_kv()
    kv.open_seq(7, [1, 2, 3, 4, 5])
    kv.ensure_capacity(7, 5)
    assert kv.seq_pages(7) == 2
    used = kv.allocator.in_use
    kv.close_seq(7)
    assert kv.allocator.in_use == used - 2
    assert 7 not in kv.tables


def test_trim_seq_releases_rejected_tail():
    kv = _mk_kv()
    kv.open_seq(1, [1, 2, 3])
    kv.ensure_capacity(1, 11)                    # speculate deep: 3 pages
    assert kv.seq_pages(1) == 3
    kv.set_len(1, 5)                             # only 5 tokens survived
    kv.trim_seq(1)
    assert kv.seq_pages(1) == 2                  # page 3 was unreachable
    kv.set_len(1, 8)
    kv.trim_seq(1)
    assert kv.seq_pages(1) == 2                  # boundary: page 2 full, kept


def test_prefix_sharing_shares_pages_and_refcounts():
    kv = _mk_kv()
    prompt = list(range(10))                     # 2 full pages + 2 tail
    kv.open_seq(1, prompt)
    kv.ensure_capacity(1, 10)
    kv.publish_seq_prefix(1, prompt)

    n_cached = kv.open_seq(2, prompt)
    assert n_cached == 8
    p1, p2 = kv.tables[1].pages, kv.tables[2].pages
    assert p1[:2] == p2[:2]                      # physical sharing
    for pid in p1[:2]:
        assert kv.allocator.refcount[pid] == 2
    kv.ensure_capacity(2, 10)
    assert p2[2] != p1[2]                        # tails stay private

    kv.close_seq(1)
    for pid in p2[:2]:
        assert kv.allocator.refcount[pid] == 1


def test_full_page_aligned_prompt_keeps_one_page_to_recompute():
    """A fully-cached, page-aligned prompt must give back its last cached
    page: prefill logits for the final position have to be recomputed and
    may only be written to pages the new sequence owns."""
    kv = _mk_kv()
    prompt = list(range(8))                      # exactly 2 pages
    kv.open_seq(1, prompt)
    kv.ensure_capacity(1, 8)
    kv.publish_seq_prefix(1, prompt)
    n_cached = kv.open_seq(2, prompt)
    assert n_cached == 4                         # last page recomputed
    assert len(kv.tables[2].pages) == 1


def test_free_tokens_accounting():
    kv = _mk_kv(n_pages=8, page_size=4)          # 7 usable after scratch
    assert kv.free_tokens == 7 * 4
    kv.open_seq(1, [1, 2, 3])
    kv.ensure_capacity(1, 6)
    assert kv.free_tokens == 5 * 4
    assert kv.resident_tokens() == 2 * 4
    kv.close_seq(1)
    assert kv.free_tokens == 7 * 4


def test_page_pool_is_head_major():
    """Pools are (L, n_pages, Hkv, page_size, hd): token t of head h of a
    page sits at [layer, page, h, t] — the kernel's (page, head) tile is a
    whole trailing (page_size, hd) block."""
    kv = _mk_kv(n_pages=8, page_size=4)
    assert kv.k_pages.shape == (2, 8, 2, 4, 4)
    kv.open_seq(1, [0])
    k = np.arange(2 * 3 * 2 * 4, dtype=np.float32).reshape(2, 3, 2, 4)
    kv.write_tokens(1, 2, jnp.asarray(k), jnp.asarray(-k))
    p0, p1 = kv.tables[1].pages
    kp = np.asarray(kv.k_pages)
    np.testing.assert_array_equal(kp[:, p0, :, 2], k[:, 0])
    np.testing.assert_array_equal(kp[:, p0, :, 3], k[:, 1])
    np.testing.assert_array_equal(kp[:, p1, :, 0], k[:, 2])


def test_write_and_gather_dense_roundtrip():
    kv = _mk_kv(n_pages=8, page_size=4)
    kv.open_seq(1, [0])
    rng = np.random.default_rng(0)
    k = rng.normal(size=(2, 6, 2, 4)).astype(np.float32)
    v = rng.normal(size=(2, 6, 2, 4)).astype(np.float32)
    kv.write_tokens(1, 0, jnp.asarray(k), jnp.asarray(v))
    kv.set_len(1, 6)
    kd, vd = kv.gather_dense(1, 6)
    np.testing.assert_allclose(np.asarray(kd), k, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vd), v, atol=1e-6)
