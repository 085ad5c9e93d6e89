"""Radix prefix cache (serving/prefix_cache.py): refcounted, copy-on-
write KV page sharing across requests.

Covers the ISSUE-4 acceptance surface: token-exactness vs per-request
generate() with the cache on AND off for full-page hits, partial-page
(copy-on-write) hits and misses — in mixed hit/miss batches under
decode_horizon_steps in {1, 8} with overlap on; refcount accounting
across donate -> share -> evict-under-pressure -> release (no leak, no
double free, the pool drains to empty); the bounded-compile-count
guarantee across cache churn; and fault-injected pool exhaustion with a
warm cache reclaiming cached pages BEFORE any live request is evicted.

Every scheduler here uses the SAME (slots, pages, page_size, max_pages,
chunk) constants, so jit signatures are shared across the module (the
test_serving.py scheme)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving import (PagePool, PagePoolExhausted, PrefixCache,
                                   ServingScheduler)

CFG = dict(num_slots=3, num_pages=32, page_size=16, max_pages_per_slot=8,
           prefill_chunk=8)
PS = CFG["page_size"]


@pytest.fixture(scope="module")
def engine():
    model = GPT2(gpt2_tiny())
    eng = deepspeed_tpu.init_inference(
        model=model, dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1})
    eng.init_params()
    return eng


def _oracle(engine, prompts, max_new):
    return [
        [int(t) for t in
         engine.generate(p[None], max_new_tokens=m, do_sample=False)[
             0, len(p):]]
        for p, m in zip(prompts, max_new)]


# --------------------------------------------------- host-only refcounts


def test_page_pool_refcount_share_release():
    pool = PagePool(num_pages=4, page_size=8)
    a = pool.allocate(2)
    assert all(pool.ref_count(p) == 1 for p in a)
    pool.share(a)                      # second holder
    assert all(pool.ref_count(p) == 2 for p in a)
    assert pool.total_shares == 2
    pool.free(a)                       # first holder lets go: still held
    assert pool.pages_in_use == 2 and pool.total_frees == 0
    pool.free(a)                       # last holder: pages recycle
    assert pool.pages_in_use == 0 and pool.total_frees == 2
    with pytest.raises(ValueError):    # double free past refcount 0
        pool.free([a[0]])
    with pytest.raises(ValueError):    # sharing a free page is a bug
        pool.share([a[0]])


def test_refcount_lifecycle_donate_share_evict_release():
    """The full page lifecycle without an engine: donate -> match ->
    share -> evict-under-pressure (pinned chains survive) -> release ->
    drain-to-empty.  No leak, no double free."""
    pool = PagePool(num_pages=6, page_size=4)
    cache = PrefixCache(pool)
    toks = list(range(12))                       # 3 full pages
    donor = pool.allocate(3)
    assert cache.insert(toks, donor) == []       # cache takes ownership
    assert cache.cached_pages == 3 and pool.pages_in_use == 3

    full, pnode, plen = cache.match(toks, limit=11)
    assert [n.page for n in full] == donor[:2]   # limit caps at 2 pages
    assert pnode is not None and plen == 3       # partial tail 8..10
    shared = cache.acquire(full)
    pool.share(shared)                           # the slot's hold
    assert all(pool.ref_count(p) == 2 for p in shared)

    # pressure: only the unpinned leaf (donor[2]) is evictable; the
    # shared chain and its interior nodes survive any demand
    assert cache.evict(100) == 1
    assert cache.cached_pages == 2 and pool.pages_in_use == 2
    assert cache.evict(100) == 0                 # everything pinned

    pool.free(shared)                            # slot releases its hold
    assert all(pool.ref_count(p) == 1 for p in shared)
    assert cache.reclaimable_pages() == 2
    assert cache.evict(100) == 2                 # now fully reclaimable
    assert cache.cached_pages == 0 and pool.pages_in_use == 0
    assert pool.total_allocs == pool.total_frees == 3

    # reclaimable_pages is EXACT, not optimistic: sharing only the LEAF
    # of a chain pins the whole ancestor chain (parents can only leave
    # after their children), so nothing is drainable
    donor2 = pool.allocate(3)
    assert cache.insert(toks, donor2) == []
    pool.share([donor2[2]])                      # live hold on the leaf
    assert cache.reclaimable_pages() == 0
    assert cache.evict(100) == 0
    pool.free([donor2[2]])
    assert cache.reclaimable_pages() == 3
    assert cache.evict(100) == 3
    assert pool.pages_in_use == 0


def test_radix_semantics_exact_match_dedup_and_cap():
    """Coherence invariant: chains are keyed by exact token IDs — one
    flipped token is a miss for that page and everything under it.
    Duplicate donations keep the incumbent page; the max_pages cap
    bounds retention."""
    pool = PagePool(num_pages=8, page_size=4)
    cache = PrefixCache(pool, max_pages=2)
    toks = list(range(12))                       # 3 full pages
    donor = pool.allocate(3)
    leftover = cache.insert(toks, donor)
    assert leftover == [donor[2]], \
        "the retention cap declines the 3rd page (its chain is pinned)"
    pool.free(leftover)
    assert cache.cached_pages == 2

    wrong = list(toks)
    wrong[5] += 1                                # flip inside page 2
    full, pnode, plen = cache.match(wrong, limit=12)
    assert [n.page for n in full] == [donor[0]]  # page 1 still exact
    assert pnode is not None and plen == 1       # toks[4] matches, [5] not

    exact, pnode2, plen2 = cache.match(toks, limit=12)
    assert [n.page for n in exact] == donor[:2]
    assert pnode2 is None and plen2 == 0         # nothing cached past p2

    # duplicate chain: incumbents win, the donor's copies come back
    dup = pool.allocate(2)
    assert cache.insert(toks[:8], dup) == dup
    pool.free(dup)
    assert cache.cached_pages == 2

    assert cache.evict(100) == 2
    assert pool.pages_in_use == 0


# -------------------------------------------------- the serving oracle


@pytest.fixture(scope="module")
def hit_mix(engine):
    """Shared across the horizon params: the hit-mix prompt set and its
    per-request generate() oracle (computed ONCE — generate() prefill
    compiles per distinct length, and the streams are deterministic)."""
    rng = np.random.default_rng(7)
    base = rng.integers(0, 256, 43).astype(np.int32)
    a = base                                  # donor: 2 full pages + 11
    b = base.copy()                           # full hit incl. COW tail
    c = base[:33].copy()                      # pure full-page hit (32)
    d = rng.integers(0, 256, 43).astype(np.int32)   # miss
    prompts, max_new = [a, b, c, d], [6, 5, 4, 3]
    return prompts, max_new, _oracle(engine, prompts, max_new)


@pytest.mark.parametrize("horizon", [1, 8])
def test_cache_hits_token_exact_vs_generate(engine, hit_mix, horizon):
    """Full-page hit, partial-page (COW) hit and miss — served in ONE
    mixed batch with the cache warm — emit exactly the per-request
    generate() greedy tokens, and exactly what a cache-off scheduler
    emits.  Parametrized over decode_horizon_steps in {1, 8} with
    overlap on."""
    prompts, max_new, want = hit_mix
    a, b, c, d = prompts

    # audit_every=1: the PR-11 refcount invariant auditor sweeps every
    # barrier step of this oracle — donate/share/COW/evict must stay
    # leak- and double-free-clean, not just token-exact
    sched = ServingScheduler(engine, decode_horizon_steps=horizon,
                             prefix_cache=True, audit_every=1, **CFG)
    ra = sched.submit(a, max_new_tokens=max_new[0])
    got1 = sched.run()
    assert got1[ra.rid] == want[0] and ra.cached_prefix_tokens == 0
    assert sched.prefix_cache.cached_pages > 0, "donation must land"

    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip([b, c, d], max_new[1:])]
    got2 = sched.run()
    for r, w in zip(reqs, want[1:]):
        assert got2[r.rid] == w, f"H={horizon} diverged for rid={r.rid}"
    # B: 2 shared pages + 10-token COW tail (limit 42); C: exactly the
    # 2 full pages, no COW (limit 32); D: miss
    assert reqs[0].cached_prefix_tokens == 42
    assert reqs[1].cached_prefix_tokens == 32
    assert reqs[2].cached_prefix_tokens == 0
    assert sched.prefix_cache.cow_copies >= 1, "COW path must engage"

    off = ServingScheduler(engine, decode_horizon_steps=horizon,
                           prefix_cache=False, **CFG)
    roff = [off.submit(p, max_new_tokens=m)
            for p, m in zip([b, c, d], max_new[1:])]
    gotoff = off.run()
    for r_on, r_off in zip(reqs, roff):
        assert got2[r_on.rid] == gotoff[r_off.rid], \
            "cache on/off must be indistinguishable in output"
    assert off.kv.pool.pages_in_use == 0

    # cached pages are retained capacity, not a leak: a full drain
    # returns the pool to empty
    sched.prefix_cache.evict(10 ** 6)
    assert sched.kv.pool.pages_in_use == 0


def test_eviction_under_pressure_token_exact(engine):
    """A warm cache + a hostage allocation squeeze the pool: admissions
    and growth must DRAIN cached pages (LRU) instead of preempting live
    requests, and output stays token-exact."""
    class Sink:
        def __init__(self):
            self.events = []

        def write_events(self, event_list):
            self.events.extend(event_list)

    rng = np.random.default_rng(11)
    warm = [rng.integers(0, 256, 43).astype(np.int32) for _ in range(2)]
    fresh = [rng.integers(0, 256, 33).astype(np.int32) for _ in range(2)]
    want = _oracle(engine, fresh, [4, 4])

    sink = Sink()
    sched = ServingScheduler(engine, prefix_cache=True, monitor=sink,
                             **CFG)
    for p in warm:
        sched.submit(p, max_new_tokens=4)
    sched.run()
    cached0 = sched.prefix_cache.cached_pages
    assert cached0 > 0
    free = sched.kv.pool.free_pages
    hostage = sched.kv.pool.allocate(free - 2)   # 2 free pages left
    reqs = [sched.submit(p, max_new_tokens=4) for p in fresh]
    got = sched.run()
    for r, w in zip(reqs, want):
        assert got[r.rid] == w
    assert sched.metrics.cache_evictions > 0, \
        "pool pressure must reclaim cached pages"
    assert sched.metrics.preemptions == 0 and sched.metrics.shed == 0, \
        "cached pages must drain before any live request suffers"
    tags = {t for t, _, _ in sink.events}
    assert {"serving/prefix_cache/cached_pages",
            "serving/prefix_cache/cached_prefix_tokens",
            "serving/prefix_cache/hit_rate",
            "serving/prefix_cache/evicted_pages"} <= tags, \
        "prefix-cache observability must flow through monitor/"
    s = sched.summary()
    assert s["cache_evictions"] == sched.metrics.cache_evictions
    assert "prefix_hit_rate" in s and "prefill_tokens_saved" in s
    sched.kv.pool.free(hostage)
    sched.prefix_cache.evict(10 ** 6)
    assert sched.kv.pool.pages_in_use == 0


def test_donation_after_preemption_keys_exact(engine):
    """Coherence across recompute preemption: a preempted request's
    prompt has its then-emitted tokens folded in, so donation MUST key
    on orig_prompt + out_tokens (keying on req.prompt would duplicate
    the folded segment and cache pages under keys their KV does not
    hold).  Every cached chain must spell a prefix of some finished
    request's true token sequence, and re-serving the donor's prompt
    against the donated chain stays token-exact."""
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, 256, 43).astype(np.int32) for _ in range(2)]
    want = _oracle(engine, prompts, [10, 10])

    sched = ServingScheduler(engine, prefix_cache=True, **CFG)
    # hostage allocation: 7 pages left for 2 requests wanting 8 — forces
    # preemption without changing pool SHAPES (jit signatures stay
    # shared with the rest of the module, like test_serving_horizon's
    # forced-eviction test)
    hostage = sched.kv.pool.allocate(CFG["num_pages"] - 7)
    reqs = [sched.submit(p, max_new_tokens=10) for p in prompts]
    got = sched.run()
    assert sched.metrics.preemptions > 0, \
        "pool was sized to force preemption; none happened"
    for r, w in zip(reqs, want):
        assert got[r.rid] == w

    seqs = [[int(t) for t in p] + w for p, w in zip(prompts, want)]

    def walk(node, path):
        for key, child in node.children.items():
            chain = path + list(key)
            assert any(chain == s[:len(chain)] for s in seqs), \
                f"cached chain {chain[:8]}... keys tokens no request produced"
            walk(child, chain)

    walk(sched.prefix_cache._root, [])

    r2 = sched.submit(prompts[0], max_new_tokens=10)
    got2 = sched.run()
    assert got2[r2.rid] == want[0]
    assert r2.cached_prefix_tokens > 0, "the donated chain must be hit"
    sched.kv.pool.free(hostage)
    sched.prefix_cache.evict(10 ** 6)
    assert sched.kv.pool.pages_in_use == 0


def test_injected_exhaustion_drains_warm_cache_first(engine):
    """Fault-injected pool exhaustion (serve.page_alloc) with a WARM
    cache: the episode reclaims cached pages instead of shedding — all
    requests finish token-exact, zero preemptions/sheds — and the
    cache-eviction counter shows the drain."""
    rng = np.random.default_rng(13)
    donor = rng.integers(0, 256, 43).astype(np.int32)
    victims = [rng.integers(0, 256, 33).astype(np.int32) for _ in range(2)]
    want = _oracle(engine, victims, [4, 4])

    # horizon 1 + overlap off: the step-keyed PR-2 plan convention
    # (docs/resilience.md) keeps the injection timing deterministic
    sched = ServingScheduler(engine, decode_horizon_steps=1, overlap=False,
                             prefix_cache=True, **CFG)
    sched.submit(donor, max_new_tokens=4)
    sched.run()
    assert sched.prefix_cache.cached_pages > 0

    inj = faults.FaultInjector(seed=0)
    inj.on("serve.page_alloc", nth=1,
           exc=PagePoolExhausted("injected exhaustion episode"))
    reqs = [sched.submit(p, max_new_tokens=4) for p in victims]
    with faults.injected(inj):
        got = sched.run()
    for r, w in zip(reqs, want):
        assert r.state == "finished"
        assert got[r.rid] == w
    assert sched.metrics.cache_evictions > 0, \
        "the injected episode must drain the cache"
    assert sched.metrics.preemptions == 0 and sched.metrics.shed == 0
    sched.prefix_cache.evict(10 ** 6)
    assert sched.kv.pool.pages_in_use == 0


def test_compile_counts_unchanged_across_cache_churn(engine):
    """Cache hits, COW copies, misses, donation and eviction never add
    jit signatures: fused decode stays <= the horizon bucket set,
    prefill stays <= the row bucket set, and the COW page copy is
    ONE more (fixed) signature — for this module's single serving
    config, covering every earlier full session here."""
    sched = ServingScheduler(engine, prefix_cache=True, **CFG)
    rng = np.random.default_rng(3)
    base = rng.integers(0, 256, 43).astype(np.int32)
    for n, m in [(43, 4), (43, 6), (33, 3), (43, 5)]:
        p = base[:n].copy() if rng.integers(2) else \
            rng.integers(0, 256, n).astype(np.int32)
        sched.submit(p, max_new_tokens=m)
    sched.run()
    assert 1 <= engine.serving_decode_multi_compile_count() <= \
        len(sched.horizon_buckets)
    assert 1 <= engine.serving_prefill_compile_count() <= \
        len(sched.prefill_row_buckets)
    assert engine.serving_page_copy_compile_count() <= 1
    sched.prefix_cache.evict(10 ** 6)
    assert sched.kv.pool.pages_in_use == 0
