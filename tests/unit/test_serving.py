"""Continuous-batching serving layer (deepspeed_tpu/serving): page-pool
invariants, the scheduler oracle (token-exact vs per-request generate()),
backpressure/eviction edge cases, and the single-jit-signature guarantee."""

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.models.llama import Llama, llama_tiny
from deepspeed_tpu.serving import (PagedKVManager, PagePool,
                                   PagePoolExhausted, QueueFull,
                                   ServingScheduler)

# ----------------------------------------------------------- page manager


def test_page_pool_alloc_free_invariants():
    pool = PagePool(num_pages=8, page_size=16)
    assert pool.free_pages == 8 and pool.pages_in_use == 0
    a = pool.allocate(3)
    b = pool.allocate(2)
    assert len(set(a) | set(b)) == 5, "pages double-allocated"
    assert pool.pages_in_use == 5 and pool.peak_in_use == 5
    pool.free(a)
    assert pool.free_pages == 6
    c = pool.allocate(6)
    assert pool.pages_in_use == 8 and pool.free_pages == 0
    assert not pool.can_allocate(1)
    with pytest.raises(PagePoolExhausted):
        pool.allocate(1)
    pool.free(b + c)
    assert pool.pages_in_use == 0 and pool.peak_in_use == 8
    assert pool.total_allocs == 11 and pool.total_frees == 11
    with pytest.raises(ValueError):   # double free
        pool.free([a[0]])


def test_page_pool_token_math():
    pool = PagePool(num_pages=4, page_size=16)
    assert pool.pages_for_tokens(1) == 1
    assert pool.pages_for_tokens(16) == 1
    assert pool.pages_for_tokens(17) == 2
    assert pool.pages_for_tokens(64) == 4


def test_kv_manager_growth_release_and_fragmentation():
    kv = PagedKVManager(num_pages=6, page_size=4, num_slots=3,
                        max_pages_per_slot=4)
    assert kv.ensure_capacity(0, 5)          # 2 pages
    assert kv.ensure_capacity(1, 9)          # 3 pages
    assert kv.slot_page_count(0) == 2 and kv.slot_page_count(1) == 3
    # the device table rows hold the allocated ids, zero-padded
    assert set(kv.table[0][:2]) == set(kv._slot_pages[0])
    assert (kv.table[0][2:] == 0).all()
    # growing within already-held pages is free
    assert kv.ensure_capacity(0, 8)
    assert kv.pool.pages_in_use == 5
    # pool has 1 page left: slot 2 wanting 2 pages must fail SOFTLY
    assert not kv.ensure_capacity(2, 8)
    assert kv.slot_page_count(2) == 0, "partial allocation leaked"
    # over the per-slot table is a config error, not a transient
    with pytest.raises(ValueError):
        kv.ensure_capacity(0, 17)
    # release recycles everything; no external fragmentation by design
    kv.release_slot(0)
    kv.release_slot(1)
    assert kv.pool.pages_in_use == 0
    assert kv.ensure_capacity(2, 6 * 4 - 16)  # now fits


# ------------------------------------------------------- serving fixtures


@pytest.fixture(scope="module")
def gpt2_engine():
    model = GPT2(gpt2_tiny())
    engine = deepspeed_tpu.init_inference(
        model=model, dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1})
    engine.init_params()
    return engine


@pytest.fixture(scope="module")
def llama_engine():
    model = Llama(llama_tiny(num_layers=2))
    engine = deepspeed_tpu.init_inference(
        model=model, dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1})
    engine.init_params()
    return engine


def _oracle(engine, prompts, max_new):
    return [
        [int(t) for t in
         engine.generate(p[None], max_new_tokens=m, do_sample=False)[
             0, len(p):]]
        for p, m in zip(prompts, max_new)]


# ------------------------------------------------------------ the oracle


def test_continuous_batching_token_exact_oracle(gpt2_engine):
    """Mixed-length prompts through the serving path emit EXACTLY the
    per-request generate() greedy tokens — across chunked prefill,
    slot churn, and queueing (more requests than slots)."""
    rng = np.random.default_rng(0)
    # 3 DISTINCT lengths across 6 requests: mixed-length + queueing
    # coverage while the per-request generate() oracle compiles only 3
    # prefill shapes (tier-1 time budget)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 11, 5, 20, 11, 5)]
    max_new = [8, 6, 10, 4, 12, 5]
    want = _oracle(gpt2_engine, prompts, max_new)

    sched = ServingScheduler(gpt2_engine, num_slots=3, num_pages=16,
                             page_size=16, max_pages_per_slot=8,
                             prefill_chunk=8)
    streamed = {}
    reqs = [sched.submit(p, max_new_tokens=m,
                         on_token=lambda r, t: streamed.setdefault(
                             r.rid, []).append(t))
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    for r, w in zip(reqs, want):
        assert got[r.rid] == w
        assert streamed[r.rid] == w, "streaming callbacks diverged"
    # every page returned to the pool after the run
    assert sched.kv.pool.pages_in_use == 0


def test_continuous_batching_oracle_with_eviction_gqa(llama_engine):
    """GQA (llama) serving stays token-exact even when a 4-page pool
    forces preemption/recompute mid-flight."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (12, 7, 12)]
    max_new = [10, 12, 8]
    want = _oracle(llama_engine, prompts, max_new)

    sched = ServingScheduler(llama_engine, num_slots=3, num_pages=4,
                             page_size=8, max_pages_per_slot=4,
                             prefill_chunk=8)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    assert sched.metrics.preemptions > 0, \
        "pool was sized to force eviction; none happened"
    for r, w in zip(reqs, want):
        assert got[r.rid] == w
    assert sched.kv.pool.pages_in_use == 0


def test_serving_metrics_flow_through_monitor(gpt2_engine):
    """TTFT / token latency / queue gauges emit as (tag, value, step)
    events through the monitor/ write_events contract."""
    class Sink:
        def __init__(self):
            self.events = []

        def write_events(self, event_list):
            self.events.extend(event_list)

    sink = Sink()
    sched = ServingScheduler(gpt2_engine, num_slots=3, num_pages=16,
                             page_size=16, max_pages_per_slot=8,
                             prefill_chunk=8, monitor=sink)
    sched.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    sched.run()
    tags = {t for t, _, _ in sink.events}
    assert {"serving/queue_depth", "serving/running", "serving/waiting",
            "serving/page_utilization", "serving/ttft_ms"} <= tags
    assert "serving/token_latency_ms" in tags
    for _, value, step in sink.events:
        assert np.isfinite(value) and step >= 1
    s = sched.summary()
    assert s["completed"] == 1 and s["tokens_emitted"] == 3
    assert 0.0 < s["page_util_peak"] <= 1.0


def test_serving_eos_stops_stream(gpt2_engine):
    # length 5 on purpose: shares the oracle test's compiled prefill shape
    prompt = np.zeros(5, np.int32)
    first = gpt2_engine.generate(prompt[None], max_new_tokens=1,
                                 do_sample=False)
    eos = int(first[0, -1])   # greedy immediately emits eos -> length 1
    sched = ServingScheduler(gpt2_engine, num_slots=3, num_pages=16,
                             page_size=16, max_pages_per_slot=8,
                             prefill_chunk=8)
    req = sched.submit(prompt, max_new_tokens=16, eos_token_id=eos)
    got = sched.run()
    assert got[req.rid] == [eos]


# ---------------------------------------------- backpressure + edge cases


def test_submit_backpressure_and_oversize_rejection(gpt2_engine):
    sched = ServingScheduler(gpt2_engine, num_slots=1, num_pages=4,
                             page_size=8, max_pages_per_slot=4,
                             prefill_chunk=8, max_queue=2)
    with pytest.raises(ValueError, match="per-slot capacity"):
        sched.submit(np.zeros(40, np.int32), max_new_tokens=8)
    sched.submit(np.zeros(4, np.int32), max_new_tokens=2)
    sched.submit(np.zeros(4, np.int32), max_new_tokens=2)
    with pytest.raises(QueueFull):
        sched.submit(np.zeros(4, np.int32), max_new_tokens=2)


def test_queue_full_backpressure_round_trip(gpt2_engine):
    """The 429-then-retry cycle: QueueFull at max_queue, the loop drains
    the queue, and the SAME submission succeeds afterwards — the
    backpressure signal is transient, not a terminal rejection."""
    sched = ServingScheduler(gpt2_engine, num_slots=3, num_pages=16,
                             page_size=16, max_pages_per_slot=8,
                             prefill_chunk=8, max_queue=2)
    prompt = np.zeros(5, np.int32)
    r1 = sched.submit(prompt, max_new_tokens=2)
    r2 = sched.submit(prompt, max_new_tokens=2)
    with pytest.raises(QueueFull):
        sched.submit(prompt, max_new_tokens=2)
    # drain: admission frees queue space on the very first step
    sched.step()
    r3 = sched.submit(prompt, max_new_tokens=2)   # retry now succeeds
    got = sched.run()
    assert set(got) == {r1.rid, r2.rid, r3.rid}
    assert all(len(t) == 2 for t in got.values())
    assert sched.kv.pool.pages_in_use == 0


def test_page_pool_exhausted_dead_end():
    """_grow_or_evict's dead-end: the pool is exhausted, the growing
    slot holds no request, and there is no evictable victim — the
    PagePoolExhausted raise (not a silent False) is the contract the
    step loop's shed-on-capacity containment is built on. Pure host
    logic: no engine needed."""
    kv = PagedKVManager(num_pages=4, page_size=8, num_slots=2,
                        max_pages_per_slot=4)
    sched = ServingScheduler.__new__(ServingScheduler)
    sched.kv = kv
    sched.num_slots = 2
    sched.slot_req = [None, None]
    sched.lengths = np.zeros(2, np.int32)
    sched.waiting = deque()
    sched._pf_flight = deque()    # no prefill dispatch in flight
    sched._inflight = deque()     # and no horizon
    sched.step_idx = 0
    sched.prefix_cache = None     # nothing cached -> nothing reclaimable
    from deepspeed_tpu.serving.mem_telemetry import NULL_MEM
    sched.mem = NULL_MEM          # telemetry off, like the constructor
    kv.pool.allocate(4)          # a foreign reservation drains the pool
    with pytest.raises(PagePoolExhausted, match="no evictable request"):
        sched._grow_or_evict(1, 8)
    assert kv.slot_page_count(1) == 0, "dead-end leaked pages"


def test_cancel_releases_pages_at_step_boundary(gpt2_engine):
    """req.cancel() mid-flight: the request leaves at the next step
    boundary with its pages recycled; the others are token-exact."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(2)]
    want = _oracle(gpt2_engine, prompts, [8, 8])
    sched = ServingScheduler(gpt2_engine, num_slots=3, num_pages=16,
                             page_size=16, max_pages_per_slot=8,
                             prefill_chunk=8)
    keep = sched.submit(prompts[0], max_new_tokens=8)
    victim = sched.submit(prompts[1], max_new_tokens=8)
    sched.step()                  # both admitted + prefilled
    assert victim.state in ("prefill", "running")
    victim.cancel()
    got = sched.run()
    assert victim.state == "cancelled" and victim.rid not in got
    assert got[keep.rid] == want[0]
    assert sched.kv.pool.pages_in_use == 0, "cancel leaked pages"
    assert sched.metrics.cancelled == 1
    assert sched.health()["cancelled"] == 1


def test_deadline_shedding_is_distinct_from_errors(gpt2_engine):
    """An already-expired deadline sheds in the queue; an infeasible
    deadline sheds at admission (EMA-based estimate); both are counted
    as shed — never failed, never finished-with-partial-tokens."""
    sched = ServingScheduler(gpt2_engine, num_slots=3, num_pages=16,
                             page_size=16, max_pages_per_slot=8,
                             prefill_chunk=8)
    ok = sched.submit(np.zeros(5, np.int32), max_new_tokens=3)
    expired = sched.submit(np.zeros(5, np.int32), max_new_tokens=3,
                           deadline_s=0.0)
    got = sched.run()
    assert expired.state == "shed" and "deadline" in expired.error
    assert expired.rid not in got and len(got[ok.rid]) == 3
    # infeasible-at-admission: the EMA from the run above prices a step;
    # a deadline far below (#steps x EMA) cannot be met
    assert sched._ema_step_s is not None
    hopeless = sched.submit(np.zeros(5, np.int32), max_new_tokens=64,
                            deadline_s=sched._ema_step_s * 0.5)
    sched.run()
    # shed either at admission (infeasible estimate) or by the queue
    # sweep if the deadline already lapsed — never failed, never served
    assert hopeless.state == "shed"
    assert "deadline" in hopeless.error or "infeasible" in hopeless.error
    assert sched.metrics.shed == 2 and sched.metrics.failed == 0


def test_completed_history_is_bounded(gpt2_engine):
    """The memory-leak fix: finished requests drain from the live map
    into a bounded deque instead of accumulating forever."""
    sched = ServingScheduler(gpt2_engine, num_slots=3, num_pages=16,
                             page_size=16, max_pages_per_slot=8,
                             prefill_chunk=8, completed_history=4)
    for _ in range(6):
        sched.submit(np.zeros(5, np.int32), max_new_tokens=1)
    sched.run()
    assert len(sched.requests) == 0, "live map must drain on retire"
    assert len(sched.completed) == 4, "history must stay bounded"
    assert sched.metrics.completed == 6


def test_single_jit_signature_across_churn(gpt2_engine):
    """The no-per-step-recompilation guarantee: at most one prefill
    compile PER ROW BUCKET (the step's prefilling slots ride one
    dispatch, padded to a power-of-four row count) and
    at most one fused-decode compile PER HORIZON BUCKET regardless of
    request churn, lengths, joins and retirements. The scheduler here
    uses the SAME (slots, pages, page_size, chunk) constants as every
    other gpt2 serving test in this module, so the count also covers the
    earlier full serving sessions — only a different scheduler CONFIG is
    a new signature, by design."""
    rng = np.random.default_rng(2)
    sched = ServingScheduler(gpt2_engine, num_slots=3, num_pages=16,
                             page_size=16, max_pages_per_slot=8,
                             prefill_chunk=8)
    for n, m in [(3, 4), (17, 9), (9, 2), (25, 7), (2, 11), (13, 3)]:
        sched.submit(rng.integers(0, 256, n).astype(np.int32),
                     max_new_tokens=m)
    sched.run()
    assert 1 <= gpt2_engine.serving_decode_multi_compile_count() <= \
        len(sched.horizon_buckets)
    assert sched.prefill_row_buckets == [1, 3]
    assert 1 <= gpt2_engine.serving_prefill_compile_count() <= \
        len(sched.prefill_row_buckets)


# ------------------------------------------------------ paged attention


def test_paged_kernel_matches_gather_fallback():
    """The scalar-prefetch Pallas kernel (interpret mode off-TPU) agrees
    with the gather-then-decode_attention fallback, GQA included."""
    from deepspeed_tpu.ops.attention.decode import paged_decode_attention
    rng = np.random.default_rng(0)
    slots, h, kv_h, d, ps, maxp, num_pages = 3, 4, 2, 16, 8, 4, 10
    q = jnp.asarray(rng.normal(size=(slots, 1, h, d)).astype(np.float32))
    kp = jnp.asarray(rng.normal(
        size=(num_pages, ps, kv_h, d)).astype(np.float32))
    vp = jnp.asarray(rng.normal(
        size=(num_pages, ps, kv_h, d)).astype(np.float32))
    pt = jnp.asarray(rng.integers(0, num_pages, (slots, maxp)).astype(
        np.int32))
    pos = jnp.asarray(np.array([5, 17, 30], np.int32))
    ref = paged_decode_attention(q, kp, vp, pt, pos)
    ker = paged_decode_attention(q, kp, vp, pt, pos, force_kernel=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=2e-6)


@pytest.mark.slow
def test_serving_bench_loadgen_smoke(tmp_path):
    """End-to-end Poisson load-gen bench (slow: compiles generate() at
    several static-batch shapes). Asserts the bench runs and reports
    both systems."""
    import json
    import subprocess
    import sys
    out = tmp_path / "serving.json"
    subprocess.run(
        [sys.executable, "benchmarks/serving_bench.py", "--requests", "8",
         "--rate", "50", "--json-out", str(out)],
        check=True, timeout=900)
    res = json.loads(out.read_text())
    assert res["continuous"]["tokens"] == res["static"]["tokens"]
    assert res["continuous"]["tokens_per_sec"] > 0
