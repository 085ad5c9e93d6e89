"""Fused multi-step paged decode (`InferenceEngine.decode_multi`) and
the overlapped horizon scheduler loop: the oracle (token-exact vs the
single-step path / per-request generate()) across horizon buckets,
mid-horizon EOS freezing, forced eviction between horizons, cancellation
landing mid-horizon, and the bounded-compile-count guarantee.

Every scheduler in this module uses the SAME (slots, pages, page_size,
max_pages, chunk) constants, so fused-decode jit signatures differ only
by horizon bucket — the compile-count test's bound covers the whole
module by design (same scheme as test_serving.py)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.serving import ServingScheduler

CFG = dict(num_slots=3, num_pages=16, page_size=16, max_pages_per_slot=8,
           prefill_chunk=8)


@pytest.fixture(scope="module")
def engine():
    model = GPT2(gpt2_tiny())
    eng = deepspeed_tpu.init_inference(
        model=model, dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1})
    eng.init_params()
    return eng


def _oracle(engine, prompts, max_new, eos=None):
    """Greedy per-request generate() streams, truncated at the first
    eos occurrence inclusive (generate() pads past eos with fill, the
    serving loop stops AT it — truncation makes the two comparable)."""
    out = []
    for p, m in zip(prompts, max_new):
        toks = [int(t) for t in engine.generate(
            p[None], max_new_tokens=m, do_sample=False)[0, len(p):]]
        if eos is not None and eos in toks:
            toks = toks[:toks.index(eos) + 1]
        out.append(toks)
    return out


# ------------------------------------------------------------- the oracle


@pytest.mark.parametrize("horizon", [1, 4, 8])
def test_horizon_oracle_token_exact_with_mid_horizon_eos(engine, horizon):
    """Serving output is token-exact vs per-request generate() for H in
    {1, 4, bucket-max}, including an EOS that lands MID-horizon (the
    device must freeze the slot on the spot: later scan steps of that
    slot write nothing and emit valid=False rows) and a max_new budget
    that expires mid-horizon."""
    rng = np.random.default_rng(4)
    # this seed's SECOND draw (length 9) greedily emits [205, 205, 205,
    # x, x, ...] with a token change at stream index 3 = step 2 of the
    # first H=4 decode horizon — strictly inside a fused scan. The eos
    # is picked from the measured stream (not hardcoded) because the
    # exact post-switch token sits on an argmax tie that numeric-config
    # differences can flip.
    p_other = rng.integers(0, 256, 5).astype(np.int32)
    p_mid = rng.integers(0, 256, 9).astype(np.int32)
    rng2 = np.random.default_rng(0)
    prompts = [p_mid,
               p_other,
               rng2.integers(0, 256, 9).astype(np.int32),
               rng2.integers(0, 256, 5).astype(np.int32)]
    # 6 expires mid-horizon for H=4 (prefill token + 4 + 1); 12 spans
    # several horizons; 10/3 cover churn
    max_new = [12, 6, 10, 3]
    base = _oracle(engine, prompts, max_new)
    eos = base[0][3]
    k = base[0].index(eos)
    assert 2 <= k <= max_new[0] - 2, \
        f"probe drifted: eos lands at {k}, not mid-horizon"
    want = _oracle(engine, prompts, max_new, eos=eos)
    assert want[0] == base[0][:k + 1]

    # audit_every=1: the PR-11 refcount auditor rides the whole oracle
    sched = ServingScheduler(engine, decode_horizon_steps=horizon,
                             audit_every=1, **CFG)
    streamed = {}
    reqs = [sched.submit(p, max_new_tokens=m, eos_token_id=eos,
                         on_token=lambda r, t: streamed.setdefault(
                             r.rid, []).append(t))
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    for r, w in zip(reqs, want):
        assert got[r.rid] == w, f"H={horizon} diverged for rid={r.rid}"
        assert streamed[r.rid] == w, "streaming callbacks diverged"
    assert sched.kv.pool.pages_in_use == 0
    assert all(h in sched.horizon_buckets for h in sched.metrics.horizons)


def test_forced_eviction_between_horizons(engine):
    """Recompute preemption still round-trips token-exact when pool
    pressure strikes BETWEEN horizons: the pre-reservation first shrinks
    the horizon bucket-by-bucket, then falls back to the legacy
    evict/requeue policy at H=1. A foreign allocation shrinks the free
    list without changing pool shapes (jit signatures stay shared with
    the rest of the module)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 9, 5)]
    max_new = [60, 60, 60]
    want = _oracle(engine, prompts, max_new)

    sched = ServingScheduler(engine, decode_horizon_steps=8, **CFG)
    hostage = sched.kv.pool.allocate(6)   # 10 pages left for 15 needed
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    assert sched.metrics.preemptions > 0, \
        "pool was sized to force eviction; none happened"
    for r, w in zip(reqs, want):
        assert got[r.rid] == w
    assert sched.kv.pool.pages_in_use == 6, "only the hostage pages remain"
    sched.kv.pool.free(hostage)


def test_cancel_mid_horizon_honored_at_next_boundary(engine):
    """req.cancel() while a fused horizon is IN FLIGHT: the tokens that
    horizon generated past the cancel are dropped at the harvest
    boundary, pages recycle, and the surviving request stays
    token-exact."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(2)]
    want = _oracle(engine, prompts, [10, 10])

    sched = ServingScheduler(engine, decode_horizon_steps=4, overlap=True,
                             **CFG)
    keep = sched.submit(prompts[0], max_new_tokens=10)
    victim = sched.submit(prompts[1], max_new_tokens=10)
    sched.step()     # admit + prefill + first token + horizon dispatched
    assert sched._inflight, "overlap must leave the horizon in flight"
    assert len(victim.out_tokens) == 1   # the prefill-boundary token
    victim.cancel()
    got = sched.run()
    assert victim.state == "cancelled" and victim.rid not in got
    assert len(victim.out_tokens) == 1, \
        "tokens generated mid-horizon after cancel must be dropped"
    assert got[keep.rid] == want[0]
    assert sched.kv.pool.pages_in_use == 0, "cancel leaked pages"
    assert sched.metrics.cancelled == 1


def test_decode_compile_count_bounded_by_horizon_buckets(engine):
    """Slot churn, mixed lengths, joins and retirements never add jit
    signatures: fused-decode compiles stay <= the horizon bucket set
    (for this module's single serving config), prefill compiles stay
    <= the row bucket set."""
    rng = np.random.default_rng(2)
    sched = ServingScheduler(engine, decode_horizon_steps=8, **CFG)
    for n, m in [(5, 4), (9, 9), (5, 2), (9, 7), (5, 11), (9, 3)]:
        sched.submit(rng.integers(0, 256, n).astype(np.int32),
                     max_new_tokens=m)
    sched.run()
    assert sched.horizon_buckets == [1, 2, 4, 8]
    assert 1 <= engine.serving_decode_multi_compile_count() <= \
        len(sched.horizon_buckets)
    assert 1 <= engine.serving_prefill_compile_count() <= \
        len(sched.prefill_row_buckets)
    # the fused path IS the decode path: the single-step primitive never
    # compiles in serving anymore
    assert engine.serving_decode_compile_count() == 0


# ------------------------------------------------- host-input staging


def test_staged_host_inputs_survive_mutation_after_dispatch(engine):
    """``device_put`` may alias a numpy buffer (zero-copy on CPU) or
    read it asynchronously (TPU), and the overlapped scheduler loop
    mutates its live ``lengths`` / page table / ``last_tok`` right after
    a dispatch returns.  The staging point must therefore hand the
    device its OWN copy: scribbling over every host array immediately
    after ``prefill_into_slots`` / ``decode_multi`` return — before
    anything blocks on the result — must not change the result."""
    slots, pages, ps, maxp, chunk = 3, 16, 16, 8, 8
    prompt = [3, 1, 4, 1, 5]

    def aligned(a):
        """A 64-byte-aligned copy: the alignment at which jaxlib's CPU
        client takes a numpy buffer zero-copy (numpy itself only
        guarantees 16, so an unaligned test would pass by luck)."""
        a = np.asarray(a)
        raw = np.zeros(a.nbytes + 64, np.uint8)
        off = -raw.ctypes.data % 64
        out = raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
        out[...] = a
        return out

    def run(scribble):
        pools = engine.init_paged_cache(pages, ps)
        table = np.zeros((slots, maxp), np.int32)
        table[:, 0] = [1, 2, 3]
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :len(prompt)] = prompt
        host = dict(ids=aligned(ids), table=aligned(table),
                    lengths=aligned(np.zeros(slots, np.int32)))
        logits, pools = engine.prefill_into_slots(
            host["ids"], 0, len(prompt), host["table"], host["lengths"],
            pools)
        if scribble:
            for a in host.values():
                a[...] = 7
        first = int(np.argmax(np.asarray(logits)))

        host = dict(toks=aligned(np.array([first, 0, 0], np.int32)),
                    active=aligned(np.array([True, False, False])),
                    table=aligned(table),
                    lengths=aligned(np.array([len(prompt), 0, 0],
                                             np.int32)),
                    budgets=aligned(np.array([6, 0, 0], np.int32)),
                    eos=aligned(np.full(slots, -1, np.int32)))
        out = engine.decode_multi(
            host["toks"], host["active"], host["table"], host["lengths"],
            pools, horizon=4, budgets=host["budgets"],
            eos_ids=host["eos"])
        if scribble:
            for a in host.values():
                a[...] = 1
        toks_block, valid = np.asarray(out[0]), np.asarray(out[1])
        return first, toks_block[valid].tolist(), np.asarray(out[4]).tolist()

    assert run(scribble=True) == run(scribble=False)
