"""Batched chunked prefill: every prefilling slot's next chunk rides ONE
``[rows, prefill_chunk]`` dispatch per boundary step.

* model/engine parity — a mixed batch (different start offsets incl. a
  mid-page boundary, ``n_valid < chunk``, ``n_valid == chunk``, padding
  rows) leaves the same pages and lengths and returns the same boundary
  logits as the same rows sent one at a time through the size-1 bucket;
* scheduler — exactly one prefill dispatch per boundary step whatever
  the number of prefilling slots, a slot evicted by a later row's growth
  is not in the dispatch, a per-row host failure closes that slot only,
  token streams equal ``generate()``;
* compile-set pin — prefill signatures stay within the row bucket set;
* the row bucket set — powers of four up to 16 rows, powers of two
  above: 17-32 prefilling rows ride a 32-row dispatch, and the tokens
  served do not depend on the bucket a chunk rode in.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm as dist
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.models.falcon_h1 import FalconH1, falcon_h1_tiny
from deepspeed_tpu.models.llama import Llama, llama_tiny
from deepspeed_tpu.models.nemotron_h import NemotronH, nemotron_h_tiny
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving import PagedKVManager, ServingScheduler
from deepspeed_tpu.serving.scheduler import (_bucket_ceil,
                                             _prefill_row_buckets)

PS, CHUNK, SLOTS, MAXP, PAGES = 16, 8, 4, 4, 16
# float32 compute on CPU: a row's matmuls do not depend on its batch
# peers, so the K/V bytes (and their int8 codes and scales) are exact;
# the boundary logits are held to this absolute ceiling
LOGIT_TOL = 1e-5
MODELS = {"llama-gqa": lambda: Llama(llama_tiny(num_layers=2)),
          "gpt2-mha": lambda: GPT2(gpt2_tiny())}


def _engine(model):
    engine = deepspeed_tpu.init_inference(
        model=MODELS[model](), dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1})
    engine.init_params()
    return engine


@pytest.fixture(scope="module", params=sorted(MODELS))
def engine(request):
    return _engine(request.param)


def _oracle(engine, prompts, max_new):
    return [[int(t) for t in
             engine.generate(p[None], max_new_tokens=m, do_sample=False)[
                 0, len(p):]]
            for p, m in zip(prompts, max_new)]


# ------------------------------------------------ model / engine parity


def _prefill_rows(engine, kv, lengths, pools, rows, bucket):
    """One dispatch carrying ``rows`` = [(slot, tokens)], padded to
    ``bucket`` rows; returns ({slot: boundary logits}, pools)."""
    ids = np.zeros((bucket, CHUNK), np.int32)
    slots = np.full(bucket, rows[0][0], np.int32)
    n_valid = np.zeros(bucket, np.int32)
    for i, (slot, toks) in enumerate(rows):
        ids[i, :len(toks)] = toks
        slots[i], n_valid[i] = slot, len(toks)
    logits, pools = engine.prefill_into_slots(ids, slots, n_valid, kv.table,
                                              lengths, pools)
    assert logits.shape[0] == bucket
    out = {slot: np.asarray(logits[i], np.float32)
           for i, (slot, _) in enumerate(rows)}
    for slot, toks in rows:
        lengths[slot] += len(toks)
    return out, pools


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_mixed_batch_matches_rows_sent_one_at_a_time(engine, kv_dtype):
    rng = np.random.default_rng(7)
    # history, written one row at a time in both runs: slot 0 stands at
    # 21 tokens (a mid-page boundary, as a prefix-cache hit seeds it),
    # slot 3 at one full chunk, slot 1 at 0, slot 2 stays empty
    hist = {0: rng.integers(0, 256, 21), 3: rng.integers(0, 256, CHUNK)}
    # the mixed batch: n_valid == chunk at offset 8, n_valid < chunk at
    # the mid-page offset 21, a fresh slot, plus one padding row
    batch = [(3, rng.integers(0, 256, CHUNK)), (0, rng.integers(0, 256, 3)),
             (1, rng.integers(0, 256, CHUNK))]

    def run(batched):
        pools = engine.init_paged_cache(PAGES, PS, kv_dtype=kv_dtype)
        kv = PagedKVManager(PAGES, PS, num_slots=SLOTS,
                            max_pages_per_slot=MAXP)
        lengths = np.zeros(SLOTS, np.int32)
        for slot in (0, 1, 3):
            assert kv.ensure_capacity(slot, 32)
        for slot, toks in hist.items():
            for c0 in range(0, len(toks), CHUNK):
                _, pools = _prefill_rows(engine, kv, lengths, pools,
                                         [(slot, toks[c0:c0 + CHUNK])], 1)
        if batched:
            logits, pools = _prefill_rows(engine, kv, lengths, pools,
                                          batch, 4)
        else:
            logits = {}
            for row in batch:
                lg, pools = _prefill_rows(engine, kv, lengths, pools,
                                          [row], 1)
                logits.update(lg)
        return logits, jax.tree.map(np.asarray, pools), lengths

    lg1, pools1, len1 = run(batched=False)
    lgb, poolsb, lenb = run(batched=True)
    assert list(lenb) == list(len1) == [24, CHUNK, 0, 2 * CHUNK]
    for a, b in zip(jax.tree.leaves(pools1), jax.tree.leaves(poolsb)):
        assert a.dtype == b.dtype and np.array_equal(a, b), \
            "batched prefill left different page contents"
    for slot in lg1:
        assert np.max(np.abs(lg1[slot] - lgb[slot])) < LOGIT_TOL
        assert int(lg1[slot].argmax()) == int(lgb[slot].argmax())


def test_model_advances_lengths_per_row(engine):
    """The model's own ``lengths`` carry: each row adds its n_valid to
    its slot, padding rows (n_valid 0, a borrowed slot id) add 0."""
    pools = engine.init_paged_cache(PAGES, PS)
    kv = PagedKVManager(PAGES, PS, num_slots=SLOTS, max_pages_per_slot=MAXP)
    for slot in range(SLOTS):
        assert kv.ensure_capacity(slot, 32)
    cache = kv_cache.prefill_step(
        pools["layers"], jnp.asarray(kv.table),
        jnp.asarray([5, 0, 9, 0], jnp.int32),
        slot=jnp.asarray([2, 0, 2, 2], jnp.int32),
        n_valid=jnp.asarray([CHUNK, 3, 0, 0], jnp.int32))
    # under the engine's own mesh, as every serving trace runs: the
    # paged attention code reads the installed mesh at trace time, and
    # a mesh another file of this worker left installed is not ours
    with dist.mesh_scope(engine.mesh):
        logits, out = engine.module.apply(
            {"params": engine._materialize(engine.params)},
            jnp.zeros((4, CHUNK), jnp.int32), cache=cache)
    assert logits.shape[:2] == (4, 1)
    assert list(np.asarray(out.lengths)) == [8, 0, 9 + CHUNK, 0]


# ------------------------------------------------------------ scheduler

CFG = dict(num_slots=SLOTS, num_pages=PAGES, page_size=PS,
           max_pages_per_slot=MAXP, prefill_chunk=CHUNK)


def _spy_prefill(engine, monkeypatch):
    """Record (slots, n_valid) of every prefill dispatch."""
    calls = []
    real = engine.prefill_into_slots

    def spy(ids, slot, n_valid, *a, **k):
        calls.append((list(np.asarray(slot)), list(np.asarray(n_valid))))
        return real(ids, slot, n_valid, *a, **k)
    monkeypatch.setattr(engine, "prefill_into_slots", spy)
    return calls


@pytest.mark.parametrize("n_prompts", [1, 3, 6])
def test_one_prefill_dispatch_per_boundary_step(engine, n_prompts):
    """However many slots are prefilling, a boundary step issues ONE
    prefill dispatch carrying all of them (read from the counters), and
    the streams stay token-exact vs generate()."""
    rng = np.random.default_rng(n_prompts)
    lens = [19, 5, 33, 8, 26, 12][:n_prompts]
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in lens]
    max_new = [5, 9, 3, 7, 4, 6][:n_prompts]
    sched = ServingScheduler(engine, audit_every=1, **CFG)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    m = sched.metrics
    rows_seen = []
    for _ in range(400):
        before = (m.prefill_dispatches, m.prefill_rows, m.prefill_tokens)
        pending = sum(len(r.prompt) - r.prefill_pos for r in reqs)
        busy = sched.step()
        d = m.prefill_dispatches - before[0]
        assert d <= 1, "more than one prefill dispatch in a step"
        if d:
            rows_seen.append(m.prefill_rows - before[1])
            assert m.prefill_tokens - before[2] == pending - sum(
                len(r.prompt) - r.prefill_pos for r in reqs)
        if not busy:
            break
    assert [r.out_tokens for r in reqs] == _oracle(engine, prompts, max_new)
    assert max(rows_seen) == min(n_prompts, SLOTS), rows_seen
    s = sched.summary()
    assert s["prefill_dispatches"] == len(rows_seen)
    assert s["prefill_rows"] == sum(rows_seen)
    assert s["prefill_tokens"] == sum(lens)
    assert s["prefill_padded_rows"] >= s["prefill_rows"]
    assert s["prefill_rows_per_dispatch"] == pytest.approx(
        sum(rows_seen) / len(rows_seen), abs=1e-3)
    assert 0.0 <= s["prefill_pad_share"] < 0.75   # power-of-four buckets
    h = sched.health()
    assert h["prefill_row_buckets"] == [1, 4]
    assert h["prefill_dispatches"] == s["prefill_dispatches"]


def test_slot_evicted_by_later_rows_growth_is_not_dispatched(monkeypatch):
    """Pool pressure inside the prefill phase: slot 1's growth finds no
    page and evicts the other live request — slot 0, whose own growth
    already succeeded in this step.  Its pages are gone, so its row must
    not ride the dispatch; it recomputes later, token-exact."""
    engine = _engine("gpt2-mha")
    calls = _spy_prefill(engine, monkeypatch)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, 12).astype(np.int32) for _ in range(2)]
    sched = ServingScheduler(engine, num_slots=2, num_pages=5, page_size=4,
                             max_pages_per_slot=4, prefill_chunk=4,
                             prefix_cache=False, audit_every=1)
    reqs = [sched.submit(p, max_new_tokens=2) for p in prompts]
    got = sched.run()
    assert sched.metrics.preemptions >= 1
    live = [[s for s, n in zip(*c) if n] for c in calls]
    # steps 1-2 carry both slots; in step 3 slot 0 grew to its third
    # page, slot 1 found the pool empty and evicted it: one row, slot 1
    assert live[:3] == [[0, 1], [0, 1], [1]], live
    assert [got[r.rid] for r in reqs] == _oracle(engine, prompts, [2, 2])
    assert all(r.state == "finished" for r in reqs)


def test_per_row_host_failure_closes_that_slot_only(engine):
    """A failure in ONE row's host preparation (here: its page growth)
    fails that request; the other rows ride the dispatch and finish
    token-exact."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (20, 9, 14)]
    max_new = [4, 4, 4]
    sched = ServingScheduler(engine, audit_every=1, **CFG)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    inj = faults.FaultInjector(seed=0)
    inj.on("serve.page_alloc", match={"rid": reqs[1].rid},
           exc=RuntimeError("row broke"))
    with faults.injected(inj):
        got = sched.run()
    assert reqs[1].state == "failed" and "row broke" in reqs[1].error
    want = _oracle(engine, prompts, max_new)
    for i in (0, 2):
        assert reqs[i].state == "finished" and got[reqs[i].rid] == want[i]
    assert sched.kv.pool.pages_in_use == 0


# -------------------------------------------------------- compile pin


@pytest.mark.parametrize("model", sorted(MODELS))
def test_prefill_compiles_bounded_by_row_buckets(model):
    """One prefill signature per row bucket actually used, never per
    row count, slot set, offset or finishing pattern — and further
    traffic adds none."""
    engine = _engine(model)
    rng = np.random.default_rng(3)
    sched = ServingScheduler(engine, **CFG)

    def wave(lens):
        for n in lens:
            sched.submit(rng.integers(0, 256, n).astype(np.int32),
                         max_new_tokens=int(rng.integers(1, 6)))
        sched.run()
    wave([3])
    wave([17, 9])
    wave([25, 2, 13])
    wave([5, 30, 8, 21, 11, 4])
    assert sched.prefill_row_buckets == [1, 4]
    n0 = engine.serving_prefill_compile_count()
    assert 1 <= n0 <= len(sched.prefill_row_buckets)
    wave([7, 7, 7])
    wave([31, 1, 16, 9, 2])
    assert engine.serving_prefill_compile_count() == n0
    assert engine.serving_prefill_compile_count() == \
        deepspeed_tpu.tracing.jit_cache_size(engine._paged_prefill_fn)


# ------------------------------------------------------ row bucket set

ROW_BUCKETS = {1: [1], 3: [1, 3], 4: [1, 4], 16: [1, 4, 16],
               32: [1, 4, 16, 32], 33: [1, 4, 16, 32, 33],
               64: [1, 4, 16, 32, 64], 128: [1, 4, 16, 32, 64, 128],
               256: [1, 4, 16, 32, 64, 128, 256]}


@pytest.mark.parametrize("num_slots", sorted(ROW_BUCKETS))
def test_row_buckets_step_by_four_to_16_rows_and_by_two_above(num_slots):
    """The set bounds the signatures (x4 while a dispatch is near its
    weight-read floor) AND what a padding row can cost (x2 above 16
    rows, where a padding row costs what a prompt row costs)."""
    buckets = _prefill_row_buckets(num_slots)
    assert buckets == ROW_BUCKETS[num_slots]
    assert buckets == sorted(set(buckets)) and buckets[-1] == num_slots
    for n in range(1, num_slots + 1):
        padded = _bucket_ceil(buckets, n)
        assert padded >= n and padded in buckets
        assert n <= 16 or padded < 2 * n, (n, padded)
        assert padded <= 4 * n
    if num_slots >= 64:
        assert {_bucket_ceil(buckets, n) for n in range(17, 33)} == {32}
        assert {_bucket_ceil(buckets, n) for n in range(33, 65)} == {64}


BUCKET_MODELS = {
    "llama": lambda: Llama(llama_tiny(num_layers=2)),
    "falcon-h1": lambda: FalconH1(falcon_h1_tiny()),
    "nemotron-h": lambda: NemotronH(nemotron_h_tiny(first_held_expert=4)),
}


@pytest.mark.parametrize("model", sorted(BUCKET_MODELS))
def test_twenty_prompts_on_64_slots_ride_one_32_row_dispatch(model):
    """20 short prompts admitted together: one [32, chunk] dispatch
    (12 padding rows with ``n_valid == 0``, which write nothing), and
    every request's tokens are what it gets served alone through the
    1-row bucket -- a dense model, a parallel hybrid (pages AND state
    in every layer) and a Mamba-2 / routed-experts hybrid alike."""
    engine = deepspeed_tpu.init_inference(
        model=BUCKET_MODELS[model](), dtype="float32", kv_cache_dtype="float32")
    engine.init_params(seed=3)
    cfg = dict(num_slots=64, num_pages=128, page_size=8,
               max_pages_per_slot=2, prefill_chunk=8,
               decode_horizon_steps=2, prefix_cache=False)
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, 256, int(n)).astype(np.int32)
               for n in rng.integers(2, 9, 20)]
    before = engine.serving_prefill_compile_count()

    together = ServingScheduler(engine, audit_every=1, **cfg)
    assert together.prefill_row_buckets == [1, 4, 16, 32, 64]
    reqs = [together.submit(p, max_new_tokens=4) for p in prompts]
    got = together.run()
    s, h = together.summary(), together.health()
    assert s["prefill_dispatches_by_bucket"] == {"32": 1}
    assert h["prefill_dispatches_by_bucket"] == {"32": 1}
    assert (s["prefill_rows"], s["prefill_padded_rows"]) == (20, 32)
    assert s["prefill_pad_share"] == pytest.approx(12 / 32)

    alone = ServingScheduler(engine, audit_every=1, **cfg)
    for p, r in zip(prompts, reqs):
        one = alone.submit(p, max_new_tokens=4)
        assert alone.run()[one.rid] == got[r.rid]
        assert r.state == one.state == "finished"
    assert alone.summary()["prefill_dispatches_by_bucket"] == {"1": 20}
    compiled = engine.serving_prefill_compile_count() - before
    assert compiled == 2 <= len(together.prefill_row_buckets)
