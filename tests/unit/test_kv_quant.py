"""Quantized paged KV cache (ISSUE-14): int8/fp8 page pools + per-row
scale pools through the whole serving stack.

The accuracy-delta oracle suite:

* fp32 / bf16 ``kv_dtype`` stays TOKEN-EXACT vs ``generate()`` with the
  pool treedef and compile counts unchanged (zero-cost-when-off for the
  entire quant path);
* int8 / fp8 divergence is bounded: a pinned per-step teacher-forced
  logit-delta ceiling, and >= 95% token agreement (longest matching
  prefix vs the fp32 ``generate()`` stream, aggregated over the
  workload) under eviction pressure, prefix-cache full-hit/partial-COW
  sharing, speculative-decode verify rounds, prefill->decode handoff,
  and on a {2x4} device mesh;
* the CAPACITY claim is machine-checked, not asserted: at equal pool
  bytes (device-true, summed from the allocated leaves via
  health()/mem telemetry), int8 holds >= 1.8x the pages and sustains
  >= 1.8x the concurrent slots of fp32 with zero preemptions, while
  the fp32 control cannot;
* ``audit_every=1`` rides every quantized scheduler here, so the
  refcount auditor + conservation-exact page attribution prove the
  host books stay dtype-blind.

Workloads are deterministic (seeded); the divergence bounds were
measured at ~0 on this fixture (tiny-model logit gaps dwarf the
quantization noise) and pinned with wide margin — a regression that
flips tokens wholesale fails loudly.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.ops.quant.kv import fp8_supported, kv_page_bytes
from deepspeed_tpu.serving import ServingScheduler
from deepspeed_tpu.serving.cluster import (ClusterRouter,
                                           make_disaggregated_group)
from deepspeed_tpu.serving.page_manager import PagedKVManager

CFG = dict(num_slots=3, num_pages=32, page_size=16, max_pages_per_slot=8,
           prefill_chunk=8)
PS = CFG["page_size"]

# pinned oracle bounds (see module docstring: measured ~0 / 1.0 on the
# fixture, pinned with margin — these are regression ceilings, not
# expectations)
LOGIT_DELTA_CEILING = 0.5      # max |fp32 - int8| boundary logit, any step
TOKEN_AGREEMENT_FLOOR = 0.95   # aggregate matched-prefix fraction


@pytest.fixture(scope="module")
def engine():
    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32",
        kv_cache_dtype="float32", mesh={"data": 1, "model": 1})
    eng.init_params()
    return eng


def _fresh_engine(kv="float32", mesh=None):
    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32", kv_cache_dtype=kv,
        mesh=mesh or {"data": 1, "model": 1})
    eng.init_params()
    return eng


def _oracle(engine, prompts, max_new):
    return [
        [int(t) for t in
         engine.generate(p[None], max_new_tokens=m, do_sample=False)[
             0, len(p):]]
        for p, m in zip(prompts, max_new)]


def _workload(seed=0, n=4, lens=(5, 9, 17, 12), max_new=12):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 256, int(lens[i % len(lens)]))
               .astype(np.int32) for i in range(n)]
    news = [max_new] * n
    return prompts, news


def _agreement(got_lists, want_lists):
    """Aggregate matched-prefix fraction: tokens matching the reference
    before the first divergence, over total reference tokens.  (After
    one flipped token the continuations legitimately differ — counting
    positionwise equality there would measure noise, not fidelity.)"""
    matched = total = 0
    for got, want in zip(got_lists, want_lists):
        m = 0
        while m < min(len(got), len(want)) and got[m] == want[m]:
            m += 1
        matched += m
        total += len(want)
    return matched / max(1, total)


def _serve(engine, prompts, max_new, **kw):
    cfg = dict(CFG)
    cfg.update(kw)
    sched = ServingScheduler(engine, **cfg)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    sched.run()
    return sched, [r.out_tokens for r in reqs]


# ---------------------------------------------------- exact float paths


def test_bf16_kv_token_exact_and_pool_treedef_unchanged():
    """bf16 kv_dtype serves token-exact vs the bf16-cache generate()
    (the float paths carry ZERO quantization machinery: the pool layer
    holds exactly the two classic leaves, and the write path is the
    byte-identical legacy code)."""
    eng = _fresh_engine(kv="bfloat16")
    prompts, max_new = _workload(seed=3)
    want = _oracle(eng, prompts, max_new)
    sched, got = _serve(eng, prompts, max_new, audit_every=1)
    assert got == want
    layer = sched.pools["layers"][0]
    assert set(layer) == {"k_pages", "v_pages"}
    assert layer["k_pages"].dtype == jnp.bfloat16
    assert sched.health()["kv_dtype"] == "bfloat16"
    # the whole quant path is off: compile counts are the standard
    # per-bucket bounds, identical to every pre-quantization suite
    assert eng.serving_decode_multi_compile_count() <= \
        len(sched.horizon_buckets)
    assert (eng.serving_page_copy_compile_count() or 0) <= 1


# ------------------------------------------------- bounded divergence


def test_int8_bounded_divergence_and_signature_stability(engine):
    """int8 pools on the shared fp32 engine: >= 95% token agreement vs
    generate(), true quantized bytes in health(), and NO signature
    churn — a second int8 scheduler re-runs on the already-compiled
    signatures (one set per dtype per bucket, never per scheduler)."""
    prompts, max_new = _workload(seed=0)
    want = _oracle(engine, prompts, max_new)
    sched, got = _serve(engine, prompts, max_new, kv_dtype="int8",
                        audit_every=1)
    assert _agreement(got, want) >= TOKEN_AGREEMENT_FLOOR
    h = sched.health()
    assert h["kv_dtype"] == "int8"
    layer = sched.pools["layers"][0]
    assert set(layer) == {"k_pages", "v_pages", "k_scale", "v_scale"}
    assert layer["k_pages"].dtype == jnp.int8
    # health bytes == the allocated leaves' nbytes == the page-bytes
    # arithmetic (the capacity ledger is device-true, never hand-math)
    leaf_bytes = sum(int(l.nbytes) for L in sched.pools["layers"]
                     for l in L.values())
    assert h["kv_pool_bytes_total"] == leaf_bytes
    assert leaf_bytes == CFG["num_pages"] * engine.kv_page_bytes(
        PS, kv_dtype="int8")

    c_multi = engine.serving_decode_multi_compile_count()
    c_prefill = engine.serving_prefill_compile_count()
    _, got2 = _serve(engine, prompts, max_new, kv_dtype="int8",
                     audit_every=1)
    assert got2 == got                     # deterministic quantization
    assert engine.serving_decode_multi_compile_count() == c_multi
    assert engine.serving_prefill_compile_count() == c_prefill


@pytest.mark.skipif(not fp8_supported(), reason="jax build lacks "
                    "float8_e4m3fn")
def test_fp8_bounded_divergence(engine):
    prompts, max_new = _workload(seed=1)
    want = _oracle(engine, prompts, max_new)
    sched, got = _serve(engine, prompts, max_new, kv_dtype="fp8",
                        audit_every=1)
    assert _agreement(got, want) >= TOKEN_AGREEMENT_FLOOR
    assert sched.health()["kv_dtype"] == "fp8"


def test_int8_teacher_forced_logit_delta_pinned(engine):
    """Per-step logit-delta oracle: the SAME token stream teacher-forced
    through fp32 pools and int8 pools via chunked prefill; every
    boundary-logit delta stays under the pinned ceiling.  This isolates
    the KV-quantization error from autoregressive drift — each step
    reads the full quantized prefix, exactly what decode does."""
    rng = np.random.default_rng(42)
    seq = rng.integers(0, 256, 48).astype(np.int32)
    deltas = []
    runs = {}
    for kvd in ("float32", "int8"):
        pools = engine.init_paged_cache(CFG["num_pages"], PS,
                                        kv_dtype=kvd)
        kvm = PagedKVManager(CFG["num_pages"], PS, CFG["num_slots"],
                             CFG["max_pages_per_slot"])
        assert kvm.ensure_capacity(0, len(seq))
        lengths = np.zeros(CFG["num_slots"], np.int32)
        chunk = CFG["prefill_chunk"]
        logits_per_step = []
        for c0 in range(0, len(seq), chunk):
            ids = np.zeros((1, chunk), np.int32)
            n = min(chunk, len(seq) - c0)
            ids[0, :n] = seq[c0:c0 + n]
            logits, pools = engine.prefill_into_slots(
                ids, 0, n, kvm.table, lengths, pools)
            lengths[0] += n
            logits_per_step.append(np.asarray(logits, np.float32))
        runs[kvd] = logits_per_step
        kvm.release_slot(0)
    for a, b in zip(runs["float32"], runs["int8"]):
        deltas.append(float(np.max(np.abs(a - b))))
    assert max(deltas) < LOGIT_DELTA_CEILING, deltas
    # and the teacher-forced argmaxes agree step for step (the token
    # the scheduler would actually sample)
    agree = [int(np.argmax(a)) == int(np.argmax(b))
             for a, b in zip(runs["float32"], runs["int8"])]
    assert sum(agree) >= 0.95 * len(agree)


# ------------------------------------- eviction + prefix-cache sharing


def test_int8_under_eviction_pressure(engine):
    """Hostage pages force eviction mid-serve: the quantized pools ride
    the recompute preemption machinery (truncate/release/re-prefill of
    quantized pages) inside the divergence bound."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, 43).astype(np.int32)
               for _ in range(2)]
    max_new = [10, 10]
    want = _oracle(engine, prompts, max_new)
    # no audit_every here: the hostage allocation below is deliberately
    # unowned, exactly what the auditor exists to flag as a leak.
    # 7 pages left for 2 requests wanting 4 each (43 + 10 tokens) —
    # forces a recompute preemption mid-decode (the test_prefix_cache
    # recipe), now over quantized pages
    sched = ServingScheduler(engine, kv_dtype="int8", **CFG)
    hostage = sched.kv.pool.allocate(CFG["num_pages"] - 7)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    sched.run()
    got = [r.out_tokens for r in reqs]
    assert sched.metrics.preemptions >= 1, \
        "pool was sized to force preemption; none happened"
    assert all(r.state == "finished" for r in reqs)
    assert _agreement(got, want) >= TOKEN_AGREEMENT_FLOOR
    sched.kv.pool.free(hostage)


def test_int8_prefix_cache_sharing_matches_fp32_hit_rate(engine):
    """Donated QUANTIZED pages stay prefix-cache-sharable: the scales
    ride the page ids, so full-hit attach and partial-page COW behave
    exactly like fp32 — same hit rate, same tokens reused — and the
    shared-prefix stream stays inside the divergence bound."""
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(0, 256, 2 * PS + 6).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(0, 256, 4).astype(np.int32)])
               for _ in range(4)]
    max_new = [8] * 4
    want = _oracle(engine, prompts, max_new)

    stats = {}
    for kvd in ("float32", "int8"):
        sched = ServingScheduler(engine, kv_dtype=kvd, prefix_cache=True,
                                 audit_every=1, **CFG)
        got = []
        for p, m in zip(prompts, max_new):       # sequential: donors
            r = sched.submit(p, max_new_tokens=m)  # then sharers
            sched.run()
            got.append(r.out_tokens)
        h = sched.health()
        stats[kvd] = (h["prefix_hit_rate"], h["tokens_reused"],
                      h["cow_copies"])
        if kvd == "int8":
            assert _agreement(got, want) >= TOKEN_AGREEMENT_FLOOR
        else:
            assert got == want
    assert stats["int8"] == stats["float32"], \
        ("quantized pages must share exactly like fp32 pages "
         f"(fp32 {stats['float32']} vs int8 {stats['int8']})")
    assert stats["int8"][1] > 0                  # sharing actually hit


# ------------------------------------------- spec decode + handoff


def test_int8_spec_decode_verify_rounds(engine):
    """ngram speculative decoding over int8 pools: the teacher-forced
    verify_multi reads dequantized KV, rollback truncates quantized
    pages (scales ride along), and the stream stays inside the bound
    with real acceptances."""
    rng = np.random.default_rng(6)
    prompts, max_new = [], []
    for _ in range(3):
        motif = rng.integers(0, 256, 8).astype(np.int32)
        prompts.append(np.concatenate(
            [np.tile(motif, 3), rng.integers(0, 256, 4).astype(np.int32)]))
        max_new.append(24)
    want = _oracle(engine, prompts, max_new)
    sched, got = _serve(engine, prompts, max_new, kv_dtype="int8",
                        spec_decode="ngram", spec_k=4, audit_every=1)
    assert _agreement(got, want) >= TOKEN_AGREEMENT_FLOOR
    assert sched.metrics.spec_proposed > 0


def test_int8_handoff_over_shared_quantized_pool(engine):
    """Prefill->decode page handoff over ONE shared int8 pool: chains
    (payload + scale pages, one id set) adopt across schedulers, the
    fleet finishes everything, and ClusterRouter.audit() passes the
    EXACT census over the quantized shared pool after a failover."""
    from deepspeed_tpu.resilience import faults

    prompts, max_new = _workload(seed=7, lens=(5, 11, 7, 9), max_new=6)
    want = _oracle(engine, prompts, max_new)
    reps = make_disaggregated_group(
        engine, num_prefill=1, num_decode=2, num_pages=32, page_size=PS,
        kv_dtype="int8", num_slots=3, max_pages_per_slot=8,
        prefill_chunk=8)
    assert all(r.sched.kv_dtype_name == "int8" for r in reps)
    router = ClusterRouter(reps)
    entries = [router.submit(p, max_new_tokens=m)
               for p, m in zip(prompts, max_new)]
    got = router.run()
    assert router.health()["handoffs"] == len(prompts)
    assert all(e.state == "finished" for e in entries)
    assert _agreement([got[e.rid] for e in entries], want) >= \
        TOKEN_AGREEMENT_FLOOR
    router.audit()

    # failover leg: kill a decode worker mid-stream; replay must stay
    # in-bound and the post-failover audit must still balance the
    # shared quantized pool
    inj = faults.FaultInjector(seed=0)
    inj.on("cluster.replica_kill", match={"replica": "g0-decode0"},
           step=router.step_idx + 2, exc=RuntimeError("reclaimed"))
    with faults.injected(inj):
        entries2 = [router.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, max_new)]
        got2 = router.run()
    assert all(e.state == "finished" for e in entries2)
    assert _agreement([got2[e.rid] for e in entries2], want) >= \
        TOKEN_AGREEMENT_FLOOR
    router.audit()


# --------------------------------------------------------- on mesh


def test_int8_on_mesh_2x4(engine):
    """int8 pools sharded over a {model=2, data=4} mesh: the scale
    pools shard their kv-head dim alongside the payload (per-device
    bytes = total / model), and the mesh stream matches the 1-device
    int8 stream token for token."""
    prompts, max_new = _workload(seed=8)
    _, got_1dev = _serve(engine, prompts, max_new, kv_dtype="int8")
    eng_mesh = _fresh_engine(kv="int8", mesh={"model": 2, "data": 4})
    sched, got = _serve(eng_mesh, prompts, max_new, num_slots=4)
    h = sched.health()
    assert h["kv_dtype"] == "int8"
    assert h["mesh"] == {"model": 2, "data": 4}
    assert h["kv_pool_bytes_per_device"] * 2 == h["kv_pool_bytes_total"]
    assert got == got_1dev, \
        "mesh sharding must not change the quantized stream"


# --------------------------------------------------- capacity (the win)


def test_int8_capacity_1p8x_at_equal_pool_bytes(engine):
    """THE acceptance criterion: at equal pool bytes, int8 KV sustains
    >= 1.8x the concurrent slots of fp32 — proven by the byte/page
    accounting of the live pools (health == summed leaf nbytes == the
    kv_page_bytes arithmetic) and by actually RUNNING the concurrency:
    the int8 pool serves 2x the fp32 slot count with zero preemptions
    where the equal-byte fp32 pool provably cannot hold it."""
    bpp_f32 = engine.kv_page_bytes(PS, kv_dtype="float32")
    bpp_i8 = engine.kv_page_bytes(PS, kv_dtype="int8")
    budget = 8 * bpp_f32                      # the fp32 pool's bytes
    pages_i8 = budget // bpp_i8
    capacity_ratio = pages_i8 / 8
    assert capacity_ratio >= 1.8, (bpp_f32, bpp_i8, capacity_ratio)

    # 6 concurrent requests of 3 pages each = 18 pages resident: fits
    # the int8 pool (25 pages in the same bytes), provably cannot fit
    # the 8-page fp32 pool
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, 24).astype(np.int32)
               for _ in range(6)]
    max_new = [16] * 6
    want = _oracle(engine, prompts, max_new)
    need_pages = 6 * -(-(24 + 16) // PS)
    assert need_pages > 8 and need_pages <= pages_i8

    sched = ServingScheduler(engine, num_slots=6, num_pages=int(pages_i8),
                             page_size=PS, max_pages_per_slot=8,
                             prefill_chunk=8, kv_dtype="int8",
                             mem_telemetry=True, audit_every=1)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    peak_running = 0
    while sched.step():
        peak_running = max(peak_running, sched.health()["running"])
    assert peak_running == 6, "int8 must sustain all 6 slots at once"
    assert sched.metrics.preemptions == 0
    assert all(r.state == "finished" for r in reqs)
    assert _agreement([r.out_tokens for r in reqs], want) >= \
        TOKEN_AGREEMENT_FLOOR

    # device-true bytes: the int8 pool REALLY fits the fp32 budget
    h = sched.health()
    assert h["kv_pool_bytes_total"] <= budget
    assert h["kv_pool_bytes_total"] == sum(
        int(l.nbytes) for L in sched.pools["layers"] for l in L.values())
    # conservation over the quantized pool (mem telemetry's taxonomy
    # sweep must sum to num_pages — classify() raises otherwise, and
    # audit_every=1 already cross-checked refcounts every barrier step)
    from deepspeed_tpu.serving import mem_telemetry as memtel
    counts = memtel.classify(sched)
    states = ("slot", "prefix_shared", "prefix_sole", "handoff",
              "draft", "free", "unattributed")
    assert sum(counts[s] for s in states) == int(pages_i8)

    # the fp32 control at the SAME byte budget cannot sustain 6 slots:
    # 8 pages < 18 needed — admission + eviction keep peak concurrency
    # strictly below, visibly in the same machine-checked gauges
    ctrl = ServingScheduler(engine, num_slots=6, num_pages=8,
                            page_size=PS, max_pages_per_slot=8,
                            prefill_chunk=8, mem_telemetry=True)
    ctrl_reqs = [ctrl.submit(p, max_new_tokens=m)
                 for p, m in zip(prompts, max_new)]
    ctrl_peak = 0
    while ctrl.step():
        ctrl_peak = max(ctrl_peak, ctrl.health()["running"])
    # "sustains" means HOLDING the residency, not momentarily admitting
    # partial prefills: the fp32 pool (8 pages < the 18 the workload
    # needs resident) either never reaches 6-way residency or has to
    # evict to escape it — capacity distress the int8 run showed none of
    assert ctrl_peak < 6 or ctrl.metrics.preemptions >= 1, \
        "equal-byte fp32 sustaining 6 slots cleanly refutes the claim"
    assert ctrl.health()["kv_pool_bytes_total"] == 8 * bpp_f32
    del ctrl_reqs


# ------------------------------------------------- page-id mechanisms


def test_copy_page_moves_scales_with_payload(engine):
    """The COW primitive copies EVERY pool leaf: a quantized page's
    scale rows move with its payload (a copy that left stale scales
    behind would dequantize the private page wrongly forever)."""
    pools = engine.init_paged_cache(4, PS, kv_dtype="int8")
    layer0 = pools["layers"][0]
    k = layer0["k_pages"].at[1].set(
        jnp.ones_like(layer0["k_pages"][1]))
    s = layer0["k_scale"].at[1].set(
        jnp.full_like(layer0["k_scale"][1], 0.5))
    pools["layers"][0] = dict(layer0, k_pages=k, k_scale=s)
    out = engine.copy_page(pools, 1, 2)
    l0 = out["layers"][0]
    np.testing.assert_array_equal(np.asarray(l0["k_pages"][2]),
                                  np.asarray(l0["k_pages"][1]))
    np.testing.assert_array_equal(np.asarray(l0["k_scale"][2]),
                                  np.full((PS, 4, 1), 0.5, np.float32))
