"""Multi-tenant serving tier (deepspeed_tpu/serving/tenancy): paged
multi-LoRA decode, per-tenant page quotas billed in page-seconds, and
weighted-fair admission over one shared page pool.

The oracles this PR is accepted on:

* **Multi-LoRA token-exactness**: a mixed batch striping three adapters
  plus base traffic through one scheduler emits EXACTLY the tokens each
  adapter produces served alone — including under forced eviction,
  prefix-cache hits, spec-decode verify rounds, and on a 2x4 mesh.
* **Prefix isolation**: identical prompts under two tenants (or two
  adapters of one tenant) NEVER share cached KV — the radix namespace
  is ``(tenant namespace, adapter)``.
* **Starvation**: a light tenant submitting after a heavy tenant's
  burst is served by deficit round-robin, not FIFO-starved behind it.
* **Quota**: a request that can never fit its tenant's page quota is
  shed WITH a reason naming the quota; an at-quota tenant with live
  work waits (its own retirements free pages) and drains only its OWN
  namespaces' cached pages — never another tenant's.
* **Byte-identity with tenancy off**: base-only traffic through a
  tenancy-on scheduler (no adapter store) reuses the pre-tenancy jit
  signatures — same tokens, ZERO new compiles.
* **Failover attribution**: a replica kill mid-stream replays under the
  same tenant/adapter (journal + WAL round-trip carries both).
"""

import json

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving import ClusterRouter, ServingScheduler, \
    make_local_fleet
from deepspeed_tpu.serving import mem_telemetry as memtel
from deepspeed_tpu.serving.cluster.journal import JournalEntry
from deepspeed_tpu.serving.scheduler import FINISHED, SHED
from deepspeed_tpu.serving.tenancy import (AdapterStore, TenantConfig,
                                           TenantRegistry, build_tenancy,
                                           parse_lora_spec,
                                           random_adapter)

CFG = dict(num_slots=3, num_pages=16, page_size=16, max_pages_per_slot=8,
           prefill_chunk=8)


@pytest.fixture(scope="module")
def engine():
    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1})
    eng.init_params()
    return eng


def _store(cfg, n=3, rank=4, mesh=None):
    """n synthetic adapters at one rank bucket.  stddev=0.5 on purpose:
    N(0, 0.02) deltas are too small to flip gpt2-tiny's greedy argmax,
    and an oracle that cannot tell adapters apart proves nothing."""
    store = AdapterStore(cfg, mesh=mesh)
    for i in range(n):
        store.add(f"a{i}", random_adapter(cfg, rank, seed=i, stddev=0.5))
    return store


def _registry(store, **overrides):
    kw = dict(adapters=tuple(store.names()) if store else ())
    kw.update(overrides)
    return TenantRegistry([TenantConfig("acme", **kw)],
                          adapter_store=store)


def _workload(rng, n=8):
    prompts = [rng.integers(0, 256, ln).astype(np.int32)
               for ln in (5, 11, 7, 5, 11, 7, 5, 11)[:n]]
    max_new = [8, 6, 10, 5, 7, 9, 6, 8][:n]
    return prompts, max_new


def _alone_oracle(engine, store_builder, prompts, max_new, adapters):
    """The reference: each request served ALONE, on a fresh scheduler
    whose store holds the SAME (seeded, deterministic) adapter weights
    — no batching, no cache, no pressure."""
    want = []
    for p, m, a in zip(prompts, max_new, adapters):
        sched = ServingScheduler(
            engine, tenancy=_registry(store_builder()), **CFG)
        req = sched.submit(p, max_new_tokens=m, tenant="acme", adapter=a)
        want.append(sched.run()[req.rid])
    return want


# --------------------------------------------------- the multi-LoRA oracle


def test_mixed_adapter_batch_token_exact_under_pressure(engine):
    """The tentpole oracle: 8 requests striped across {a0, a1, a2,
    base} through ONE scheduler with prefix cache + ngram spec decode +
    a page hostage forcing eviction — every stream equals its
    adapter-alone reference exactly."""
    rng = np.random.default_rng(0)
    prompts, max_new = _workload(rng)
    # two requests per lane share a head so prefix hits land inside an
    # adapter namespace mid-oracle
    prompts[4] = np.concatenate([prompts[0], prompts[4]])
    prompts[5] = np.concatenate([prompts[1], prompts[5]])
    roster = ["a0", "a1", "a2", None] * 2
    want = _alone_oracle(engine, lambda: _store(engine.module.cfg),
                         prompts, max_new, roster)

    sched = ServingScheduler(
        engine, tenancy=_registry(_store(engine.module.cfg)),
        prefix_cache=True, spec_decode="ngram", spec_k=4, **CFG)
    hostage = sched.kv.pool.allocate(13)     # 3 pages left -> churn
    reqs = [sched.submit(p, max_new_tokens=m, tenant="acme", adapter=a)
            for p, m, a in zip(prompts, max_new, roster)]
    got = sched.run()
    for r, w, a in zip(reqs, want, roster):
        assert got[r.rid] == w, f"adapter {a} diverged in the mix"
    assert sched.metrics.preemptions >= 1, \
        "the hostage never forced an eviction"
    assert sched.metrics.prefix_lookups > 0
    # the streams must actually differ by adapter, or the oracle is
    # vacuous (base == adapter would mean the deltas never applied)
    assert got[reqs[0].rid] != got[reqs[3].rid] or \
        got[reqs[1].rid] != got[reqs[3].rid]
    sched.kv.pool.free(hostage)
    out = sched.audit()
    assert out["ok"] and out["tenants"]["acme"]["slot"] == 0


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual CPU mesh")
def test_mixed_adapter_batch_token_exact_on_mesh(engine):
    """The same mixed-adapter batch on a model=2 x data=4 mesh (the
    adapter pack shards its factors over ``model`` when divisible)
    emits exactly the 1-device adapter-alone streams."""
    rng = np.random.default_rng(1)
    prompts, max_new = _workload(rng, n=4)
    roster = ["a0", "a1", "a2", None]
    want = _alone_oracle(engine, lambda: _store(engine.module.cfg),
                         prompts, max_new, roster)

    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32",
        kv_cache_dtype="float32", tensor_parallel={"tp_size": 2},
        mesh={"data": 4, "model": 2})
    eng.init_params()
    store = _store(eng.module.cfg, mesh=eng.mesh)
    sched = ServingScheduler(eng, tenancy=_registry(store), **CFG)
    reqs = [sched.submit(p, max_new_tokens=m, tenant="acme", adapter=a)
            for p, m, a in zip(prompts, max_new, roster)]
    got = sched.run()
    for r, w, a in zip(reqs, want, roster):
        assert got[r.rid] == w, f"adapter {a} diverged on-mesh"


# -------------------------------------------- signature economics (pins)


def test_rank_bucket_warmup_then_zero_extra_signatures(engine):
    """After one mixed-adapter run warms the rank bucket's signatures,
    adapter churn — a different striping, and an all-base batch through
    the same store — compiles NOTHING new: adapter ids are traced data,
    so every mix shares one signature per horizon bucket."""
    rng = np.random.default_rng(2)
    prompts, max_new = _workload(rng, n=4)

    def run(roster):
        sched = ServingScheduler(
            engine, tenancy=_registry(_store(engine.module.cfg)), **CFG)
        for p, m, a in zip(prompts, max_new, roster):
            sched.submit(p, max_new_tokens=m, tenant="acme", adapter=a)
        sched.run()

    run(["a0", "a1", "a2", None])            # rank-bucket warmup
    decode0 = engine.serving_decode_multi_compile_count()
    prefill0 = engine.serving_prefill_compile_count()
    run(["a2", None, "a0", "a1"])            # churned striping
    run([None, None, None, None])            # base-only, store loaded
    assert engine.serving_decode_multi_compile_count() == decode0, \
        "adapter churn compiled a new decode signature"
    assert engine.serving_prefill_compile_count() == prefill0, \
        "adapter churn compiled a new prefill signature"


def test_base_only_byte_identical_with_tenancy_off(engine):
    """Tenancy WITHOUT an adapter store is free: the same workload
    through a tenancy-on scheduler emits byte-identical tokens and
    reuses the tenancy-off jit signatures (the adapters side input
    stays the (None, None) leafless pytree)."""
    rng = np.random.default_rng(3)
    prompts, max_new = _workload(rng, n=6)

    plain = ServingScheduler(engine, **CFG)
    reqs = [plain.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got_plain = plain.run()
    decode0 = engine.serving_decode_multi_compile_count()
    prefill0 = engine.serving_prefill_compile_count()

    tenanted = ServingScheduler(
        engine, tenancy=TenantRegistry([TenantConfig("acme")]), **CFG)
    reqs_t = [tenanted.submit(p, max_new_tokens=m, tenant="acme")
              for p, m in zip(prompts, max_new)]
    got_t = tenanted.run()
    assert [got_t[r.rid] for r in reqs_t] == \
        [got_plain[r.rid] for r in reqs]
    assert engine.serving_decode_multi_compile_count() == decode0
    assert engine.serving_prefill_compile_count() == prefill0
    h = tenanted.health()
    assert h["tenancy"] and h["adapters"] == 0
    assert h["tenants"]["acme"]["completed"] == len(prompts)
    assert h["tenants"]["acme"]["page_seconds"] > 0, \
        "page-seconds billing never landed on the ledger"


# --------------------------------------------------- prefix isolation


def test_prefix_cache_isolated_by_tenant_and_adapter(engine):
    """Identical prompts NEVER share cached KV across the tenant or
    adapter boundary: only a same-(tenant, adapter) resubmit hits."""
    store = _store(engine.module.cfg, n=1)
    reg = TenantRegistry(
        [TenantConfig("acme", adapters=("a0",)), TenantConfig("bert")],
        adapter_store=store)
    sched = ServingScheduler(engine, tenancy=reg, prefix_cache=True,
                             **CFG)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 256, 20).astype(np.int32)

    def serve(tenant, adapter=None):
        req = sched.submit(prompt, max_new_tokens=4, tenant=tenant,
                           adapter=adapter)
        sched.run()
        return req

    assert serve("acme").cached_prefix_tokens == 0
    assert serve("acme").cached_prefix_tokens > 0, \
        "same-tenant resubmit must hit its own namespace"
    assert serve("bert").cached_prefix_tokens == 0, \
        "tenant bert hit tenant acme's cached KV"
    assert serve("acme", "a0").cached_prefix_tokens == 0, \
        "adapter traffic hit the base-model namespace"
    assert serve("acme", "a0").cached_prefix_tokens > 0
    sched.audit()


def test_registry_rejects_shared_namespace():
    with pytest.raises(ValueError, match="share prefix namespace"):
        TenantRegistry([
            TenantConfig("acme", prefix_namespace="shared"),
            TenantConfig("bert", prefix_namespace="shared")])


# ------------------------------------------------------ fairness oracle


def test_wdrr_light_tenant_not_starved(engine):
    """The starvation oracle: 6 heavy-tenant requests queued FIRST,
    then 2 light-tenant requests.  Plain FIFO would finish the light
    tenant dead last; deficit round-robin must interleave it — every
    light request finishes before the heavy backlog drains."""
    # quantum 1: with 1-page requests the default 8-page quantum lets
    # a tenant burst 8 admissions per visit — legal DRR, but this
    # oracle wants strict interleave to be visible in 8 requests
    reg = TenantRegistry([TenantConfig("heavy"), TenantConfig("light")],
                         quantum_pages=1)
    sched = ServingScheduler(engine, tenancy=reg, **dict(
        CFG, num_slots=2))
    rng = np.random.default_rng(5)
    for _ in range(6):
        sched.submit(rng.integers(0, 256, 7).astype(np.int32),
                     max_new_tokens=6, tenant="heavy")
    for _ in range(2):
        sched.submit(rng.integers(0, 256, 7).astype(np.int32),
                     max_new_tokens=6, tenant="light")
    sched.run()
    order = [r.tenant for r in sched.completed]
    assert order.index("light") < len(order) - 1 and \
        max(i for i, t in enumerate(order) if t == "light") < \
        max(i for i, t in enumerate(order) if t == "heavy"), \
        f"light tenant starved behind the heavy burst: {order}"
    u = reg.usage_fields()
    assert u["light"]["completed"] == 2 and u["heavy"]["completed"] == 6


# -------------------------------------------------------- quota oracle


def test_quota_shed_with_reason_and_counter(engine):
    """A request that can NEVER fit its tenant's quota is shed at
    admission with a reason naming the quota, and the shed lands on the
    metrics counter, the health() scalar and the tenant's ledger."""
    reg = TenantRegistry([TenantConfig("acme", page_quota=1)])
    sched = ServingScheduler(engine, tenancy=reg, **CFG)
    rng = np.random.default_rng(6)
    req = sched.submit(rng.integers(0, 256, 20).astype(np.int32),
                       max_new_tokens=16, tenant="acme")
    sched.run()
    assert req.state == SHED
    assert "quota" in req.error and "acme" in req.error
    assert sched.metrics.quota_shed == 1
    h = sched.health()
    assert h["quota_shed"] == 1
    assert h["tenants"]["acme"]["shed"] == 1


def test_at_quota_tenant_waits_for_its_own_pages(engine):
    """At quota with live work the tenant WAITS (its own retirements
    free pages) instead of being shed: both requests finish."""
    reg = TenantRegistry([TenantConfig("acme", page_quota=3)])
    sched = ServingScheduler(engine, tenancy=reg, **CFG)
    rng = np.random.default_rng(7)
    reqs = [sched.submit(rng.integers(0, 256, 20).astype(np.int32),
                         max_new_tokens=8, tenant="acme")
            for _ in range(2)]
    got = sched.run()
    assert all(r.state == FINISHED for r in reqs)
    assert all(len(got[r.rid]) == 8 for r in reqs)
    assert sched.metrics.quota_shed == 0


def test_quota_drains_own_namespace_never_a_peers(engine):
    """Capacity isolation: an over-quota tenant evicts only ITS
    namespaces' cached prefix pages — a peer tenant's cached KV
    survives untouched."""
    store = None
    reg = TenantRegistry([TenantConfig("acme"),
                          TenantConfig("bert", page_quota=4)],
                         adapter_store=store)
    sched = ServingScheduler(engine, tenancy=reg, prefix_cache=True,
                             **CFG)
    rng = np.random.default_rng(8)
    # acme seeds its namespace with cached pages
    sched.submit(rng.integers(0, 256, 32).astype(np.int32),
                 max_new_tokens=4, tenant="acme")
    sched.run()
    acme_ns = sched._tenant_namespaces("acme")
    acme_cached = {p for ns in acme_ns
                   for p in sched.prefix_cache.ns_iter_pages(ns)}
    assert acme_cached, "the acme run never cached a prefix"
    # bert fills its quota with cached pages, then needs them back
    sched.submit(rng.integers(0, 256, 32).astype(np.int32),
                 max_new_tokens=4, tenant="bert")
    sched.run()
    r2 = sched.submit(rng.integers(0, 256, 40).astype(np.int32),
                      max_new_tokens=4, tenant="bert")
    sched.run()
    assert r2.state == FINISHED, (r2.state, r2.error)
    after = {p for ns in acme_ns
             for p in sched.prefix_cache.ns_iter_pages(ns)}
    assert after == acme_cached, \
        "bert's quota drain evicted acme's cached pages"
    sched.audit()


# -------------------------------------------- intake validation + policy


def test_tenancy_intake_validation(engine):
    store = _store(engine.module.cfg, n=1)
    reg = _registry(store)
    sched = ServingScheduler(engine, tenancy=reg, **CFG)
    prompt = np.arange(5, dtype=np.int32)
    with pytest.raises(ValueError, match="name its tenant"):
        sched.submit(prompt)
    with pytest.raises(KeyError, match="unknown tenant"):
        sched.submit(prompt, tenant="nobody")
    with pytest.raises(ValueError, match="not entitled"):
        TenantRegistry([TenantConfig("t", adapters=("a0",))],
                       adapter_store=store).resolve("t", "a1")
    with pytest.raises(ValueError, match="not in the adapter store"):
        TenantRegistry([TenantConfig("t", adapters=("missing",))],
                       adapter_store=store)
    plain = ServingScheduler(engine, **CFG)
    with pytest.raises(ValueError, match="no tenancy"):
        plain.submit(prompt, tenant="acme")
    # multi-LoRA rides the greedy path only: policy traffic is rejected
    # at intake instead of silently dropping its peers' deltas
    with pytest.raises(ValueError, match="greedy decode path"):
        sched.submit(prompt, tenant="acme",
                     sampling={"temperature": 0.7, "do_sample": True})


def test_cli_tenancy_builders(engine, tmp_path):
    assert parse_lora_spec("a0=random:4:0,b=w.npz") == \
        [("a0", "random:4:0"), ("b", "w.npz")]
    with pytest.raises(ValueError, match="--tenants"):
        build_tenancy(engine.module.cfg, tenants=None, lora="a0=random")
    cfgp = tmp_path / "tenants.json"
    cfgp.write_text(json.dumps({"tenants": [
        {"name": "acme", "adapters": ["a0"], "page_quota": 8},
        {"name": "bert", "weight": 2.0}]}))
    reg = build_tenancy(engine.module.cfg, tenants=str(cfgp),
                        lora="a0=random:4:0")
    assert sorted(reg.tenants) == ["acme", "bert"]
    assert reg.store.names() == ["a0"]
    assert reg.tenants["acme"].page_quota == 8
    assert reg.tenants["bert"].weight == 2.0


# ------------------------------------------------- attribution + audit


def test_classify_tenants_conservation_and_leak_detection(engine):
    """classify_tenants charges every attributable page to exactly one
    tenant (conservation vs the global classifier) and refuses a live
    page no tenant can be charged for."""
    reg = TenantRegistry([TenantConfig("acme"), TenantConfig("bert")])
    sched = ServingScheduler(engine, tenancy=reg, prefix_cache=True,
                             **CFG)
    rng = np.random.default_rng(9)
    for i in range(4):
        sched.submit(rng.integers(0, 256, 12).astype(np.int32),
                     max_new_tokens=6,
                     tenant="acme" if i % 2 else "bert")
    # mid-flight census: step a few times so live slots are charged
    for _ in range(3):
        sched.step()
    rep = memtel.classify_tenants(sched)
    assert rep["ok"] and rep["label"] == "tenancy"
    total = sum(sum(d.values()) for d in rep["tenants"].values())
    base = memtel.classify(sched)
    attributable = sum(base[k] for k in
                       ("slot", "handoff", "prefix_shared",
                        "prefix_sole"))
    assert total == attributable, "per-tenant charges != global census"
    sched.run()
    # forge an unattributable live slot: its pages drop out of the
    # per-tenant charge, so conservation vs the global census breaks
    sched.submit(rng.integers(0, 256, 12).astype(np.int32),
                 max_new_tokens=32, tenant="acme")
    while not any(sched.slot_req):
        sched.step()
    victim = next(s for s in range(sched.num_slots)
                  if sched.slot_req[s] is not None)
    sched.slot_req[victim].tenant = None     # unattributable live page
    with pytest.raises(memtel.AuditError):
        memtel.classify_tenants(sched)
    sched.slot_req[victim].tenant = "acme"
    sched.run()


def test_failover_replay_keeps_tenant_and_adapter(engine, tmp_path):
    """Kill a replica mid-stream: every request replays under its
    original tenant/adapter (token-exact vs the adapter-alone
    reference), the journal carries the attribution through the WAL
    round-trip, and the fleet-shared registry's ledgers stay coherent."""
    rng = np.random.default_rng(10)
    prompts, max_new = _workload(rng, n=6)
    roster = ["a0", "a1", None] * 2
    want = _alone_oracle(engine, lambda: _store(engine.module.cfg),
                         prompts, max_new, roster)

    reg = _registry(_store(engine.module.cfg))
    reps = make_local_fleet(engine, 2, tenancy=reg, **CFG)
    router = ClusterRouter(reps)
    inj = faults.FaultInjector(seed=0)
    plan = inj.on("cluster.replica_kill", match={"replica": "replica0"},
                  step=2, exc=RuntimeError("replica crash"))
    with faults.injected(inj):
        entries = [router.submit(p, max_new_tokens=m, tenant="acme",
                                 adapter=a)
                   for p, m, a in zip(prompts, max_new, roster)]
        got = router.run()
    assert plan.fired == 1, "the kill must land mid-stream"
    h = router.health()
    assert h["failovers"] == 1 and h["finished"] == len(prompts)
    for e, w, a in zip(entries, want, roster):
        assert e.state == "finished", (e.rid, e.state, e.error)
        assert (e.tenant, e.adapter) == ("acme", a), \
            "replay lost the tenancy attribution"
        assert got[e.rid] == w, f"adapter {a} diverged across failover"
    # WAL round-trip: to_record -> from_record keeps both fields
    for e in entries:
        rec = json.loads(json.dumps(e.to_record()))
        back = JournalEntry.from_record(rec)
        assert (back.tenant, back.adapter) == (e.tenant, e.adapter)
    router.journal.dump(str(tmp_path / "journal.json"))
    dumped = json.loads((tmp_path / "journal.json").read_text())
    assert all(s["tenant"] == "acme" for s in dumped["entries"])
    # ONE registry serves the whole fleet: ledgers are fleet-wide
    assert reg.usage["acme"].completed >= len(prompts)
    assert reg.usage["acme"].page_seconds > 0
