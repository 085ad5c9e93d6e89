"""Disaggregated cluster serving tier (deepspeed_tpu/serving/cluster):
zero-lost-request failover under replica kills, prefix-aware routing,
rolling drain/restart, prefill/decode KV handoff with graceful degrade,
and the health()-schema / idempotency contracts the router rides on.

The failover oracle is the PR's headline: with a mixed workload
(prefix-shared + spec-decode traffic) across 3 replicas, killing a
replica mid-stream completes EVERY request token-exact vs the
single-engine generate() reference — zero lost, zero duplicated — and
the replay is reported distinctly.
"""

import json

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving import (ClusterRouter, QueueFull,
                                   ServingScheduler,
                                   make_disaggregated_group,
                                   make_local_fleet)

CFG = dict(num_slots=3, num_pages=16, page_size=16, max_pages_per_slot=8,
           prefill_chunk=8)


@pytest.fixture(scope="module")
def engine():
    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1})
    eng.init_params()
    return eng


def _oracle(engine, prompts, max_new):
    return [
        [int(t) for t in
         engine.generate(p[None], max_new_tokens=m, do_sample=False)[
             0, len(p):]]
        for p, m in zip(prompts, max_new)]


def _mixed_workload(rng, n_shared=4, n_spec=2):
    """Prefix-shared traffic (one system prompt, distinct tails) plus
    spec-decode-friendly traffic (repeated motifs, longer budgets)."""
    head = rng.integers(0, 256, 11).astype(np.int32)
    prompts, max_new = [], []
    for _ in range(n_shared):
        tail = rng.integers(0, 256, 5).astype(np.int32)
        prompts.append(np.concatenate([head, tail]))
        max_new.append(int(rng.integers(5, 9)))
    for _ in range(n_spec):
        motif = rng.integers(0, 256, 4).astype(np.int32)
        prompts.append(np.concatenate([np.tile(motif, 3),
                                       rng.integers(0, 256, 4).astype(
                                           np.int32)]))
        max_new.append(12)
    return prompts, max_new


def _leak_check(replicas):
    for rep in replicas:
        if rep.sched is None:
            continue
        cached = 0 if rep.sched.prefix_cache is None \
            else rep.sched.prefix_cache.cached_pages
        assert rep.sched.kv.pool.pages_in_use == cached, \
            f"{rep.id} leaked pages"


# ------------------------------------------------------ failover oracle


def test_failover_zero_lost_token_exact(engine, tmp_path):
    """The acceptance oracle: 3 replicas serving mixed prefix-shared +
    spec-decode traffic, one replica killed mid-stream — ALL requests
    finish token-exact vs generate(), zero lost, zero duplicated, and
    health()/journal report the replay distinctly."""
    rng = np.random.default_rng(0)
    prompts, max_new = _mixed_workload(rng)
    want = _oracle(engine, prompts, max_new)

    # audit_every=1: the PR-11 refcount auditor rides every replica's
    # barrier steps through the whole failover scenario
    reps = make_local_fleet(engine, 3, prefix_cache=True,
                            spec_decode="ngram", spec_k=4,
                            audit_every=1, **CFG)
    router = ClusterRouter(reps)
    inj = faults.FaultInjector(seed=0)
    plan = inj.on("cluster.replica_kill", match={"replica": "replica0"},
                  step=2, exc=RuntimeError("replica crash"))
    with faults.injected(inj):
        entries = [router.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, max_new)]
        got = router.run()
    assert plan.fired == 1, "the kill must actually land mid-stream"
    router.audit()   # fleet-wide refcount census after the failover
    h = router.health()
    assert h["failovers"] == 1
    assert h["replays"] >= 1, "the dead replica held work"
    assert h["failed"] == 0 and h["shed"] == 0 and h["cancelled"] == 0
    assert h["finished"] == len(prompts)
    assert h["replicas"]["replica0"]["state"] == "dead"
    for e, w in zip(entries, want):
        assert e.state == "finished", (e.rid, e.state, e.error)
        # token-exact AND exactly-once: the emitted stream equals the
        # reference exactly, so nothing was lost or duplicated even
        # though part of it ran on the dead replica
        assert got[e.rid] == w, (e.rid, e.replica_history)
    replayed = [e for e in entries if e.replays > 0]
    assert replayed and all(len(e.replica_history) > 1 for e in replayed)
    _leak_check(reps)
    # the CI artifact path: journal + health dump round-trips as JSON
    router.journal.dump(str(tmp_path / "journal.json"))
    dumped = json.loads((tmp_path / "journal.json").read_text())
    assert dumped["counts"]["finished"] == len(prompts)
    assert any(s["replays"] for s in dumped["entries"])


def test_failover_sampled_stream_exact_and_grammar_valid(engine):
    """Decoding-policy failover: sampled requests (seeded, penalized)
    and a grammar-constrained request survive a replica kill with the
    EXACT token stream an undisturbed fleet serves — the position-keyed
    PRNG means a survivor resumes the stream bitwise, not merely from
    the same distribution — and the constrained output still matches
    its grammar after the replay."""
    from deepspeed_tpu.serving.sampling import compile_grammar

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 9, 7, 5)]
    rows = [
        dict(sampling={"do_sample": True, "temperature": 0.9,
                       "top_p": 0.95}, seed=101),
        dict(sampling={"do_sample": True, "temperature": 1.1,
                       "top_k": 50, "repetition_penalty": 1.2},
             seed=202),
        dict(sampling={"do_sample": True}, seed=303,
             grammar={"regex": "(ab|cd)+"}),
        dict(sampling=None, seed=None),   # greedy control rides along
    ]
    max_new = [8, 8, 10, 6]

    def serve(kill):
        reps = make_local_fleet(engine, 2, **CFG)
        router = ClusterRouter(reps)
        inj = faults.FaultInjector(seed=0)
        plan = None
        if kill:
            plan = inj.on("cluster.replica_kill",
                          match={"replica": "replica0"}, step=3,
                          exc=RuntimeError("replica crash"))
        with faults.injected(inj):
            entries = [router.submit(p, max_new_tokens=m, **row)
                       for p, m, row in zip(prompts, max_new, rows)]
            got = router.run()
        if kill:
            assert plan.fired == 1
            assert router.health()["replays"] >= 1
        assert all(e.state == "finished" for e in entries), \
            [(e.rid, e.state, e.error) for e in entries]
        _leak_check(reps)
        return [got[e.rid] for e in entries]

    calm, stormy = serve(kill=False), serve(kill=True)
    assert stormy == calm, \
        "failover replay must continue the sampled streams bitwise"
    g = compile_grammar({"regex": "(ab|cd)+"},
                        engine.module.cfg.vocab_size)
    assert g.accepts(stormy[2]), stormy[2]


def test_replica_restart_rejoins_routing(engine):
    """A dead replica restarted through the router serves again."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(3)]
    want = _oracle(engine, prompts, [6, 6, 6])
    reps = make_local_fleet(engine, 2, **CFG)
    router = ClusterRouter(reps)
    inj = faults.FaultInjector(seed=0)
    inj.on("cluster.replica_kill", match={"replica": "replica1"},
           step=1, exc=RuntimeError("boom"))
    with faults.injected(inj):
        e0 = [router.submit(p, max_new_tokens=6) for p in prompts[:2]]
        got = router.run()
    assert reps[1].state == "dead"
    router.restart_replica(reps[1])
    assert reps[1].state == "up" and reps[1].restarts == 1
    # drain replica0 so the new request MUST land on the restarted one
    reps[0].begin_drain()
    e2 = router.submit(prompts[2], max_new_tokens=6)
    got2 = router.run()
    assert got2[e2.rid] == want[2] and e2.replica_history == ["replica1"]
    assert [got[e.rid] for e in e0] == want[:2]


# ------------------------------------------------- disaggregated serving


def test_disaggregated_handoff_token_exact_and_degrade(engine):
    """Prefill-worker -> decode-worker page handoff is token-exact vs
    unified serving, and the tier degrades to unified (no crash, no
    lost requests) when the last prefill worker dies."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 11, 5, 11)]
    max_new = [8, 6, 10, 4]
    want = _oracle(engine, prompts, max_new)

    reps = make_disaggregated_group(
        engine, num_prefill=1, num_decode=1, num_pages=32, page_size=16,
        num_slots=3, max_pages_per_slot=8, prefill_chunk=8)
    router = ClusterRouter(reps)
    entries = [router.submit(p, max_new_tokens=m)
               for p, m in zip(prompts, max_new)]
    got = router.run()
    h = router.health()
    assert h["handoffs"] == len(prompts), \
        "every request must ride the prefill->decode handoff"
    assert h["degraded_routes"] == 0 and not h["degraded"]
    for e, w in zip(entries, want):
        assert e.state == "finished" and got[e.rid] == w, \
            (e.rid, e.state, e.error, e.replica_history)
    # the decode worker's scheduler never ran a prefill dispatch for
    # handed-off work: its requests decode straight off adopted pages
    decode = [r for r in reps if r.role == "decode"][0]
    assert decode.sched.metrics.completed == len(prompts)

    # kill the only prefill worker with fresh traffic queued: the tier
    # must keep serving unified — zero lost, still token-exact
    inj = faults.FaultInjector(seed=0)
    inj.on("cluster.replica_kill", match={"replica": "g0-prefill0"},
           step=router.step_idx + 2, exc=RuntimeError("node reclaimed"))
    with faults.injected(inj):
        entries2 = [router.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, max_new)]
        got2 = router.run()
    h = router.health()
    assert h["prefill_workers_up"] == 0 and h["degraded"]
    assert h["degraded_routes"] >= 1
    assert h["failed"] == 0 and h["shed"] == 0
    for e, w in zip(entries2, want):
        assert e.state == "finished" and got2[e.rid] == w, \
            (e.rid, e.state, e.error, e.replica_history)
    # the shared pool reconciles: only the decode worker's cache (none
    # here) may retain pages
    _leak_check(reps)


def test_handoff_fault_degrades_to_unified(engine):
    """An injected ``cluster.handoff`` fault frees the packet's pages
    and requeues the request for unified serving — contained, never
    lost, still token-exact."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(2)]
    want = _oracle(engine, prompts, [6, 6])
    reps = make_disaggregated_group(
        engine, num_prefill=1, num_decode=1, num_pages=32, page_size=16,
        num_slots=3, max_pages_per_slot=8, prefill_chunk=8)
    router = ClusterRouter(reps)
    inj = faults.FaultInjector(seed=0)
    plan = inj.on("cluster.handoff", nth=1,
                  exc=RuntimeError("transport torn"))
    with faults.injected(inj):
        entries = [router.submit(p, max_new_tokens=6) for p in prompts]
        got = router.run()
    assert plan.fired == 1
    for e, w in zip(entries, want):
        assert e.state == "finished" and got[e.rid] == w, \
            (e.rid, e.state, e.error, e.replica_history)
    assert router.health()["failed"] == 0
    _leak_check(reps)


# --------------------------------------------- routing + rolling restart


def test_prefix_aware_routing_beats_round_robin(engine):
    """With more prefix families than replicas, prefix-aware routing
    pins each family to one replica's radix cache; round-robin sprays
    members across the fleet and eats a cold miss per (family, replica)
    pair.  Aggregate hit rate must show it."""
    rng = np.random.default_rng(3)
    heads = [rng.integers(0, 256, 11).astype(np.int32) for _ in range(3)]
    waves = []
    for _ in range(3):   # one member per family per arrival wave
        waves.append([np.concatenate(
            [h, rng.integers(0, 256, 5).astype(np.int32)])
            for h in heads])

    def serve(routing):
        reps = make_local_fleet(engine, 2, prefix_cache=True, **CFG)
        router = ClusterRouter(reps, routing=routing)
        entries = []
        for wave in waves:   # paced arrivals: later waves see warm
            entries += [router.submit(p, max_new_tokens=4) for p in wave]
            router.run()     # caches on whichever replica served them
        assert all(e.state == "finished" for e in entries)
        return router.health()["aggregate_prefix_hit_rate"]

    rr, pf = serve("round_robin"), serve("prefix")
    assert pf > rr, f"prefix routing {pf} must beat round-robin {rr}"


def test_rolling_restart_zero_failed(engine):
    """Drain + restart every replica in sequence while the fleet keeps
    serving: zero failed requests, all token-exact, every replica
    restarted exactly once."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(8)]
    max_new = [6] * 8
    want = _oracle(engine, prompts, max_new)
    reps = make_local_fleet(engine, 3, prefix_cache=True, **CFG)
    router = ClusterRouter(reps)
    entries = [router.submit(p, max_new_tokens=m)
               for p, m in zip(prompts, max_new)]
    for _ in range(2):   # work in flight on every replica
        router.step()
    router.rolling_restart()
    got = router.run()
    h = router.health()
    assert h["failed"] == 0 and h["shed"] == 0
    assert h["restarts"] == 3 and h["drains"] == 3
    assert all(r.restarts == 1 and r.state == "up" for r in reps)
    for e, w in zip(entries, want):
        assert e.state == "finished" and got[e.rid] == w
    # restarted replicas still serve
    e2 = router.submit(prompts[0], max_new_tokens=6)
    got2 = router.run()
    assert got2[e2.rid] == want[0]


def test_router_backpressure_bounded_retry(engine):
    """QueueFull at every replica is absorbed by bounded retry with
    backoff — the burst completes once capacity frees up, and the
    retries are reported; a hopeless request sheds distinctly after the
    budget."""
    reps = make_local_fleet(engine, 1, max_queue=2, **CFG)
    router = ClusterRouter(reps, retry_backoff_s=0.01)
    prompt = np.zeros(5, np.int32)
    entries = [router.submit(prompt, max_new_tokens=2) for _ in range(8)]
    got = router.run()
    h = router.health()
    assert h["retries"] > 0, "the burst must have tripped backpressure"
    assert all(e.state == "finished" for e in entries)
    assert len(got) == 8 and h["shed"] == 0


# ------------------------------------------- contracts the router rides


def test_idempotent_rid_and_cancel_after_terminal(engine):
    """At-most-once admission: resubmitting a client rid returns the
    incumbent entry.  Cancel after terminal is an idempotent no-op."""
    reps = make_local_fleet(engine, 1, **CFG)
    router = ClusterRouter(reps)
    prompt = np.zeros(5, np.int32)
    e1 = router.submit(prompt, max_new_tokens=3, rid="client-1")
    dup = router.submit(prompt, max_new_tokens=99, rid="client-1")
    assert dup is e1 and e1.max_new_tokens == 3
    assert router.health()["duplicate_rids"] == 1
    got = router.run()
    assert e1.state == "finished" and len(got["client-1"]) == 3
    # cancel-after-terminal: no state change, no exception, False back
    assert router.cancel("client-1") is False
    assert e1.state == "finished" and e1.emitted == got["client-1"]
    assert router.health()["cancelled"] == 0
    # resubmitting a TERMINAL rid is also absorbed (the journal is the
    # dedup window); unknown rids are a no-op cancel
    dup2 = router.submit(prompt, max_new_tokens=5, rid="client-1")
    assert dup2 is e1 and e1.state == "finished"
    assert router.cancel("never-seen") is False
    # a queued cancel is honored without ever touching a replica
    e2 = router.submit(prompt, max_new_tokens=3, rid="client-2")
    assert router.cancel("client-2") is True
    router.run()
    assert e2.state == "cancelled" and e2.emitted == []


HEALTH_SCHEMA = {
    # key -> allowed types (None listed where the field is nullable)
    "step": (int,),
    "uptime_s": (float,),
    "steps_per_s": (float,),
    "tracing": (bool,),
    # the last steps over 1.0 s, each with its split by phase
    "slow_steps": (list,),
    "mesh": (dict, type(None)),
    "mesh_devices": (int, type(None)),
    "serving_axes": (dict, type(None)),
    # per-slot recurrent state beside the page pool and the routed
    # layers' counters (0 / None for a model with neither)
    "state_pool_bytes_per_device": (int,),
    "state_pool_bytes_total": (int,),
    "state_resets": (int,),
    "moe_assignments": (int,),
    "moe_held_assignments": (int,),
    "moe_held_load_max_over_mean": (float,),
    "moe_dense_experts_read_share": (float,),
    "moe_walk_share": (float,),
    "prefix_cache_refused": (str, type(None)),
    # the paged-attention dispatch decision (path/dispatch/reason of
    # single-token decode, and the same three under "multi_token" for
    # prefill/verify) — kernel vs reference must be operator-visible,
    # never silent
    "paged_attention": (dict, type(None)),
    # quantized serving memory (kv_dtype in {float32, bfloat16, int8,
    # fp8}); the byte figures reflect the TRUE quantized footprint
    # (payload + scale pools summed from the allocated leaves)
    "kv_dtype": (str,),
    "weight_dtype": (str, type(None)),
    "kv_pool_bytes_per_device": (int, type(None)),
    "kv_pool_bytes_total": (int, type(None)),
    "prefix_cache": (bool,),
    "prefix_hit_rate": (float, type(None)),
    "tokens_reused": (int,),
    "pages_shared": (int,),
    "cached_pages": (int,),
    "cow_copies": (int,),
    "running": (int,),
    "waiting": (int,),
    "live_requests": (int,),
    "queue_capacity": (int,),
    "free_pages": (int,),
    "page_utilization": (float,),
    "ema_step_ms": (float, type(None)),
    "decode_horizon_steps": (int,),
    "horizon_buckets": (list,),
    # horizons the slot-bound rule chose below the configured pick
    # (PR 47, _turnover_horizon) and their share of all horizons
    "horizon_turnover_picks": (int,),
    "horizon_turnover_share": (float,),
    "overlap": (bool,),
    "spec_decode": (str,),
    "spec_k": (int, type(None)),
    "spec_acceptance_rate": (float,),
    "spec_mean_accepted": (float,),
    "spec_draft_tokens": (int,),
    "spec_accepted_tokens": (int,),
    "spec_rollbacks": (int,),
    "spec_degraded": (int,),
    # memory observability (PR 11): the page-state attribution rides
    # every health snapshot (telemetry on or off — the sweep is
    # heartbeat-cadence); byte figures derive from the topology
    # snapshot's pool_bytes_per_device
    "mem_telemetry": (bool,),
    "mem_slot_pages": (int,),
    "mem_prefix_shared_pages": (int,),
    "mem_prefix_sole_pages": (int,),
    "mem_handoff_pages": (int,),
    "mem_draft_pages": (int,),
    "mem_unattributed_pages": (int,),
    "mem_free_pages": (int,),
    "mem_free_frac": (float,),
    "mem_page_seconds": (float,),
    "mem_pressure_events": (int,),
    "mem_pressure_episodes": (int,),
    "mem_slot_bytes_per_device": (int, type(None)),
    "mem_prefix_bytes_per_device": (int, type(None)),
    "mem_handoff_bytes_per_device": (int, type(None)),
    "mem_free_bytes_per_device": (int, type(None)),
    # communication & compile observability (PR 12): the HLO comm-
    # ledger summary (None until comm_ledger() ran — health itself
    # never pays an analysis compile) and the recompile watchdog
    "comm_telemetry": (bool,),
    "comm_bytes_per_step": (int, type(None)),
    "comm_bytes_per_token": (float, int, type(None)),
    "comm_collectives_per_step": (int, type(None)),
    "comm_axis_bytes": (dict, type(None)),
    "comm_ici_bytes_per_step": (int, type(None)),
    "comm_dcn_bytes_per_step": (int, type(None)),
    "compile_watchdog": (bool,),
    "compiles": (int,),
    "steady_recompiles": (int,),
    # serving autotuner (PR 13): online-controller presence + nudge
    # count, and the searched-config provenance (--tuned-config)
    "online_tuner": (bool,),
    "tune_nudges": (int,),
    "tuned_from": (str, type(None)),
    # decoding-policy subsystem (PR 16): the scheduler-wide default
    # policy label plus the per-request policy counters (sampled/
    # grammar intakes, policy-path dispatches, contained grammar
    # violations)
    "decoding_policy": (str,),
    "sampled_requests": (int,),
    "grammar_requests": (int,),
    "policy_dispatches": (int,),
    "grammar_violations": (int,),
    "inflight_horizons": (int,),
    "draining": (bool,),
    "handoffs": (int,),
    "pending_handoffs": (int,),
    # cross-pool KV transport (PR 19): chunked page-chain transfer
    # counters — bytes exported/imported over device_put or the wire
    # sidecar, chunk count, host-measured transfer time, aborts
    "handoff_bytes_out": (int,),
    "handoff_bytes_in": (int,),
    "handoff_chunks": (int,),
    "handoff_transport_ms": (float, int),
    "handoff_aborted": (int,),
    "completed": (int,),
    "failed": (int,),
    "shed": (int,),
    "cancelled": (int,),
    "preemptions": (int,),
    "tokens_emitted": (int,),
    "last_error": (str, type(None)),
    # router HA (PR 17): the fencing state the owning replica/worker
    # stamps — the lease epoch this scheduler last saw, and how many
    # stale-epoch calls it rejected/cancelled
    "ha_epoch": (int, type(None)),
    "ha_fenced": (int,),
    # sequence-parallel prefill (PR 18): the resolved long-context
    # routing state — threshold, transport (or why it degraded),
    # compile-pinned chunk buckets, the fairness reserve cap, and the
    # routing/shed counters admission dashboards key off
    "seq_parallel_threshold": (int,),
    "seq_parallel_axis": (str, type(None)),
    "seq_parallel_impl": (str, type(None)),
    "seq_parallel_degrade_reason": (str, type(None)),
    "sp_chunk_buckets": (list,),
    "prefill_row_buckets": (list,),
    "prefill_dispatches": (int,),
    "prefill_rows": (int,),
    "prefill_padded_rows": (int,),
    "prefill_tokens": (int,),
    "prefill_rows_per_dispatch": (int, float),
    "prefill_pad_share": (int, float),
    # dispatches per row bucket they rode in, {"16": n, "32": n, ...}
    # (PR 41): which programs of the bucket set the traffic runs
    "prefill_dispatches_by_bucket": (dict,),
    "prefill_reserve_cap": (int,),
    "seq_prefill_routed": (int,),
    "seq_prefill_chunks": (int,),
    "seq_prefill_degraded": (int,),
    "seq_prefill_shed": (int,),
    # multi-tenant serving (PR 20): tenancy presence, the per-tenant
    # usage ledgers + live page footprints (None with tenancy off),
    # adapter-store shape (count + rank bucket — the jit-signature
    # inputs) and the quota-shed counter
    "tenancy": (bool,),
    "tenants": (dict, type(None)),
    "tenant_pages": (dict, type(None)),
    "adapters": (int,),
    "adapter_rank_bucket": (int,),
    "quota_shed": (int,),
}


def test_health_schema_pinned(engine):
    """The health() snapshot is an API: the cluster router keys
    admission, routing and death detection off these fields, ds_serve
    prints them, and CI uploads them.  A rename or type change must
    fail HERE, not silently break routing."""
    sched = ServingScheduler(engine, prefix_cache=True, **CFG)
    sched.submit(np.zeros(5, np.int32), max_new_tokens=3)
    sched.run()
    h = sched.health()
    assert set(h) == set(HEALTH_SCHEMA), (
        f"health() keys changed: added {set(h) - set(HEALTH_SCHEMA)}, "
        f"removed {set(HEALTH_SCHEMA) - set(h)} — update the router, "
        "ds_serve, docs and this pin TOGETHER")
    for key, types in HEALTH_SCHEMA.items():
        assert isinstance(h[key], types), \
            f"health()[{key!r}] = {h[key]!r} is not {types}"
    assert {"path", "dispatch", "reason"} <= \
        set(h["paged_attention"]) & set(h["paged_attention"]["multi_token"])
    # the specific fields admission/routing consume must be live values
    assert h["running"] == 0 and h["completed"] == 1
    assert 0.0 <= h["page_utilization"] <= 1.0


def test_scheduler_drain_modes(engine):
    """drain(): in-flight requests finish inside the grace budget;
    still-queued work sheds distinctly; grace_s=0 sheds mid-flight work
    with the dedicated reason instead of losing it."""
    sched = ServingScheduler(engine, **CFG)
    done = [sched.submit(np.zeros(5, np.int32), max_new_tokens=3)
            for _ in range(3)]
    queued = [sched.submit(np.zeros(5, np.int32), max_new_tokens=3)
              for _ in range(3)]
    sched.step()     # the first wave is admitted and prefilling
    counts = sched.drain(grace_s=30.0, shed_waiting=True)
    assert counts["finished"] == 3 and counts["shed"] == 3
    assert all(r.state == "finished" for r in done)
    assert all(r.state == "shed" and "still queued" in r.error
               for r in queued)
    assert sched.kv.pool.pages_in_use == 0
    with pytest.raises(QueueFull, match="draining"):
        sched.submit(np.zeros(5, np.int32), max_new_tokens=1)

    sched2 = ServingScheduler(engine, **CFG)
    live = [sched2.submit(np.zeros(5, np.int32), max_new_tokens=64)
            for _ in range(2)]
    sched2.step()
    counts = sched2.drain(grace_s=0.0, shed_waiting=True)
    assert counts["shed"] == 2 and counts["finished"] == 0
    assert all(r.state == "shed" and "grace budget exhausted" in r.error
               for r in live)
    assert sched2.kv.pool.pages_in_use == 0, "drain leaked pages"


# ----------------------------------------------- process-backed replicas


@pytest.mark.slow
def test_process_replica_sigkill_zero_lost(engine):
    """The real thing: two worker PROCESSES, one SIGKILLed mid-stream.
    The router detects the death (reaped pid / missed heartbeats) and
    replays onto the survivor; every request finishes token-exact vs
    the in-process generate() reference (workers init params with the
    same seed), zero lost, zero duplicated."""
    from deepspeed_tpu.serving import ProcessReplica

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(4)]
    max_new = [24, 24, 24, 24]
    want = _oracle(engine, prompts, max_new)
    reps = [ProcessReplica(f"proc{i}", model="gpt2-tiny",
                           term_grace_s=5.0) for i in range(2)]
    try:
        for rep in reps:
            rep.wait_ready()
        router = ClusterRouter(reps, heartbeat_misses=1)
        entries = [router.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, max_new)]
        # let streams start, then SIGKILL the replica holding work
        import time as _time
        deadline = _time.monotonic() + 600
        while _time.monotonic() < deadline:
            router.step()
            if sum(len(e.emitted) for e in entries) >= 2:
                break
            _time.sleep(0.05)
        assert sum(len(e.emitted) for e in entries) >= 2, \
            "workers never started streaming"
        victim = next(r for r in reps if r.load() > 0)
        victim.kill()
        got = router.run(max_steps=200000)
        h = router.health()
        assert h["failovers"] == 1 and h["replays"] >= 1
        assert h["failed"] == 0
        for e, w in zip(entries, want):
            assert e.state == "finished", (e.rid, e.state, e.error)
            assert got[e.rid] == w, (e.rid, e.replica_history)
    finally:
        for rep in reps:
            rep.die("test teardown")


@pytest.mark.slow
def test_process_replica_revival_no_double_adopt(engine):
    """Heartbeat-flap pin, process flavor: a SIGKILLed ProcessReplica is
    REVIVED via restart_replica after its in-flight work already
    replayed to the survivor.  The revived worker (a fresh incarnation)
    must not be double-adopted: requests in flight at the kill finish
    exactly once token-exact, fresh post-revival traffic is served, and
    the journal audit stays clean throughout."""
    from deepspeed_tpu.serving import ProcessReplica

    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(4)]
    max_new = [24, 24, 24, 24]
    want = _oracle(engine, prompts, max_new)
    reps = [ProcessReplica(f"proc{i}", model="gpt2-tiny",
                           term_grace_s=5.0) for i in range(2)]
    try:
        for rep in reps:
            rep.wait_ready()
        router = ClusterRouter(reps, heartbeat_misses=1)
        entries = [router.submit(p, max_new_tokens=m, rid=f"r{i}")
                   for i, (p, m) in enumerate(zip(prompts, max_new))]
        import time as _time
        deadline = _time.monotonic() + 600
        while _time.monotonic() < deadline:
            router.step()
            if sum(len(e.emitted) for e in entries) >= 2:
                break
            _time.sleep(0.05)
        victim = next(r for r in reps if r.load() > 0)
        inc0 = victim.incarnation
        victim.kill()
        got = router.run(max_steps=200000)
        assert router.journal.audit() == []
        # revive the killed worker: fresh process, bumped incarnation
        router.restart_replica(victim)
        victim.wait_ready()
        assert victim.incarnation == inc0 + 1
        assert victim.state == "up"
        # the finished streams stay exactly-once (no late double-emit
        # from the revived id) and fresh traffic is served
        for e, w in zip(entries, want):
            assert e.state == "finished", (e.rid, e.state, e.error)
            assert got[e.rid] == w, (e.rid, e.replica_history)
        more = [router.submit(p, max_new_tokens=8, rid=f"post{i}")
                for i, p in enumerate(prompts[:2])]
        got2 = router.run(max_steps=200000)
        for e in more:
            assert e.state == "finished", (e.rid, e.state, e.error)
            assert len(got2[e.rid]) == 8
        for e, w in zip(entries, want):
            assert e.emitted == w, "revival double-emitted into an " \
                                   "already-finished stream"
        assert router.journal.audit() == []
        assert router.health()["restarts"] == 1
    finally:
        for rep in reps:
            rep.die("test teardown")


@pytest.mark.slow
def test_ds_serve_sigterm_graceful_drain(tmp_path):
    """bin/ds_serve under SIGTERM: in-flight requests drain within the
    grace budget, the still-queued remainder lands as distinct `shed`
    rows, and the process exits 0 with the summary line intact."""
    import os
    import signal as _signal
    import subprocess
    import sys
    import time as _time

    reqs = tmp_path / "reqs.jsonl"
    with open(reqs, "w") as f:
        for _ in range(6):
            f.write(json.dumps({"prompt": list(range(5)),
                                "max_new_tokens": 400}) + "\n")
    out_path = tmp_path / "out.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu", DS_PREEMPTION_GRACE_S="60")
    proc = subprocess.Popen(
        [sys.executable, "bin/ds_serve", "--model", "gpt2-tiny",
         "--input", str(reqs), "--output", str(out_path), "--stream",
         "--num-slots", "2", "--num-pages", "64", "--page-size", "16",
         "--max-new-tokens", "400"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    # SIGTERM once the server is mid-stream (first token written)
    deadline = _time.monotonic() + 600
    while _time.monotonic() < deadline:
        if out_path.exists() and '"token"' in out_path.read_text():
            break
        if proc.poll() is not None:
            raise AssertionError(f"ds_serve died early: "
                                 f"{proc.stderr.read()}")
        _time.sleep(0.2)
    proc.send_signal(_signal.SIGTERM)
    rc = proc.wait(timeout=300)
    assert rc == 0, proc.stderr.read()
    rows = [json.loads(x) for x in out_path.read_text().splitlines()]
    results = [r for r in rows if "status" in r]
    assert len(results) == 6
    by_status = {}
    for r in results:
        by_status.setdefault(r["status"], []).append(r)
    # slots were busy with 2 requests; the queued remainder must be
    # SHED with the drain reason — not silently dropped, not "failed"
    assert len(by_status.get("shed", [])) >= 1
    assert all("drain" in r["error"] for r in by_status["shed"])
    assert not by_status.get("failed")
    summary = [r for r in rows if "summary" in r]
    assert summary and summary[0]["health"]["draining"] is True


# --------------------------------------------- review-caught regressions


def test_rolling_restart_reclaims_prefix_cache_from_shared_pool(engine):
    """Review-caught leak: restart() must reclaim the outgoing
    scheduler's prefix-cache pages — in a disaggregated group the pool
    is SHARED, so pages an abandoned scheduler still references would
    never recycle and the group would march to exhaustion one rolling
    restart at a time."""
    rng = np.random.default_rng(6)
    head = rng.integers(0, 256, 17).astype(np.int32)
    reps = make_disaggregated_group(
        engine, num_prefill=1, num_decode=1, num_pages=32, page_size=16,
        num_slots=3, max_pages_per_slot=8, prefill_chunk=8,
        prefix_cache=True)
    router = ClusterRouter(reps)
    pool = reps[0].group.pool
    for round_ in range(3):
        entries = [router.submit(
            np.concatenate([head, rng.integers(0, 256, 3).astype(
                np.int32)]), max_new_tokens=4) for _ in range(3)]
        router.run()
        assert all(e.state == "finished" for e in entries)
        router.rolling_restart()
        # every restart wiped both schedulers: the shared pool must be
        # FULLY free again (cached pages reclaimed, not stranded)
        assert pool.free_pages == pool.num_pages, \
            (round_, pool.free_pages, pool.num_pages)


def test_oversize_prompt_fails_fast_not_capacity_shed(engine):
    """Review-caught misclassification: a submit validation error
    (oversize prompt) is permanent — the router must fail the request
    with the real message instead of burning the retry budget and
    labeling it a capacity shed."""
    reps = make_local_fleet(engine, 2, **CFG)
    router = ClusterRouter(reps)
    huge = np.zeros(CFG["max_pages_per_slot"] * CFG["page_size"] + 8,
                    np.int32)
    entry = router.submit(huge, max_new_tokens=8)
    router.run()
    assert entry.state == "failed", (entry.state, entry.error)
    assert "per-slot capacity" in entry.error
    assert router.health()["retries"] == 0, \
        "a permanent validation error must not burn backoff retries"


def test_remote_handle_cancel_survives_broken_pipe():
    """Review-caught: cancel() through a dead worker pipe must stay a
    no-raise no-op (the heartbeat pass owns the death), so
    router.cancel keeps its idempotence contract mid-crash."""
    from deepspeed_tpu.serving.cluster.replica import (ReplicaKilled,
                                                       _RemoteHandle)

    class _BrokenPipeReplica:
        def _send(self, op):
            raise ReplicaKilled("pipe broken")

    h = _RemoteHandle("w0", None, _BrokenPipeReplica())
    h.cancel()   # must not raise
    assert h.state == "running"
