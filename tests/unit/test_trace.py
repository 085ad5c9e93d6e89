"""End-to-end request tracing + flight recorder (serving/trace.py).

The two acceptance pins:

* **Zero-cost-when-off** — with tracing disabled the scheduler runs the
  byte-identical loop: same tokens, same compile counts, nothing
  recorded (the shared NULL_TRACER).
* **Failover oracle with tracing on** — a replica killed mid-stream
  yields a merged fleet trace that loads as valid Chrome-trace JSON in
  which the killed replica's spans and the survivor's replay spans
  share the journal rid with an explicit flow link, the flight-recorder
  dump correlates with the journal entries that were in flight, and
  every output stays token-exact vs ``generate()``.
"""

import json

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving import (ClusterRouter, FlightRecorder,
                                   ServingScheduler, SpanTracer,
                                   make_local_fleet, prometheus_text)
from deepspeed_tpu.serving.trace import EVENT_TAXONOMY, NULL_TRACER

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


def _serve(engine, prompts, max_new, tracer=None, **kw):
    sched = ServingScheduler(engine, tracer=tracer, **CFG, **kw)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    return sched, reqs, got


def _chrome_ok(trace):
    """Structural validity of a Chrome-trace JSON object: it must
    round-trip through json and every event must carry the fields the
    Perfetto/catapult loaders key on."""
    trace = json.loads(json.dumps(trace))   # JSON-serializable
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert isinstance(e["name"], str)
        assert e["ph"] in ("X", "i", "s", "f", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    # process/thread metadata names the tracks
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in evs)
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in evs)
    return evs


# ------------------------------------------------- zero cost when off


def test_tracing_off_is_zero_cost(engine):
    """The pin: tracing disabled leaves tokens AND compile signatures
    byte-identical, and records nothing anywhere (NULL_TRACER)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, 7).astype(np.int32) for _ in range(4)]
    max_new = [6, 5, 6, 5]
    want = _oracle(engine, prompts, max_new)

    sched_off, reqs_off, got_off = _serve(engine, prompts, max_new)
    assert sched_off.tracer is NULL_TRACER
    assert len(NULL_TRACER.events) == 0

    def compiles():
        return (engine.serving_decode_multi_compile_count(),
                engine.serving_decode_compile_count(),
                engine.serving_verify_compile_count(),
                engine.serving_page_copy_compile_count())
    compiles_after_off = compiles()

    tracer = SpanTracer(process="t")
    sched_on, reqs_on, got_on = _serve(engine, prompts, max_new,
                                       tracer=tracer)
    compiles_after_on = compiles()

    for r_off, r_on, w in zip(reqs_off, reqs_on, want):
        assert r_off.out_tokens == w, "untraced run must match generate()"
        assert r_on.out_tokens == w, "traced run must match generate()"
    # tracing is host-only: the traced run may not add ONE signature
    assert compiles_after_on == compiles_after_off
    assert tracer.events, "the traced run must actually record spans"


def test_null_tracer_is_shared_and_inert(engine):
    s1 = ServingScheduler(engine, **CFG)
    s2 = ServingScheduler(engine, **CFG)
    assert s1.tracer is s2.tracer is NULL_TRACER
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x"):    # the no-op context manager
        pass
    NULL_TRACER.instant("x")
    NULL_TRACER.complete("x", 0.0, 1.0)
    NULL_TRACER.flow("s", "id", "x")
    assert len(NULL_TRACER.events) == 0


# ------------------------------------------------------- span model


def test_lifecycle_spans_and_chrome_export(engine):
    """One traced run produces the documented lifecycle phases and a
    structurally valid Chrome-trace export with slot tracks."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, 12).astype(np.int32)
               for _ in range(3)]
    max_new = [6, 6, 6]
    want = _oracle(engine, prompts, max_new)
    tracer = SpanTracer(process="serve0")
    sched, reqs, got = _serve(engine, prompts, max_new, tracer=tracer)
    for r, w in zip(reqs, want):
        assert r.out_tokens == w

    names = {e[1] for e in tracer.events}
    for must in ("queued", "prefill_chunk", "horizon_dispatch",
                 "device_wait", "harvest", "decode_burst", "request"):
        assert must in names, f"missing lifecycle span {must}"

    evs = _chrome_ok(tracer.to_chrome())
    # one track per slot: decode bursts land on distinct slot tids
    burst_tids = {e["tid"] for e in evs if e["name"] == "decode_burst"}
    assert len(burst_tids) >= 2
    # per-request spans are rid-keyed and terminal-stated
    req_spans = [e for e in evs if e["name"] == "request"]
    assert {e["args"]["rid"] for e in req_spans} == \
        {r.rid for r in reqs}
    assert all(e["args"]["state"] == "finished" for e in req_spans)
    # the queue-wait phase closes at admission with a real duration
    assert all(e["dur"] >= 0 for e in evs
               if e["name"] == "queued" and e["ph"] == "X")


def test_prefix_and_cow_spans(engine):
    """A full-page cache hit emits prefix_hit; a partial-page hit pays
    (and records) the copy-on-write page copy."""
    rng = np.random.default_rng(2)
    base = rng.integers(0, 256, 20).astype(np.int32)
    tracer = SpanTracer(process="serve0")
    sched = ServingScheduler(engine, prefix_cache=True, tracer=tracer,
                             **CFG)
    r1 = sched.submit(base, max_new_tokens=5)
    sched.run()
    # full-page reuse: same first 16-token page + distinct tail
    r2 = sched.submit(np.concatenate(
        [base[:16], rng.integers(0, 256, 4).astype(np.int32)]),
        max_new_tokens=4)
    sched.run()
    # partial-page reuse: 8 tokens into the cached page -> COW copy
    r3 = sched.submit(np.concatenate(
        [base[:8], rng.integers(0, 256, 6).astype(np.int32)]),
        max_new_tokens=4)
    sched.run()
    assert r1.state == r2.state == r3.state == "finished"
    names = [e[1] for e in tracer.events]
    assert "prefix_hit" in names
    assert "cow_copy" in names
    hit = next(e for e in tracer.serialized()
               if e["name"] == "prefix_hit")
    assert hit["args"]["cached_tokens"] >= 8


def test_spec_round_spans(engine):
    """Speculative rounds emit propose/verify-dispatch spans and the
    per-slot spec_round bursts, token-exact as ever."""
    rng = np.random.default_rng(3)
    motif = rng.integers(0, 256, 4).astype(np.int32)
    prompts = [np.concatenate([np.tile(motif, 3),
                               rng.integers(0, 256, 4).astype(np.int32)])]
    want = _oracle(engine, prompts, [12])
    tracer = SpanTracer(process="serve0")
    sched, reqs, got = _serve(engine, prompts, [12], tracer=tracer,
                              spec_decode="ngram", spec_k=4)
    assert reqs[0].out_tokens == want[0]
    names = {e[1] for e in tracer.events}
    assert "spec_propose" in names
    assert "spec_verify_dispatch" in names
    assert "spec_round" in names


def test_trace_ctx_propagates_journal_rid(engine):
    """submit(trace_ctx=...) overrides the span identity: spans carry
    the cluster-level trace id instead of the local rid."""
    tracer = SpanTracer(process="serve0")
    sched = ServingScheduler(engine, tracer=tracer, **CFG)
    req = sched.submit(np.zeros(5, np.int32), max_new_tokens=3,
                       trace_ctx={"trace_id": "client-42", "attempt": 0})
    assert req.trace_rid == "client-42"
    sched.run()
    rids = {e[6] for e in tracer.events if e[6] is not None}
    assert rids == {"client-42"}


# -------------------------------------------------- failover oracle


def test_failover_trace_rid_link_and_flight_record(engine, tmp_path):
    """The acceptance oracle, tracing flavor: 3 traced replicas serving
    mixed prefix-shared + spec traffic, replica0 killed mid-stream via
    the fault point.  Assert (a) everything stays token-exact vs
    generate(), (b) the merged fleet trace is valid Chrome JSON in
    which the killed replica's spans and the survivor's replay spans
    share the rid with an explicit s/f flow link, and (c) the
    flight-recorder dump correlates with the journal entries that were
    in flight on the dead replica."""
    rng = np.random.default_rng(4)
    head = rng.integers(0, 256, 11).astype(np.int32)
    prompts, max_new = [], []
    for _ in range(4):
        prompts.append(np.concatenate(
            [head, rng.integers(0, 256, 5).astype(np.int32)]))
        max_new.append(int(rng.integers(5, 9)))
    motif = rng.integers(0, 256, 4).astype(np.int32)
    prompts.append(np.concatenate(
        [np.tile(motif, 3), rng.integers(0, 256, 4).astype(np.int32)]))
    max_new.append(12)
    want = _oracle(engine, prompts, max_new)

    reps = make_local_fleet(engine, 3, prefix_cache=True,
                            spec_decode="ngram", spec_k=4, **CFG)
    tracer = SpanTracer(process="router")
    flight = FlightRecorder(str(tmp_path / "flight"))
    router = ClusterRouter(reps, tracer=tracer, flight_recorder=flight)
    inj = faults.FaultInjector(seed=0)
    plan = inj.on("cluster.replica_kill", match={"replica": "replica0"},
                  step=2, exc=RuntimeError("chaos"))
    with faults.injected(inj):
        entries = [router.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, max_new)]
        got = router.run()
    assert plan.fired == 1
    h = router.health()
    assert h["failovers"] == 1 and h["replays"] >= 1 and h["failed"] == 0
    for e, w in zip(entries, want):
        assert e.state == "finished" and got[e.rid] == w, \
            (e.rid, e.state, e.replica_history)

    # (b) merged fleet trace: valid, rid-linked across processes
    trace_path = router.dump_trace(str(tmp_path / "fleet_trace.json"))
    evs = _chrome_ok(json.load(open(trace_path)))
    pname = {e["args"]["name"]: e["pid"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "replica0" in pname, "the dead replica must be in the trace"
    replayed = [e for e in entries if e.replays > 0]
    assert replayed
    for entry in replayed:
        rid_evs = [e for e in evs
                   if e.get("args", {}).get("rid") == entry.rid]
        pids = {e["pid"] for e in rid_evs}
        assert pname["replica0"] in pids, \
            "the killed replica's spans must carry the rid"
        survivors = [pname[r] for r in entry.replica_history[1:]]
        assert any(p in pids for p in survivors), \
            "the survivor's replay spans must carry the same rid"
        flows = [e for e in evs
                 if e.get("id") == f"replay:{entry.rid}:1"]
        assert {e["ph"] for e in flows} == {"s", "f"}, \
            "the replay must be explicitly flow-linked"
        s_ev = next(e for e in flows if e["ph"] == "s")
        f_ev = next(e for e in flows if e["ph"] == "f")
        assert s_ev["pid"] == pname["replica0"]
        assert f_ev["pid"] != s_ev["pid"]
    assert any(e["name"] == "replica_death" for e in evs)

    # (c) the flight record correlates with the journal
    assert flight.dumps, "replica death must trigger a dump"
    rec = json.load(open(flight.dumps[0]))
    assert rec["reason"].startswith("replica_death:replica0")
    dumped_rids = {s["rid"] for s in rec["journal_entry"]}
    assert dumped_rids, "the in-flight journal entries ride the dump"
    assert dumped_rids <= {e.rid for e in entries}
    assert {e.rid for e in replayed} <= dumped_rids
    _chrome_ok(rec["trace"])
    # ...and the journal dump round-trips with the replay recorded
    router.journal.dump(str(tmp_path / "journal.json"))
    jd = json.loads((tmp_path / "journal.json").read_text())
    assert {s["rid"] for s in jd["entries"] if s["replays"]} == \
        {e.rid for e in replayed}


@pytest.mark.slow
def test_process_replica_sigkill_trace(engine, tmp_path):
    """The real thing, traced: two worker PROCESSES with span tracing
    over the JSONL protocol, one SIGKILLed mid-stream.  The merged
    fleet trace holds the dead worker's flushed spans (carrying the
    journal rids), the router's death/replay spans, and the flow link;
    outputs stay token-exact vs generate()."""
    from deepspeed_tpu.serving import ProcessReplica

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(4)]
    max_new = [24] * 4
    want = _oracle(engine, prompts, max_new)
    reps = [ProcessReplica(f"proc{i}", model="gpt2-tiny",
                           term_grace_s=5.0, trace=True)
            for i in range(2)]
    try:
        for rep in reps:
            rep.wait_ready()
        tracer = SpanTracer(process="router")
        flight = FlightRecorder(str(tmp_path / "flight"))
        router = ClusterRouter(reps, heartbeat_misses=1, tracer=tracer,
                               flight_recorder=flight)
        entries = [router.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, max_new)]
        import time as _time
        deadline = _time.monotonic() + 600
        while _time.monotonic() < deadline:
            router.step()
            if sum(len(e.emitted) for e in entries) >= 2:
                break
            _time.sleep(0.05)
        assert sum(len(e.emitted) for e in entries) >= 2
        victim = next(r for r in reps if r.load() > 0)
        victim.kill()
        got = router.run(max_steps=200000)
        h = router.health()
        assert h["failovers"] == 1 and h["failed"] == 0
        for e, w in zip(entries, want):
            assert e.state == "finished" and got[e.rid] == w, \
                (e.rid, e.state, e.replica_history)

        evs = _chrome_ok(router.fleet_trace())
        pname = {e["args"]["name"]: e["pid"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        # worker-side spans made it across the process boundary with
        # the journal rid (the trace ctx rode the submit op)
        worker_spans = [e for e in evs
                       if e["pid"] in (pname.get("proc0"),
                                       pname.get("proc1"))
                       and e.get("args", {}).get("rid") is not None]
        assert worker_spans, "worker spans must reach the router"
        assert {e["args"]["rid"] for e in worker_spans} <= \
            {e.rid for e in entries}
        assert any(e["name"] == "replica_death" for e in evs)
        replayed = [e for e in entries if e.replays > 0]
        assert replayed
        for entry in replayed:
            flows = [e for e in evs
                     if e.get("id") == f"replay:{entry.rid}:1"]
            assert {e["ph"] for e in flows} == {"s", "f"}
        assert flight.dumps, "the SIGKILL death must trigger a dump"
        rec = json.load(open(flight.dumps[0]))
        assert {s["rid"] for s in rec["journal_entry"]} <= \
            {e.rid for e in entries}
    finally:
        for rep in reps:
            rep.die("test teardown")


# ------------------------------------------------- flight recorder


def test_flight_recorder_fault_trigger_and_bounds(engine, tmp_path):
    """A fault point actually firing auto-dumps the recent-span window;
    the recorder is bounded (limit files, then counted skips) and the
    span ring is bounded (dropped counter)."""
    tracer = SpanTracer(process="serve0", capacity=8)
    flight = FlightRecorder(str(tmp_path), limit=1)
    flight.register("serve0", tracer)
    flight.arm_fault_observer()
    try:
        sched = ServingScheduler(engine, tracer=tracer, **CFG)
        inj = faults.FaultInjector(seed=0)
        inj.on("serve.step", steps=(1, 2), times=2,
               action=lambda ctx: None)
        with faults.injected(inj):
            for _ in range(3):
                sched.submit(np.zeros(5, np.int32), max_new_tokens=16)
            sched.run()
    finally:
        flight.disarm_fault_observer()
    assert flight.count == 1 and flight.skipped == 1, \
        "2 firings, limit 1: one dump + one counted skip"
    rec = json.load(open(flight.dumps[0]))
    assert rec["reason"] == "fault:serve.step"
    assert rec["extra"]["ctx"]["step"] == 1
    # the ring is bounded: far more than 8 events were recorded
    assert len(tracer.events) <= 8 and tracer.dropped > 0


def test_flight_recorder_observer_never_breaks_faults(engine):
    """An exploding observer must not alter fault semantics: the fired
    plan's action still runs, nothing leaks out of the loop, and a
    raising plan still raises into the containment path."""
    def bomb(point, ctx):
        raise RuntimeError("observer bug")
    faults.observe(bomb)
    try:
        sched = ServingScheduler(engine, **CFG)
        inj = faults.FaultInjector(seed=0)
        benign = inj.on("serve.step", nth=1, action=lambda ctx: None)
        raising = inj.on("serve.request", nth=1, exc=RuntimeError("x"))
        with faults.injected(inj):
            req = sched.submit(np.zeros(5, np.int32), max_new_tokens=3)
            sched.run()
        assert benign.fired == 1 and raising.fired == 1
        # the raising plan's containment still classified the request
        assert req.state == "failed" and "x" in req.error
    finally:
        faults.unobserve(bomb)


# -------------------------------------------- telemetry exposition


def test_prometheus_text_exposition(engine):
    rng = np.random.default_rng(6)
    sched, _, _ = _serve(engine,
                         [rng.integers(0, 256, 5).astype(np.int32)], [3])
    text = prometheus_text(sched.health(), prefix="ds_serving",
                           labels={"replica": "r0"})
    lines = [ln for ln in text.splitlines() if ln]
    # every sample line: name{labels} value, preceded by a TYPE line
    samples = [ln for ln in lines if not ln.startswith("#")]
    assert samples
    for ln in samples:
        name, val = ln.rsplit(" ", 1)
        assert name.endswith('{replica="r0"}')
        float(val)                      # numeric
    assert any("ds_serving_completed" in ln for ln in samples)
    assert any("ds_serving_uptime_s" in ln for ln in samples)
    assert any("ds_serving_steps_per_s" in ln for ln in samples)
    # booleans export as 0/1; strings/None/nested are skipped
    assert any(ln.startswith("ds_serving_tracing") for ln in samples)
    assert not any("last_error" in ln for ln in samples)
    assert not any("spec_decode{" in ln for ln in samples)
    # summary() percentiles export the same way
    stext = prometheus_text(sched.summary())
    assert "ds_serving_ttft_ms_p50" in stext


def test_health_uptime_and_steps_per_s(engine):
    import time as _time
    sched = ServingScheduler(engine, **CFG)
    h0 = sched.health()
    assert h0["uptime_s"] >= 0 and h0["steps_per_s"] == 0.0
    sched.submit(np.zeros(5, np.int32), max_new_tokens=3)
    sched.run()
    _time.sleep(0.01)
    h1 = sched.health()
    assert h1["uptime_s"] > h0["uptime_s"]
    assert h1["steps_per_s"] > 0.0
    # steps_per_s is computed from the UNROUNDED uptime while uptime_s
    # reports 3 decimals — with a tiny uptime the reconstruction error
    # is bounded by the rounding half-ulp, not a fixed constant (the
    # old flat 0.5 bound flaked whenever uptime landed near 40ms)
    tol = h1["steps_per_s"] * 0.0005 / max(h1["uptime_s"] - 0.0005,
                                           1e-6) + 0.01
    assert abs(h1["steps_per_s"] - h1["step"] / h1["uptime_s"]) < tol


def test_live_loop_emits_only_documented_tags(engine):
    """End-to-end taxonomy pin over a REAL serving run with the
    optional subsystems (prefix cache + spec decode) engaged."""
    from deepspeed_tpu.monitor.monitor import RingBufferMonitor
    rb = RingBufferMonitor(maxlen=8192)
    sched = ServingScheduler(engine, prefix_cache=True,
                             spec_decode="ngram", spec_k=4, monitor=rb,
                             **CFG)
    rng = np.random.default_rng(7)
    for _ in range(3):
        sched.submit(rng.integers(0, 256, 7).astype(np.int32),
                     max_new_tokens=8)
    sched.run()
    emitted = {tag for tag, _, _ in rb.events}
    assert emitted <= set(EVENT_TAXONOMY), \
        emitted - set(EVENT_TAXONOMY)
    assert all(step >= 1 for _, _, step in rb.events)


# ------------------------------------------------- phases of one step()
# (tracing.Phases: one primitive feeds a device profile, summary() and
# the SpanTracer)

DEPTH_ONE = ("chain", "device_wait", "harvest", "sweep", "admit",
             "prefill", "spec_dispatch", "horizon_dispatch", "observe")
NEW_KEYS = tuple(f"phase_{k}_s" for k in DEPTH_ONE) + (
    "phase_first_token_wait_s", "step_wall_s", "host_busy_frac",
    "first_token_wait_frac", "queue_wait_ms_p50", "queue_wait_ms_p90",
    "slow_steps", "slow_step_max_s", "slow_step_max_blocked_s")
# the span names a plain traced run recorded before the phases, and the
# ones the phases add
OLD_SPANS = {"queued", "prefill_chunk", "horizon_dispatch", "device_wait",
             "harvest", "decode_burst", "request"}
NEW_SPANS = {"step", "chain", "sweep", "admit", "prefill",
             "first_token_wait", "first_token", "observe"}


def _workload(seed, n=4):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 256, 7).astype(np.int32) for _ in range(n)]
    return prompts, [6, 5, 6, 5][:n]


def test_summary_holds_the_phase_counters_with_tracing_off(engine):
    import time as _time
    prompts, max_new = _workload(0)
    _serve(engine, prompts, max_new)      # a step that compiles is slow
    sched = ServingScheduler(engine, **CFG)
    # a client that takes 2 ms a token: the steps are long beside the
    # few microseconds between two phases, whatever the machine does
    reqs = [sched.submit(p, max_new_tokens=m,
                         on_token=lambda req, tok: _time.sleep(0.002))
            for p, m in zip(prompts, max_new)]
    sched.run()
    assert sched.tracer is NULL_TRACER and len(NULL_TRACER.events) == 0
    s = sched.summary()
    for key in NEW_KEYS:
        assert isinstance(s[key], (int, float)), key
    wall = s["step_wall_s"]
    assert wall > 0
    # exhaustive and disjoint at depth one: the phases are the step
    assert sum(s[f"phase_{k}_s"] for k in DEPTH_ONE) == \
        pytest.approx(wall, rel=0.02)
    # the host's share and the two waits' shares are the whole
    assert s["host_busy_frac"] + (s["phase_device_wait_s"] +
                                  s["phase_first_token_wait_s"]) / wall \
        == pytest.approx(1.0, abs=2e-4)
    assert s["first_token_wait_frac"] == pytest.approx(
        s["phase_first_token_wait_s"] / wall, abs=1e-4)
    assert s["phase_first_token_wait_s"] > 0     # four prompts finished
    assert s["phase_first_token_wait_s"] <= s["phase_prefill_s"]
    # device_wait_frac keeps its definition: the harvest's pull over the
    # steps' wall up to the bookkeeping
    m = sched.metrics
    assert s["device_wait_frac"] == round(
        m.device_wait_s / (m.device_wait_s + m.host_s), 4)
    assert m.device_wait_s == pytest.approx(s["phase_device_wait_s"],
                                            abs=1e-5)
    # every request was admitted once, from a queue it hardly stood in
    assert len(m.queue_wait_s) == len(reqs)
    assert 0 <= s["queue_wait_ms_p50"] <= s["queue_wait_ms_p90"] \
        <= s["ttft_ms_p99"]
    assert s["slow_steps"] == 0 and 0 < s["slow_step_max_s"] < 1.0
    assert sched.health()["slow_steps"] == []


def test_phases_feed_the_span_tracer_under_the_old_names(engine):
    """Tokens and compile counts are those of the untraced run, and the
    recorded names are the old set plus the new phases."""
    prompts, max_new = _workload(0)
    want = _oracle(engine, prompts, max_new)
    _serve(engine, prompts, max_new)

    def compiles():
        return (engine.serving_decode_multi_compile_count(),
                engine.serving_prefill_compile_count(),
                engine.serving_decode_compile_count(),
                engine.serving_verify_compile_count(),
                engine.serving_page_copy_compile_count())
    before = compiles()
    tracer = SpanTracer(process="t")
    sched, reqs, _ = _serve(engine, prompts, max_new, tracer=tracer)
    assert compiles() == before
    assert [r.out_tokens for r in reqs] == want
    assert {e[1] for e in tracer.events} == OLD_SPANS | NEW_SPANS
    by_name = {}
    for e in tracer.events:
        by_name.setdefault(e[1], []).append(e)
    # the four sites keep their category, track and args
    assert {(e[2], e[5]) for e in by_name["device_wait"]} == \
        {("device", "device")}
    assert {(e[2], e[5]) for e in by_name["harvest"]} == \
        {("dispatch", "scheduler")}
    assert all(set(e[7]) == {"horizon", "spec", "tokens"}
               for e in by_name["harvest"])
    # since PR 47 beside whether admission left requests waiting and the
    # step cost the slot-bound horizon rule read (0.0 un-engaged); since
    # PR 49 beside the decoding slots that rode the prefill dispatch (0
    # while nothing waits); since PR 61 beside the slots launched off the
    # device's copy of a first token the host pulls afterwards
    assert all(set(e[7]) == {"horizon", "slots", "slot_bound", "riders",
                             "ahead", "p_ms", "d_ms"}
               for e in by_name["horizon_dispatch"])
    assert sum(e[7]["ahead"] for e in by_name["horizon_dispatch"]) > 0
    # since PR 59 beside whether the dispatch was launched before the
    # last one's tokens were pulled (0 while nothing waits)
    assert all(set(e[7]) == {"rows", "padded_rows", "tokens", "riders",
                             "lookahead"}
               for e in by_name["prefill_chunk"])
    assert {e[7]["riders"] for name in ("horizon_dispatch", "prefill_chunk")
            for e in by_name[name]} == {0}
    # (1: launched ahead of a horizon's harvest, into a slot whose
    # request that horizon was sure to finish, while the last one waits)
    assert {e[7]["lookahead"] for e in by_name["prefill_chunk"]} <= {0, 1}
    assert sum(e[7]["tokens"] for e in by_name["harvest"]) + \
        len(by_name["request"]) == sum(len(w) for w in want)
    # the tracer's spans and the accumulators are one measurement
    for name in ("device_wait", "admit", "first_token_wait"):
        assert sum(e[4] for e in by_name[name]) == pytest.approx(
            sched.phases.seconds[name], abs=1e-9)
        assert len(by_name[name]) == sched.phases.counts[name]
    # a replica that swaps the scheduler's tracer swaps the phases' too
    other = SpanTracer(process="u")
    sched.tracer = other
    assert sched.phases.tracer is other


def test_the_boundary_phases_open_once_a_dispatch_under_look_ahead(engine):
    """Slot-bound with the ride forced, prefill dispatches stay in
    flight across step boundaries: ``first_token_wait`` and
    ``first_token`` still open once for every dispatch that samples,
    one right after the other, inside ``prefill`` inside ``step`` -- so
    the readers that cut the device's idle time by these names
    (``sched.idle_in_boundary.*``, ``host_busy_frac``) keep reading --
    and a dispatch launched ahead says so (``lookahead=1``)."""
    from tests.unit.test_serving_ride import hold_walls
    rng = np.random.default_rng(7)
    tracer = SpanTracer(process="t")
    sched = ServingScheduler(engine, tracer=tracer, **CFG)
    hold_walls(sched, 0)
    for n, new in [(40, 6), (33, 9), (20, 5), (25, 12), (20, 7), (36, 10),
                   (14, 4), (30, 8)]:
        sched.submit(rng.integers(0, 256, n).astype(np.int32),
                     max_new_tokens=new)
    sched.run()
    s = sched.summary()
    assert s["prefill_lookahead_share"] > 0.3
    by_name = {}
    for e in tracer.events:
        by_name.setdefault(e[1], []).append(e)
    waits, emits = by_name["first_token_wait"], by_name["first_token"]
    chunks = by_name["prefill_chunk"]
    # every dispatch with riders samples; so may one without
    sampling = sum(e[7]["riders"] > 0 for e in chunks)
    assert sampling <= len(waits) == len(emits) <= len(chunks)
    assert sum(e[7]["lookahead"] for e in chunks) == round(
        s["prefill_lookahead_share"] * len(chunks))

    def span(e):
        return e[3], e[3] + e[4]

    def inside(child, parents):
        lo, hi = span(child)
        return any(p_lo <= lo and hi <= p_hi
                   for p_lo, p_hi in map(span, parents))
    for w, f in zip(sorted(waits, key=span), sorted(emits, key=span)):
        assert span(w)[1] <= span(f)[0]         # the pull, then the emit
        assert inside(w, by_name["prefill"]) and \
            inside(f, by_name["prefill"])
    assert all(inside(p, by_name["step"]) for p in by_name["prefill"])
    # the accumulators are the same measurement
    for name in ("first_token_wait", "first_token"):
        assert len(by_name[name]) == sched.phases.counts[name]
    assert s["host_busy_frac"] <= 1.0 and s["first_token_wait_frac"] >= 0.0


def test_a_slow_step_is_recorded_with_its_phase_split(engine):
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger
    prompts, max_new = _workload(3, n=2)
    _serve(engine, prompts, max_new)      # compile outside the record
    tracer = SpanTracer(process="t")
    sched = ServingScheduler(engine, tracer=tracer, **CFG)
    for p, m in zip(prompts, max_new):
        sched.submit(p, max_new_tokens=m)
    inj = faults.FaultInjector(seed=0)
    inj.on("serve.step", step=2, action=faults.sleep_s(1.05))
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    ds_logger.addHandler(handler)    # it does not propagate to caplog
    try:
        with faults.injected(inj):
            sched.run()
    finally:
        ds_logger.removeHandler(handler)
    s = sched.summary()
    assert s["slow_steps"] == 1
    assert 1.05 <= s["slow_step_max_s"] < 2.0
    # the step slept in the fault point: neither blocked nor in a phase
    assert s["slow_step_max_blocked_s"] < 0.5
    (rec,) = sched.health()["slow_steps"]
    assert rec["step"] == 2 and rec["wall_s"] == s["slow_step_max_s"]
    assert rec["other_s"] >= 1.0 and rec["blocked_s"] < 0.5
    assert set(rec["phases_s"]) <= set(DEPTH_ONE) | {
        "prefill_chunk", "first_token_wait", "first_token"}
    assert sum(rec["phases_s"].get(k, 0.0) for k in DEPTH_ONE) == \
        pytest.approx(rec["wall_s"] - rec["other_s"], abs=1e-3)
    slow = [e for e in tracer.events if e[1] == "slow_step"]
    assert len(slow) == 1 and slow[0][0] == "i" and slow[0][7] == rec
    lines = [ln for ln in lines if "slow scheduler step" in ln]
    assert len(lines) == 1 and json.loads(
        lines[0].split("step ", 1)[1]) == rec


# nesting of the annotations in a device profile: child -> parent
NESTING = {
    "ds.sched.chain": "ds.sched.step",
    "ds.sched.device_wait": "ds.sched.step",
    "ds.sched.harvest": "ds.sched.step",
    "ds.sched.sweep": "ds.sched.step",
    "ds.sched.admit": "ds.sched.step",
    "ds.sched.prefill": "ds.sched.step",
    "ds.sched.horizon_dispatch": "ds.sched.step",
    "ds.sched.observe": "ds.sched.step",
    "ds.sched.prefill_chunk": "ds.sched.prefill",
    "ds.sched.first_token_wait": "ds.sched.prefill",
    "ds.sched.first_token": "ds.sched.prefill",
}


def test_the_phases_are_events_of_a_device_profile(engine, tmp_path):
    """jax.profiler with the chip benchmark's own options around three
    steps: every name of the table is in the .xplane.pb, nested as the
    table says, on the profiler's clock."""
    import glob

    import jax
    from jax.profiler import ProfileData
    prompts, max_new = _workload(5, n=3)
    sched = ServingScheduler(engine, **CFG)
    for p in prompts:
        sched.submit(p, max_new_tokens=12)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            sched.step()
    finally:
        jax.profiler.stop_trace()
    sched.run()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    evs = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ds."):
                    s = int(ev.start_ns)
                    evs.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    names = {e[0] for e in evs}
    assert names == set(NESTING) | {"ds.sched.step", "ds.engine.stage",
                                    "ds.engine.launch"}
    assert sum(e[0] == "ds.sched.step" for e in evs) == 3

    def inside(child, parents):
        return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)
    for child, parent in NESTING.items():
        parents = [e for e in evs if e[0] == parent]
        for ev in (e for e in evs if e[0] == child):
            assert inside(ev, parents), (child, parent)
    # the engine's staging and launch lie inside a dispatching phase
    dispatching = [e for e in evs if e[0] in (
        "ds.sched.prefill_chunk", "ds.sched.horizon_dispatch",
        "ds.sched.chain")]
    for ev in (e for e in evs if e[0].startswith("ds.engine.")):
        assert inside(ev, dispatching), ev[0]
    # depth-one phases of one step do not overlap
    one = sorted(e for e in evs if NESTING.get(e[0]) == "ds.sched.step")
    one.sort(key=lambda e: e[1])
    assert all(a[2] <= b[1] for a, b in zip(one, one[1:]))
    # small whole numbers ride as the events' stats
    chunk = next(e for e in evs if e[0] == "ds.sched.prefill_chunk")
    assert {"rows", "padded_rows", "tokens"} <= set(chunk[3])
    launch = {e[3].get("program") for e in evs
              if e[0] == "ds.engine.launch"}
    assert {"prefill", "decode_multi"} <= launch
    disp = [e for e in evs if e[0] == "ds.sched.horizon_dispatch"]
    assert all({"horizon", "slots"} <= set(e[3]) for e in disp)
