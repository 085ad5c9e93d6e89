"""Communication & compile observability (PR 12).

The pins:

* **HLO-ledger exactness** — on the forced 8-device CPU mesh the
  static comm ledger's per-axis byte counts are EXACT against
  hand-derived expectations, twice over: (a) for explicit-collective
  ``shard_map`` programs where every byte is derivable from first
  principles (shapes x ring formulas x scan trip counts), and (b) for
  the real sharded ``decode_multi`` dispatch under the pinned
  ``SERVING_AXIS_RULES`` sharding, where the model-axis rows decompose
  analytically (embedding + per-layer attn/mlp row-parallel psums; the
  vocab-sharded argmax gather pair) and the whole ledger is exactly
  linear in the horizon (everything lives in the scan body).
* **Recompile watchdog acceptance** — an injected steady-state
  signature churn (an off-bucket horizon) fires EXACTLY ONE flight
  dump naming the recompiled function.
* **Zero-cost-when-off** — comm-telemetry-off runs hold the shared
  ``NULL_TRACER``, and off/on runs are token-exact with identical
  compile counts: serving at H in {1, 8} on-mesh, and a supervised
  train run (loss trajectory + compile counts bitwise-identical).
* **One funnel** — the eager comms logger, the tracer spans and the
  monitor routing of ``comm.log_summary`` all describe the same
  events; the legacy print is byte-identical when no monitor sink is
  attached.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.comm.telemetry import (bench_row, wire_bytes,
                                          write_ledger_json)
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.monitor.monitor import RingBufferMonitor
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.profiling import comm_ledger as cl
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.serving import ServingScheduler
from deepspeed_tpu.serving.sharding import SERVING_AXIS_RULES
from deepspeed_tpu.tracing import (EVENT_TAXONOMY, NULL_TRACER,
                                   CompileWatchdog, FlightRecorder,
                                   SpanTracer, jit_cache_size, scope)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual CPU mesh")

MODEL_AX, DATA_AX = 2, 4
CFG = dict(num_slots=8, num_pages=32, page_size=16, max_pages_per_slot=4,
           prefill_chunk=8)


@pytest.fixture(scope="module")
def engine():
    """One sharded engine for the module (model=2 x data=4 — the
    pinned SERVING_AXIS_RULES exercise both axes: kv_heads/vocab over
    `model`, slots over `data`)."""
    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32",
        kv_cache_dtype="float32",
        tensor_parallel={"tp_size": MODEL_AX},
        mesh={"data": DATA_AX, "model": MODEL_AX})
    eng.init_params()
    yield eng
    # leave no module-level observability armed for other test modules
    eng.enable_comm_telemetry(False)
    eng.set_compile_watchdog(None)


def _oracle(engine, prompts, max_new):
    return [
        [int(t) for t in
         engine.generate(p[None], max_new_tokens=m, do_sample=False)[
             0, len(p):]]
        for p, m in zip(prompts, max_new)]


def _serve(engine, prompts, max_new, horizon=8, **kw):
    sched = ServingScheduler(engine, decode_horizon_steps=horizon,
                             **CFG, **kw)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    sched.run()
    return sched, reqs


# --------------------------------------------------- parser unit pins


def test_shape_bytes_and_iota_groups():
    assert cl._shape_bytes("f32[8,8]{1,0}") == 256
    assert cl._shape_bytes("(s32[2,2]{1,0}, f32[4]{0})") == 32
    assert cl._shape_bytes("bf16[3]") == 6
    assert cl._shape_bytes("pred[]") == 1
    # the v2 iota replica-group form: [2,4]<=[4,2]T(1,0) is
    # arange(8).reshape(4,2).T.reshape(2,4)
    assert cl._iota_groups([2, 4], [4, 2], (1, 0)) == \
        [[0, 2, 4, 6], [1, 3, 5, 7]]
    assert cl._iota_groups([4, 2], [8], None) == \
        [[0, 1], [2, 3], [4, 5], [6, 7]]


def test_async_start_collectives_count_once():
    """The async form XLA emits on real TPU meshes: a `-start` op's
    tuple result aliases the operand, so the result bytes must be the
    largest component, not the tuple sum (which would over-report
    all-gather traffic by (1+1/n)x), and the `-done` half must not
    count at all."""
    hlo = """HloModule m

ENTRY %main (p0: f32[8]) -> f32[32] {
  %p0 = f32[8]{0} parameter(0)
  %ags = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %p0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true
  ROOT %agd = f32[32]{0} all-gather-done((f32[8]{0}, f32[32]{0}) %ags)
}
"""
    led = cl.ledger_from_hlo(hlo)
    ag = led["per_op"]["all_gather"]
    assert ag["count"] == 1, "the -done half must not count"
    assert ag["bytes"] == 128                      # the full buffer
    assert ag["wire_bytes"] == int(128 * 3 / 4)    # (n-1)/n * out


def test_wire_byte_formulas():
    # the busbw numerators of the standard ring algorithms
    assert wire_bytes("all_reduce", 1024, 1024, 4) == 2 * 768
    assert wire_bytes("all_gather", 256, 1024, 4) == 768
    assert wire_bytes("reduce_scatter", 1024, 256, 4) == 768
    assert wire_bytes("all_to_all", 1024, 1024, 4) == 768
    assert wire_bytes("collective_permute", 512, 512, 4) == 512
    assert wire_bytes("all_reduce", 1024, 1024, 1) == 0


def test_bench_row_schema():
    row = bench_row("all_reduce", 1 << 20, 0.001, 4, axis="data")
    assert set(row) == {"op", "bytes", "latency_ms", "algbw_gbps",
                       "busbw_gbps", "n", "axis"}
    # calc_bw_log: algbw = 2*size/t, busbw = size/t * 2(n-1)/n
    assert row["algbw_gbps"] == pytest.approx(2 * (1 << 20) / 1e-3 / 1e9,
                                              rel=1e-3)
    # all_gather scales bytes to the full buffer (per-member input)
    g = bench_row("all_gather", 1 << 10, 0.001, 4)
    assert g["bytes"] == (1 << 10) * 4


def test_write_ledger_json_preserves_previous(tmp_path):
    path = str(tmp_path / "ledger.json")
    write_ledger_json(path, {"results": [1]})
    write_ledger_json(path, {"results": [2]})
    got = json.load(open(path))
    assert got["schema"] == "comm-ledger/v1"
    assert got["results"] == [2]
    assert got["previous_committed"]["results"] == [1]
    # one level deep only — no unbounded history chain
    assert "previous_committed" not in got["previous_committed"]


# ------------------------------- explicit-collective exactness oracle


def test_explicit_collective_ledger_exact():
    """Hand-derived exactness on programs whose every collective is
    written in source: shapes x the documented wire formulas x the
    scan trip count — the parser, the while-loop multiplier and the
    axis attribution have nowhere to hide."""
    mesh = make_mesh(MeshConfig(data=DATA_AX, model=MODEL_AX))
    dist.set_mesh(mesh)
    H = 5
    x = jnp.ones((8, 16), jnp.float32)     # per-data-shard [2,16] = 128B

    def scanned(v):
        def step(c, _):
            # one model-axis psum per step, data-dependent so nothing
            # folds away
            return dist.all_reduce(c + 1.0, group="model"), ()
        out, _ = lax.scan(step, v, None, length=H)
        return out

    f = jax.jit(jax.shard_map(scanned, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
    led = cl.ledger_from_hlo(f.lower(x).compile().as_text(), mesh=mesh)
    shard_bytes = 2 * 16 * 4                      # [2,16] f32
    n = MODEL_AX
    per = wire_bytes("all_reduce", shard_bytes, shard_bytes, n)
    assert led["per_axis_op"]["model"]["all_reduce"]["count"] == H
    assert led["per_axis"]["model"] == H * per
    assert led["per_tier"] == {"ici": H * per, "dcn": 0}
    assert led["unknown_trip_counts"] == 0

    def gathered(v):
        return lax.all_gather(v * 1.5, "data", tiled=True)

    g = jax.jit(jax.shard_map(gathered, mesh=mesh, in_specs=P("data"),
                              out_specs=P(), check_vma=False))
    led = cl.ledger_from_hlo(g.lower(x).compile().as_text(), mesh=mesh)
    # operand = the [2,16] shard, output = the full [8,16] buffer
    per = wire_bytes("all_gather", shard_bytes, shard_bytes * DATA_AX,
                     DATA_AX)
    assert led["per_axis_op"]["data"]["all_gather"]["count"] == 1
    assert led["per_axis"]["data"] == per
    assert per == int(shard_bytes * DATA_AX * (DATA_AX - 1) / DATA_AX)

    perm = [(i, (i + 1) % DATA_AX) for i in range(DATA_AX)]

    def ring(v):
        def step(c, _):
            return lax.ppermute(c * 1.0001, "data", perm), ()
        out, _ = lax.scan(step, v, None, length=H)
        return out

    r = jax.jit(jax.shard_map(ring, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
    led = cl.ledger_from_hlo(r.lower(x).compile().as_text(), mesh=mesh)
    pa = led["per_axis_op"]["data"]["collective_permute"]
    assert pa["count"] == H
    assert led["per_axis"]["data"] == H * shard_bytes


# ------------------------------------ decode_multi exactness oracle


def test_decode_multi_ledger_oracle(engine):
    """THE acceptance oracle: per-axis byte counts of the sharded
    decode_multi dispatch, exact against a hand-derived expectation
    for the pinned SERVING_AXIS_RULES sharding.

    Derivation (gpt2-tiny: L layers, E embed, fp32; mesh model=n_m,
    data=n_d; S slots so S_l = S/n_d slots per data shard; horizon H —
    every collective lives in the scan body, trip count H):

    * **model-axis all-reduces** — the row-parallel psums GSPMD emits
      where a weight's contracted dim is model-sharded: the vocab-
      sharded embedding gather (1) + attention out-projection (1) +
      MLP down-projection (1) per layer = ``H * (2L + 1)`` psums of
      one token row per local slot ``[S_l, 1, E] f32``, each moving
      ``2(n_m-1)/n_m * S_l*E*4`` wire bytes.
    * **model-axis all-gathers** — the greedy argmax over
      vocab-sharded logits gathers the per-shard (max, argmax) pair:
      ``H * 2`` gathers of ``[S_l, n_m]`` (f32 + s32), each
      ``(n_m-1)/n_m * S_l*n_m*4`` wire bytes.
    * **linearity** — the whole per-(axis, op) ledger scales exactly
      with H (nothing outside the scan), pinned by comparing H=4
      against scale_ledger(H=2, x2).
    """
    assert dict(SERVING_AXIS_RULES)["kv_heads"] == "model"
    assert dict(SERVING_AXIS_RULES)["slots"] == "data"
    cfg = engine.module.cfg
    L, E = cfg.num_layers, cfg.hidden_size
    S_l = CFG["num_slots"] // DATA_AX
    n_m = MODEL_AX

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, 5).astype(np.int32)
               for _ in range(3)]
    sched4, _ = _serve(engine, prompts, [6, 6, 6], horizon=4,
                       comm_telemetry=True)
    ledgers = sched4.comm_ledger()
    led4 = ledgers["decode_multi[h=4]"]
    H = 4

    psum_payload = S_l * 1 * E * 4
    psum_wire = wire_bytes("all_reduce", psum_payload, psum_payload,
                           n_m)
    ar = led4["per_axis_op"]["model"]["all_reduce"]
    assert ar["count"] == H * (2 * L + 1)
    assert ar["wire_bytes"] == H * (2 * L + 1) * psum_wire

    gather_out = S_l * n_m * 4
    gather_wire = wire_bytes("all_gather", S_l * 1 * 4, gather_out, n_m)
    ag = led4["per_axis_op"]["model"]["all_gather"]
    assert ag["count"] == H * 2
    assert ag["wire_bytes"] == H * 2 * gather_wire

    # the slot-sharded paged-KV traffic rides the data axis (gather/
    # scatter of data-sharded tables into the data-replicated pools)
    assert led4["per_axis"].get("data", 0) > 0
    # single-process CPU mesh: everything is ICI tier, exactly
    assert led4["per_tier"]["dcn"] == 0
    assert led4["per_tier"]["ici"] == led4["wire_bytes"]
    assert led4["unknown_trip_counts"] == 0

    # exact horizon linearity: H=4 == 2 x (H=2), per (axis, op)
    sched2, _ = _serve(engine, prompts, [6, 6, 6], horizon=2,
                       comm_telemetry=True)
    led2 = sched2.comm_ledger()["decode_multi[h=2]"]
    assert cl.scale_ledger(led2, 2)["per_axis_op"] == \
        led4["per_axis_op"]
    engine.enable_comm_telemetry(False)
    engine.set_compile_watchdog(None)


def test_comm_health_fields_and_gauges(engine):
    rb = RingBufferMonitor(maxlen=4096)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, 5).astype(np.int32)
               for _ in range(2)]
    sched = ServingScheduler(engine, decode_horizon_steps=4,
                             comm_telemetry=True, monitor=rb, **CFG)
    for p in prompts:
        sched.submit(p, max_new_tokens=4)
    sched.run()
    h0 = sched.health()
    assert h0["comm_telemetry"] is True
    assert h0["comm_bytes_per_step"] is None, \
        "health() must never pay the analysis compile itself"
    sched.comm_ledger()
    h = sched.health()
    assert h["comm_bytes_per_step"] > 0
    assert h["comm_ici_bytes_per_step"] == h["comm_bytes_per_step"]
    assert h["comm_dcn_bytes_per_step"] == 0
    assert set(h["comm_axis_bytes"]) >= {"model", "data"}
    # bytes/token = bytes/step over (horizon x num_slots) — one
    # decode_multi dispatch serves every slot for `horizon` steps
    assert h["comm_bytes_per_token"] == pytest.approx(
        h["comm_bytes_per_step"]
        / (sched._comm_summary["horizon"] * CFG["num_slots"]), abs=0.5)
    emitted = {tag for tag, _, _ in rb.events
               if tag.startswith("serving/comm/")}
    assert {"serving/comm/bytes_per_step",
            "serving/comm/bytes_per_token",
            "serving/comm/collectives_per_step",
            "serving/comm/ici_bytes_per_step",
            "serving/comm/axis/model",
            "serving/comm/axis/data"} <= emitted
    assert emitted <= set(EVENT_TAXONOMY)
    engine.enable_comm_telemetry(False)
    engine.set_compile_watchdog(None)


# --------------------------------------------- zero cost when off


def test_comm_telemetry_off_is_zero_cost_serving(engine):
    """The pin, serving half: off runs hold NULL_TRACER, and off/on
    runs are token-exact with identical compile counts at H in
    {1, 8} on the mesh — capture, watchdog AND the post-hoc ledger
    analysis add no jit signatures."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, 7).astype(np.int32)
               for _ in range(4)]
    max_new = [6, 5, 6, 5]
    want = _oracle(engine, prompts, max_new)

    def compiles():
        return (engine.serving_decode_multi_compile_count(),
                engine.serving_decode_compile_count(),
                engine.serving_verify_compile_count(),
                engine.serving_page_copy_compile_count(),
                engine.serving_prefill_compile_count())

    for horizon in (1, 8):
        engine.enable_comm_telemetry(False)
        engine.set_compile_watchdog(None)
        sched_off, reqs_off = _serve(engine, prompts, max_new,
                                     horizon=horizon)
        assert sched_off.tracer is NULL_TRACER
        assert sched_off.compile_watchdog is None
        compiles_off = compiles()

        sched_on, reqs_on = _serve(engine, prompts, max_new,
                                   horizon=horizon, comm_telemetry=True)
        compiles_on = compiles()
        for r_off, r_on, w in zip(reqs_off, reqs_on, want):
            assert r_off.out_tokens == w
            assert r_on.out_tokens == w
        assert compiles_on == compiles_off, \
            f"comm telemetry added a jit signature at H={horizon}"
        # the analysis pass is AOT — it may not grow the jit caches
        sched_on.comm_ledger()
        assert compiles() == compiles_off
    engine.enable_comm_telemetry(False)
    engine.set_compile_watchdog(None)


def test_comm_profile_train_zero_cost():
    """The pin, training half: a supervised run with the comm profile
    + compile watchdog armed produces the SAME loss trajectory and the
    SAME compile counts as the bare run, and the train comm ledger
    shows the data-parallel gradient psums on the data axis."""
    from deepspeed_tpu.resilience.supervisor import ResilientTrainer
    from tests.unit.simple_model import (SimpleModel,
                                         random_regression_data,
                                         simple_loss_fn)

    def make_engine():
        model = SimpleModel()
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "mesh": {"data": 8}, "steps_per_print": 1000}
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=cfg, loss_fn=simple_loss_fn(model))
        return eng

    def batch_fn(step):
        return random_regression_data(n=32, seed=step)

    def run(tmp, comm):
        eng = make_engine()
        sup = ResilientTrainer(eng, tmp, save_interval=0,
                               compile_watchdog=comm, mfu_gauge=False)
        losses = []
        orig = eng.train_batch

        def spy(*a, **kw):
            loss = orig(*a, **kw)
            losses.append(float(loss))
            return loss

        eng.train_batch = spy
        sup.train(5, batch_fn=batch_fn)
        eng.train_batch = orig
        led = eng.comm_profile() if comm else None
        return eng, losses, eng.train_compile_counts(), led, sup

    import tempfile
    eng_off, losses_off, cc_off, _, _ = run(tempfile.mkdtemp(), False)
    eng_on, losses_on, cc_on, led, sup = run(tempfile.mkdtemp(), True)
    assert losses_on == losses_off
    assert cc_on == cc_off
    # comm_profile is AOT analysis: counts still unchanged after it
    assert eng_on.train_compile_counts() == cc_on
    # the SPMD grad sync is real data-axis all-reduce traffic
    ar = led["per_axis_op"]["data"]["all_reduce"]
    assert ar["wire_bytes"] > 0
    assert led["per_tier"]["dcn"] == 0
    # the supervisor observed the warmup compiles as compile events
    assert sup.compile_watchdog is not None
    assert sum(sup.compile_watchdog.counts.values()) >= 1
    assert sup.compile_watchdog.steady_recompiles == 0


# ------------------------------------------------ recompile watchdog


def test_watchdog_fires_exactly_one_flight_dump(engine, tmp_path):
    """Acceptance: an injected steady-state signature churn (an
    off-bucket horizon) fires EXACTLY ONE watchdog flight dump naming
    the recompiled function; warmup compiles fire none."""
    tracer = SpanTracer(process="t")
    fr = FlightRecorder(str(tmp_path))
    wd = CompileWatchdog(tracer=tracer, flight_recorder=fr)
    engine.enable_comm_telemetry(False)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, 5).astype(np.int32)
               for _ in range(2)]
    sched = ServingScheduler(engine, decode_horizon_steps=8,
                             compile_watchdog=wd, tracer=tracer, **CFG)
    for p in prompts:
        sched.submit(p, max_new_tokens=5)
    sched.run()
    # warm-up covers the prefill row buckets the steady traffic uses:
    # two prompts together above, a lone one here
    sched.submit(prompts[1], max_new_tokens=5)
    sched.run()
    assert fr.dumps == [], "warmup compiles must not dump"
    wd.mark_steady()

    # inject churn: an off-bucket horizon recompiles decode_multi
    sched.horizon_buckets = [3]
    r = sched.submit(prompts[0], max_new_tokens=4)
    sched.run()
    assert len(r.out_tokens) == 4
    assert wd.steady_recompiles == 1
    assert len(fr.dumps) == 1
    assert "recompile_decode_multi" in fr.dumps[0]
    record = json.load(open(fr.dumps[0]))
    assert record["extra"]["fn"] == "decode_multi"
    assert record["extra"]["horizon"] == 3
    # the storm instant + compile spans are on the tracer
    names = [e[1] for e in tracer.events]
    assert "recompile_storm" in names and "compile" in names
    engine.set_compile_watchdog(None)


def test_watchdog_auto_steady_ticker():
    wd = CompileWatchdog(steady_after_steps=3)
    wd.on_compile("f", 1, 0.0, 0.1)
    for _ in range(2):
        wd.step()
    assert not wd.steady
    wd.step()
    assert wd.steady
    wd.on_compile("f", 1, 0.2, 0.3)
    assert wd.steady_recompiles == 1
    assert wd.summary()["compiles"] == 2


def test_jit_cache_size_shared_helper(engine):
    assert jit_cache_size(None) == 0
    assert jit_cache_size(object()) == 0
    fn = jax.jit(lambda x: x + 1)
    assert jit_cache_size(fn) == 0
    fn(jnp.ones(3))
    assert jit_cache_size(fn) == 1
    # the serving counters read the same probe
    assert engine.serving_decode_multi_compile_count() == \
        jit_cache_size(engine._paged_decode_multi_fn)


# ------------------------------------- per-collective tracing funnel


def test_traced_collectives_record_spans():
    mesh = make_mesh(MeshConfig(data=DATA_AX, model=MODEL_AX))
    dist.set_mesh(mesh)
    tracer = SpanTracer(process="t")
    x = jnp.ones((8, 16), jnp.float32)

    def f(v):
        return dist.all_reduce(v, group="data")

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                               out_specs=P(), check_vma=False))
    with scope(tracer):
        jf(x)
    evs = [e for e in tracer.events if e[2] == "comm"]
    assert evs, "traced collective must record through current_tracer()"
    ph, name, cat, _, _, track, _, args, _, _ = evs[0]
    assert name.startswith("comm.all_reduce")
    assert args["bytes"] == 2 * 16 * 4      # the per-shard payload
    assert args["axes"] == "data" and args["n"] == DATA_AX
    assert args["wire_bytes"] == wire_bytes("all_reduce", 128, 128,
                                            DATA_AX)
    # recording happens at TRACE time: a cache-hit call retraces
    # nothing and so adds no span — and without a scoped tracer, the
    # shared NULL_TRACER records nothing
    n = len(tracer.events)
    with scope(tracer):
        jf(x)
    assert len(tracer.events) == n


def test_eager_funnel_unifies_logger_tracer_and_monitor(capsys):
    mesh = make_mesh(MeshConfig(data=DATA_AX, model=MODEL_AX))
    dist.set_mesh(mesh)
    dist.comms_logger.comms_dict.clear()
    dist.configure(enabled=True)
    tracer = SpanTracer(process="t")
    x = jnp.ones((8, 4))
    with scope(tracer):
        dist.eager_collective(
            lambda v: dist.all_reduce(v, group="data"), x, group="data",
            in_spec=P("data"), out_spec=P(), op_name="all_reduce")
    # ONE funnel: the legacy accumulator AND a timed span agree
    assert "all_reduce" in dist.comms_logger.comms_dict
    spans = [e for e in tracer.events
             if e[0] == "X" and e[1] == "comm.all_reduce"]
    assert spans and spans[0][7]["busbw_gbps"] >= 0
    rows = dist.comms_logger.ledger_rows()
    assert rows and set(rows[0]) >= {"op", "bytes", "latency_ms",
                                     "algbw_gbps", "busbw_gbps", "n"}

    # monitor routing: events ride the sink, the print is suppressed
    rb = RingBufferMonitor()
    dist.attach_monitor(rb)
    capsys.readouterr()
    table = dist.log_summary()
    assert "all_reduce" in table
    assert capsys.readouterr().out == ""
    tags = {t for t, _, _ in rb.events}
    assert {"comm/all_reduce/calls", "comm/all_reduce/bytes",
            "comm/all_reduce/busbw_gbps"} <= tags
    assert tags <= set(EVENT_TAXONOMY)

    # sink detached: the legacy print is preserved byte-identically
    dist.attach_monitor(None)
    printed = dist.log_summary()
    out = capsys.readouterr().out
    assert out == printed + "\n"
    dist.configure(enabled=False)


# ----------------------------------------------- fleet aggregation


def test_cluster_comm_aggregation(engine):
    from deepspeed_tpu.serving import ClusterRouter, make_local_fleet
    engine.enable_comm_telemetry(False)
    replicas = make_local_fleet(engine, 2, comm_telemetry=True, **CFG)
    router = ClusterRouter(replicas)
    rng = np.random.default_rng(4)
    for _ in range(4):
        router.submit(rng.integers(0, 256, 5).astype(np.int32), 4)
    for _ in range(400):
        if not router.step():
            break
    fleet = router.comm_ledger()
    assert set(fleet) == {"replica0", "replica1"}
    h = router.health()
    per = [rep.sched.comm_health_fields()["comm_bytes_per_step"]
           for rep in replicas]
    assert all(v is not None and v > 0 for v in per)
    assert h["aggregate_comm_bytes_per_step"] == sum(per)
    assert h["aggregate_steady_recompiles"] == 0
    engine.enable_comm_telemetry(False)
    engine.set_compile_watchdog(None)
