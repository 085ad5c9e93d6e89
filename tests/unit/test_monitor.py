"""Monitor-sink coverage + the unified event-taxonomy pin.

Contracts the observability tier rides on:

* **RingBufferMonitor** — bounded, ordered ``tail()``: the live
  interrogation surface for supervisors/health endpoints.
* **csvMonitor** — one CSV per tag with a ``(step, value)`` schema that
  round-trips: the artifact external dashboards ingest.
* **Event taxonomy** — every ``serving/*`` / ``cluster/*`` event name
  ``ServingMetrics``/``ClusterMetrics`` emit appears in
  ``tracing.EVENT_TAXONOMY`` AND in ``docs/observability.md``: a rename
  fails HERE, not an operator's dashboard.  (The ``train/*`` +
  ``resilience/*`` half of the taxonomy is pinned against the live
  supervisor in ``test_train_trace.py``; the doc pin below covers ALL
  names.)
* **step >= 1 invariant** — enforced centrally
  (``monitor.clamp_min_step`` in ``MonitorMaster.write_events`` and the
  metrics funnels), replacing the old per-callsite stamping (the
  ``record_mesh`` step-1 hack).
* **Prometheus exposition hardening** — arbitrary ``health()`` keys and
  label values cannot emit malformed exposition: metric/label names are
  sanitized, label values escaped.
"""

import csv
import json
import math
import os
import types

import pytest

from deepspeed_tpu.monitor.config import get_monitor_config
from deepspeed_tpu.monitor.monitor import (MonitorMaster,
                                           RingBufferMonitor, clamp_min_step,
                                           csvMonitor)
from deepspeed_tpu.serving.metrics import ClusterMetrics, ServingMetrics
from deepspeed_tpu.serving.trace import EVENT_TAXONOMY
from deepspeed_tpu.tracing import prometheus_text

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ------------------------------------------------------------- sinks

def test_ring_buffer_tail_ordering_and_bounds():
    rb = RingBufferMonitor(maxlen=8)
    for i in range(1, 21):
        rb.write_events([("t/a", float(i), i)])
    assert len(rb.events) == 8, "ring must stay bounded"
    # tail(n) returns the MOST RECENT n, oldest-first
    assert [s for _, _, s in rb.tail(3)] == [18, 19, 20]
    assert [s for _, _, s in rb.tail(8)] == list(range(13, 21))
    # n > len degrades to the whole buffer, still ordered
    assert [s for _, _, s in rb.tail(99)] == list(range(13, 21))


def test_csv_monitor_schema_round_trip(tmp_path):
    cfg = types.SimpleNamespace(enabled=True, output_path=str(tmp_path),
                                job_name="job")
    mon = csvMonitor(cfg)
    mon.write_events([("serving/ttft_ms", 12.5, 1),
                      ("serving/ttft_ms", 7.25, 2),
                      ("serving/queue_depth", 3, 2)])
    # one file per tag, '/' flattened; header then (step, value) rows
    path = tmp_path / "job" / "serving_ttft_ms.csv"
    with open(path) as f:
        rows = list(csv.reader(f))
    assert rows[0] == ["step", "serving_ttft_ms"]
    assert [(int(s), float(v)) for s, v in rows[1:]] == \
        [(1, 12.5), (2, 7.25)]
    with open(tmp_path / "job" / "serving_queue_depth.csv") as f:
        rows = list(csv.reader(f))
    assert [(int(s), float(v)) for s, v in rows[1:]] == [(2, 3.0)]


# --------------------------------------------------- step >= 1 clamp

def test_clamp_min_step_clamps_and_passes_through():
    evs = [("a", 1.0, 0), ("b", 2.0, -3), ("c", 3.0, 5)]
    out = clamp_min_step(evs, warn=False)
    assert [s for _, _, s in out] == [1, 1, 5]
    # the all-valid fast path returns the SAME list (no copy per step)
    ok = [("a", 1.0, 1)]
    assert clamp_min_step(ok) is ok


def test_monitor_master_enforces_step_invariant(tmp_path):
    """Regression (the record_mesh step-1 stamping hack): the invariant
    lives in MonitorMaster.write_events now — any emitter handing a
    step < 1 event gets it clamped centrally, with a warning."""
    master = MonitorMaster(get_monitor_config({}))

    class Sink:
        enabled = True

        def __init__(self):
            self.events = []

        def write_events(self, event_list):
            self.events.extend(event_list)

    sink = Sink()
    master.csv_monitor = sink
    master.write_events([("train/loss", 1.0, 0), ("train/lr", 0.1, 2)])
    assert [s for _, _, s in sink.events] == [1, 2]


def test_serving_metrics_funnel_clamps_construction_gauges():
    """record_mesh fires at scheduler construction (step 0 by nature);
    the central funnel stamps it to 1 — no sink ever sees step < 1,
    with no per-callsite workaround in metrics.py."""
    rb = RingBufferMonitor()
    m = ServingMetrics(rb)
    m.record_mesh({"mesh_shape": {"data": 2, "model": 4},
                   "kv_pool_bytes_per_device": 1024})
    cm = ClusterMetrics(rb)
    cm.event(0, "failover")
    assert rb.events, "gauges must reach the sink"
    assert all(step >= 1 for _, _, step in rb.events)


# ---------------------------------------------------- taxonomy pin

def _drive_all_serving_events(m):
    """Exercise every ServingMetrics recording path that emits monitor
    events (a new record_* emitting an undocumented tag fails the
    subset assertion below)."""
    m.record_mesh({"mesh_shape": {"data": 1, "model": 1, "pipe": 1,
                                  "expert": 1, "sequence": 1},
                   "kv_pool_bytes_per_device": 1})
    m.record_step(1, queue_depth=1, running=1, waiting=1,
                  page_utilization=0.5, device_wait_s=0.1, host_s=0.1,
                  cached_pages=2)
    m.record_prefix(1, 16, 32)
    m.record_cache_eviction(1, 2)
    m.record_tbt(1, 0.01)
    m.record_horizon(1, 8, 24, 0.002)
    m.record_spec(1, proposed=8, accepted=6, emitted=7, rollbacks=1,
                  rollback_tokens=2, k=8, slot_rounds=1)
    m.record_spec_degrade(1, rid=1, reason="x")
    m.record_spec_wait(1, 0.001)
    m.record_policy_request(1, sampled=True, grammar=True)
    m.record_policy_dispatch(1, 3)
    m.record_grammar_violation(1, rid=1)
    m.record_handoff(1, 32)
    m.record_handoff_transport(1, "out", 4096, 2, 1.5)
    m.record_handoff_transport(1, "in", 4096, 2, 1.5)
    m.record_handoff_abort(1)
    m.record_seq_prefill_route(1, 256, 16)
    m.record_seq_prefill_chunk(1, 128)
    m.record_seq_prefill_degrade(1)
    m.record_state_pool(4096)
    m.record_state_resets(1, 2)
    m.record_prefix_refused()
    m.record_routing(1, [768, 96, 3 * 1024, 1, 1, 512, 1])
    m.record_seq_prefill_shed(1, 33)
    m.record_mem(1, {"slot": 3, "prefix_shared": 2, "prefix_sole": 1,
                     "handoff": 0, "draft": 0, "unattributed": 0,
                     "free": 10}, 0.625, 1.25)
    m.record_pressure(1, "grow")
    m.record_pressure_episode(1)
    for knob, value in (("decode_horizon", 4), ("spec_k", 4),
                        ("prefix_cache_pages", 16)):
        m.record_tune(1, knob, value)
    m.record_comm(1, {"bytes_per_step": 4096, "bytes_per_token": 512.0,
                      "collectives_per_step": 12, "ici_bytes": 4096,
                      "dcn_bytes": 0,
                      "per_axis": {"data": 1024, "model": 3072,
                                   "pipe": 1, "expert": 1,
                                   "sequence": 1, "data+model": 7}})
    m.record_recompile(1, 1)
    m.record_first_token(1, 0.05)
    m.record_token(1, 0.01)
    for state in ("failed", "shed", "cancelled"):
        m.record_terminal(1, state, rid=1, reason="x")


def test_record_routing_adds_up_differences_modulo_2_32():
    """Two readings of the routed layers' wrapping uint32 counters: the
    second lies past the wrap in every entry, and the metrics add the
    differences, the small calls beside the calls and, beside the small
    calls, the share of the held experts with a pair and the calls that
    walked."""
    m = ServingMetrics(None)
    wrap = 1 << 32
    m.record_routing(1, [wrap - 700, wrap - 90, wrap - 2048, wrap - 11,
                         wrap - 8, wrap - 5000, wrap - 6])
    first = (m.moe_assignments, m.moe_held_assignments, m.moe_calls,
             m.moe_dense_calls, m.moe_read_share_q10, m.moe_walk_calls)
    assert first == (wrap - 700, wrap - 90, wrap - 11, wrap - 8,
                     wrap - 5000, wrap - 6)
    # 99 calls on, 88 of them small, which touched 11,264 / 1024 = 11
    # times all the held experts between them (an eighth each), and 66
    # of which walked
    m.record_routing(2, [68, 6, 1024, 88, 80, 6264, 60])
    s = m.summary()
    assert s["moe_assignments"] - first[0] == 768
    assert s["moe_held_assignments"] - first[1] == 96
    assert s["moe_calls"] - first[2] == 99
    assert s["moe_dense_calls"] - first[3] == 88
    assert m.moe_read_share_q10 - first[4] == 11264
    assert m.moe_walk_calls - first[5] == 66
    # nothing ran since
    m.record_routing(3, [68, 6, 1024, 88, 80, 6264, 60])
    assert m.summary()["moe_dense_calls"] == s["moe_dense_calls"]
    assert m.moe_read_share_q10 - first[4] == 11264
    assert m.moe_walk_calls - first[5] == 66


@pytest.mark.parametrize("read_q10, walked, share, walk_share", [
    (0, 3, 0.0, 1.0), (64 * 3, 3, 0.0625, 1.0), (1024 * 3, 0, 1.0, 0.0),
    (1024 + 2 * 512, 2, 0.6667, 0.6667)],
    ids=["none", "one_of_16", "all", "mixed"])
def test_the_walks_two_shares_are_over_the_small_calls(
        read_q10, walked, share, walk_share):
    """``moe_dense_experts_read_share`` and ``moe_walk_share`` in
    ``summary()``: the sixth counter over 1024, and the seventh, over
    the SMALL calls (3 of the 5 here), 0.0 before any; always in
    [0, 1]."""
    m = ServingMetrics(None)
    assert m.summary()["moe_dense_experts_read_share"] == 0.0
    assert m.summary()["moe_walk_share"] == 0.0
    m.record_routing(1, [640, 40, 5 * 2048, 5, 3, read_q10, walked])
    assert m.summary()["moe_dense_experts_read_share"] == share
    assert m.summary()["moe_walk_share"] == walk_share


def test_decode_live_page_share_is_live_pages_over_the_tables_walked():
    """Hand-made horizons over a table of 4 slots x 6 pages: the share
    is the pages the emitting slots' lengths span over steps x 24; no
    harvested step reads None, never a division error; a spec harvest
    (no horizon recorded) leaves it alone."""
    m = ServingMetrics(None)
    assert m.summary()["decode_live_page_share"] is None
    m.record_spec_wait(1, 0.001)
    assert m.summary()["decode_live_page_share"] is None
    # 8 steps; two slots emit 8 tokens each: one inside its first page,
    # one crossing from its second page into its third after 3 steps
    m.record_horizon(1, 8, 16, 0.0, live_rows=16, kv_tokens=400,
                     live_pages=8 * 1 + (3 * 2 + 5 * 3), table_pages=24)
    assert m.summary()["decode_live_page_share"] == round(29 / (8 * 24), 4)
    # 4 steps in which every slot is at capacity: the whole table
    m.record_horizon(2, 4, 16, 0.0, live_rows=16, kv_tokens=16 * 96,
                     live_pages=4 * 24, table_pages=24)
    s = m.summary()
    assert s["decode_steps"] == 12
    assert s["decode_live_page_share"] == round((29 + 96) / (12 * 24), 4)
    # a horizon in which nothing emitted adds steps and no pages
    m.record_horizon(3, 8, 0, 0.0, table_pages=24)
    assert m.summary()["decode_live_page_share"] == \
        round(125 / (20 * 24), 4)


def test_prefill_live_page_share_is_live_pages_over_the_tables_dispatched():
    """Hand-made dispatches over slots of 6 pages: the share is the
    (row, page) entries the rows' chunks reach -- prompt rows, riders
    and one page a padding row -- over padded rows x 6; None before any
    dispatch, and a decode horizon leaves it alone."""
    m = ServingMetrics(None)
    assert m.summary()["prefill_live_page_share"] is None
    m.record_horizon(1, 8, 16, 0.0, live_pages=29, table_pages=24)
    assert m.summary()["prefill_live_page_share"] is None
    # 3 prompt rows in a bucket of 4: chunks ending in pages 1, 3 and 6,
    # and the padding row's one page
    m.record_prefill_dispatch(1, rows=3, padded_rows=4, tokens=24,
                              live_pages=1 + 3 + 6 + 1, table_pages=4 * 6)
    assert m.summary()["prefill_live_page_share"] == round(11 / 24, 4)
    # 2 prompt rows and 2 riders deep in their slots fill a bucket of 4
    m.record_prefill_dispatch(2, rows=2, padded_rows=4, tokens=16,
                              riders=2, live_pages=2 + 4 + 5 + 6,
                              table_pages=4 * 6)
    s = m.summary()
    assert s["ride_rows"] == 2 and s["prefill_dispatches"] == 2
    assert s["prefill_live_page_share"] == round((11 + 17) / 48, 4)
    # the decode counter beside it is its own
    assert s["decode_live_page_share"] == round(29 / (8 * 24), 4)


def _lookahead_share(m):
    """Three of four hand-made dispatches were launched before the one
    before was pulled; nothing dispatched reads 0.0."""
    assert m.summary()["prefill_lookahead_share"] == 0.0
    m.record_prefill_dispatch(1, rows=3, padded_rows=4, tokens=24)
    for step in (2, 3, 4):
        m.record_prefill_dispatch(step, rows=2, padded_rows=4, tokens=16,
                                  riders=2, lookahead=True)
    assert m.summary()["prefill_lookahead_share"] == 0.75
    assert m.summary()["prefill_dispatches"] == 4


def _lookahead_fallbacks(m):
    """Counted by reason, in the reasons' order; a reason never hit is
    absent, and the map is no number (a scalar sink never sees it)."""
    assert m.summary()["prefill_lookahead_fallbacks"] == {}
    for why in ("pages", "policy", "pages", "eviction", "drain",
                "other", "pages", "not_slot_bound"):
        m.record_lookahead_fallback(why)
    got = m.summary()["prefill_lookahead_fallbacks"]
    assert got == {"drain": 1, "eviction": 1, "not_slot_bound": 1,
                   "other": 1, "pages": 3, "policy": 1}
    assert list(got) == sorted(got)


def _overrun_rows(m):
    """Summed over the pulls of dispatches that were in flight; a pull
    that dropped nothing adds nothing."""
    assert m.summary()["prefill_overrun_rows"] == 0
    for dropped in (0, 2, 0, 1):
        m.record_lookahead_pull(dropped)
    assert m.summary()["prefill_overrun_rows"] == 3


def _horizon_share(m):
    """Two of the three horizons that followed a boundary in its step
    were launched before its pull; none launched reads 0.0."""
    assert m.summary()["horizon_lookahead_share"] == 0.0
    for before_pull in (True, False, True):
        m.record_horizon_after_boundary(before_pull)
    assert m.summary()["horizon_lookahead_share"] == round(2 / 3, 4)


@pytest.mark.parametrize("check", [_lookahead_share, _lookahead_fallbacks,
                                   _overrun_rows, _horizon_share],
                         ids=["share", "fallbacks", "overrun_rows",
                              "horizon_share"])
def test_the_prefill_look_ahead_counters(check):
    """``summary()``'s four counters of the slot-bound look-ahead
    (``ServingScheduler._launch_boundary``), each from hand-made
    records."""
    check(ServingMetrics(None))


def test_key_block_counters_are_live_pages_over_what_the_grid_walks():
    """A hand-counted dispatch at four pages a key block, the tail of
    one page walked and a longer one masked: rows holding 9, 6 and 1
    pages and a padding row walk (2 blocks + 1 page) + (1 block + 1
    masked block) + 1 + 1 = 7 steps over 9 + 8 + 1 + 1 computed pages.
    None before a dispatch; a dispatch recorded without the kernel's
    count (no page pool) leaves both None."""
    m = ServingMetrics(None)
    s = m.summary()
    assert s["prefill_key_block_fill_share"] is None
    assert s["prefill_pages_per_step"] is None
    m.record_prefill_dispatch(1, rows=3, padded_rows=4, tokens=24)
    s = m.summary()
    assert s["prefill_key_block_fill_share"] is None
    assert s["prefill_pages_per_step"] is None
    m.record_prefill_dispatch(2, rows=3, padded_rows=4, tokens=24,
                              live_pages=9 + 6 + 1 + 1, table_pages=4 * 12,
                              key_blocks=3 + 2 + 1 + 1,
                              block_pages=9 + 8 + 1 + 1)
    s = m.summary()
    assert s["prefill_key_block_fill_share"] == round(17 / 19, 4)
    assert s["prefill_pages_per_step"] == round(17 / 7, 4)
    # one page a step: both read 1.0
    one = ServingMetrics(None)
    one.record_prefill_dispatch(1, rows=2, padded_rows=2, tokens=16,
                                live_pages=5, table_pages=12, key_blocks=5,
                                block_pages=5)
    s = one.summary()
    assert s["prefill_key_block_fill_share"] == 1.0
    assert s["prefill_pages_per_step"] == 1.0


def test_the_scheduler_records_the_kernels_own_count_of_a_dispatch():
    """The counters come from ops/attention/paged_prefill's count at the
    engine's geometry: a 19-token prompt over pages of 16 in chunks of 8
    is three dispatches holding 1, 1 and 2 live pages -- walked a page a
    step, no block of 8 pages fits a tail that short."""
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
    from deepspeed_tpu.ops.attention.paged_prefill import key_block_plan
    from deepspeed_tpu.serving import ServingScheduler
    engine = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1})
    engine.init_params()
    sched = ServingScheduler(engine, num_slots=2, num_pages=12,
                             page_size=16, max_pages_per_slot=6,
                             prefill_chunk=8)
    cfg = engine.module.cfg
    cols, tiles, block = key_block_plan(8, cfg.num_heads, cfg.num_heads, 16,
                                        cfg.head_dim, 4, 4)
    assert (tiles, block) == (1, 8)
    count = sched._count_key_blocks([16, 0], [3, 0], max_pages=6)
    assert count == dict(live_pages=3, table_pages=12, key_blocks=3,
                         block_pages=3)
    sched.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=2)
    sched.run()
    s = sched.metrics.summary()
    assert s["prefill_dispatches"] == 3
    assert s["prefill_live_page_share"] == round(4 / 18, 4)
    assert s["prefill_key_block_fill_share"] == 1.0
    assert s["prefill_pages_per_step"] == 1.0


@pytest.mark.parametrize("dispatches,by_bucket,pad_share", [
    ([], {}, 0.0),
    ([(20, 32)], {"32": 1}, 12 / 32),
    ([(3, 4), (17, 32), (30, 32), (5, 16), (33, 64), (1, 1)],
     {"1": 1, "4": 1, "16": 1, "32": 2, "64": 1}, 1 - 89 / 149),
])
def test_prefill_dispatches_are_counted_by_the_bucket_they_rode_in(
        dispatches, by_bucket, pad_share):
    """(rows, padded_rows) of each dispatch: ``summary()`` holds the
    count per bucket under JSON keys in the buckets' order, never a
    bucket that was not used, beside the pad share of the same rows."""
    m = ServingMetrics(None)
    for step, (rows, padded) in enumerate(dispatches, 1):
        m.record_prefill_dispatch(step, rows=rows, padded_rows=padded,
                                  tokens=8 * rows)
    s = m.summary()
    assert s["prefill_dispatches_by_bucket"] == by_bucket
    assert list(s["prefill_dispatches_by_bucket"]) == \
        sorted(by_bucket, key=int)
    assert sum(by_bucket.values()) == s["prefill_dispatches"]
    assert s["prefill_pad_share"] == pytest.approx(pad_share, abs=1e-4)
    json.dumps(s["prefill_dispatches_by_bucket"])


_CLUSTER_TAGS = ("heartbeat_miss", "failover", "replay", "retry",
                 "handoff", "handoff_degrade", "drain", "restart")


def test_event_taxonomy_pins_every_emitted_name():
    from deepspeed_tpu.serving.metrics import HaMetrics

    rb = RingBufferMonitor(maxlen=4096)
    _drive_all_serving_events(ServingMetrics(rb))
    cm = ClusterMetrics(rb)
    for tag in _CLUSTER_TAGS:
        cm.event(1, tag)
    for state in ("finished", "failed", "shed", "cancelled"):
        cm.record_terminal(1, state)
    cm.record_handoff_transfer(1, "wire", 4096, 2, 1.5)
    cm.record_handoff_abort(1)
    ha = HaMetrics(rb)
    ha.record_gauges(1, epoch=1, fenced_writes=0, wal_records=3)
    ha.record_takeover(2, epoch=2, fenced_writes=1, wal_records=5)
    emitted = {tag for tag, _, _ in rb.events}
    unknown = emitted - set(EVENT_TAXONOMY)
    assert not unknown, (
        f"events emitted outside the documented taxonomy: {unknown} — "
        "add them to trace.EVENT_TAXONOMY AND docs/observability.md "
        "(renames break operator dashboards; this pin breaks first)")


def test_event_taxonomy_documented():
    """Every taxonomy name appears verbatim in docs/observability.md —
    the table operators read is the table the code emits."""
    doc = open(os.path.join(REPO, "docs", "observability.md")).read()
    missing = [name for name in EVENT_TAXONOMY if name not in doc]
    assert not missing, f"undocumented events: {missing}"


# ------------------------------------------ prometheus hardening

def test_prometheus_metric_names_are_sanitized():
    """health() keys are arbitrary strings; the exposition format only
    allows [a-zA-Z0-9_:] in metric names — every other char becomes
    '_' so a weird key can't emit an unparseable line."""
    text = prometheus_text({"a b/c-d%": 1.0, "ok_name": 2.0,
                            "per-request p99 (ms)": 3.5},
                           prefix="ds_test")
    lines = [ln for ln in text.splitlines() if not ln.startswith("#")]
    assert "ds_test_a_b_c_d_ 1.0" in lines
    assert "ds_test_ok_name 2.0" in lines
    assert "ds_test_per_request_p99__ms_ 3.5" in lines
    for ln in lines:
        name = ln.split(" ", 1)[0].split("{", 1)[0]
        assert all(c.isalnum() or c in "_:" for c in name), ln


def test_prometheus_label_values_are_escaped():
    r"""Backslash, double-quote and newline in label VALUES must escape
    per the exposition format (\\, \", \n) — a fault reason or model
    path in a label can't break the sample line."""
    text = prometheus_text(
        {"x": 1},
        labels={"reason": 'disk "full"\nretry', "path": "C:\\tmp"})
    sample = [ln for ln in text.splitlines()
              if not ln.startswith("#")][0]
    assert "\n" not in sample, "raw newline must never survive"
    assert '\\"full\\"' in sample
    assert "\\n" in sample
    assert "C:\\\\tmp" in sample
    # label names sanitize too (invalid chars -> _, no leading digit)
    text2 = prometheus_text({"x": 1}, labels={"9bad-key": "v"})
    assert '_9bad_key="v"' in text2


def test_prometheus_value_filtering():
    """Booleans export 0/1; NaN, strings, None and nested dicts are
    skipped rather than emitted malformed."""
    text = prometheus_text({"flag": True, "off": False,
                            "nan": math.nan, "s": "str",
                            "none": None, "nested": {"a": 1}},
                           prefix="p")
    lines = [ln for ln in text.splitlines() if not ln.startswith("#")]
    assert lines == ["p_flag 1", "p_off 0"]


# The end-to-end "live serving loop emits only documented tags" pin
# rides tests/unit/test_trace.py (it shares that module's engine);
# the training-side live pin rides tests/unit/test_train_trace.py.
