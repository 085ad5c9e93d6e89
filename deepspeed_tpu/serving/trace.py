"""End-to-end request tracing for the serving tier.

The tracing core — :class:`SpanTracer`, :class:`FlightRecorder`,
:func:`merge_chrome`, :func:`prometheus_text`, the shared
:data:`EVENT_TAXONOMY` and the :data:`NULL_TRACER` singleton — lives in
:mod:`deepspeed_tpu.tracing` since the training tier adopted the same
machinery (step spans, goodput ledger, stall watchdog); this module
re-exports it unchanged for the serving tier's callers and keeps the
serving-only pieces (the device-profile integration below).

Three export surfaces over ONE span stream (Dapper-style per-request
tracing plus a flight recorder — the standard answer for "where did the
time go / what was the fleet doing" in a multi-tier serving system):

1. **Chrome-trace / Perfetto JSON** — :meth:`SpanTracer.to_chrome` /
   :meth:`SpanTracer.dump` (and ``ClusterRouter.dump_trace`` for the
   merged fleet view).  One *process* per replica, one *track* per
   slot plus scheduler/device tracks; open the file at
   https://ui.perfetto.dev or chrome://tracing.
2. **Flight recorder** — every tracer keeps its spans in a bounded
   ring, so the most recent window of activity survives the event that
   made it interesting.  :class:`FlightRecorder` dumps that window (from
   every registered tracer, correlated with the journal entry that was
   in flight) when a replica dies, a fault point fires, or an
   uncontained error escapes.
3. **Prometheus text exposition** — :func:`prometheus_text` renders the
   existing ``health()``/``summary()`` counters and gauges in the
   text-based exposition format for external scrapers (the node-exporter
   textfile-collector pattern; ``ds_serve --health-interval`` writes it
   next to the health JSONL).

Span timestamps are **host-side** ``time.monotonic()`` readings shifted
to the unix epoch at export (one offset captured per tracer, so spans
from different processes line up on the wall clock within NTP skew).
Nothing here touches the device: tracing disabled is the
:data:`NULL_TRACER` no-op (zero new jit signatures, token- and
compile-count-identical — pinned by ``tests/unit/test_trace.py``), and
tracing enabled only adds bounded host bookkeeping per dispatch.

**Trace context.**  Spans carry a request id (``rid``).  Inside one
scheduler that is the local ``Request.rid``; across the cluster the
router propagates ``trace_ctx={"trace_id": <journal rid>, "attempt":
n}`` through ``submit``/``attach_handoff`` (and the worker JSONL
protocol), so every span of one client request — prefill on the replica
that died, replay decode on the survivor, the handoff between them —
shares one id.  Failover replays additionally get an explicit
Chrome-trace flow link (``ph: s/f``) from the dead replica's last
routed span to the survivor's replay admission.
"""

import json
import os

from deepspeed_tpu.tracing import (EVENT_TAXONOMY,  # noqa: F401
                                   NULL_TRACER,
                                   FlightRecorder,
                                   SpanTracer,
                                   merge_chrome,
                                   prometheus_text,
                                   start_metrics_server)
from deepspeed_tpu.utils.logging import logger


# -------------------------------------------- device-profile integration

def profile_serving(sched, n_steps=8, trace_dir=None, depth=3):
    """Capture a JAX device profile of ``n_steps`` scheduler steps and
    aggregate it by model component and per module (the ``profiling/``
    xplane pipeline, pointed at the serving loop instead of a train
    step).

    Returns ``{"components": [...], "rows": [...], "table": str}`` from
    ``profiling.module_profiler`` — measured post-fusion device time by
    ``tracing.component`` (the vocabulary the benchmark's ``scope.*``
    metrics read), then time / flops / HBM bytes per module.  Raises
    RuntimeError where the backend records no device plane (plain CPU
    jax builds); callers (``ds_serve --profile-steps``) degrade to a
    warning.
    """
    from deepspeed_tpu.profiling.module_profiler import (
        aggregate_by_component, aggregate_by_module, capture_trace,
        format_profile)

    records = capture_trace(lambda: sched.step(), n_steps=n_steps,
                            trace_dir=trace_dir)
    return {"components": aggregate_by_component(records),
            "rows": aggregate_by_module(records, depth=depth),
            "table": format_profile(records, depth=depth)}


def write_profile_report(report, out_dir):
    """Drop the per-module aggregation next to the trace artifacts:
    ``module_profile.json`` (rows), ``component_profile.json`` (seconds
    by component) + ``module_profile.txt`` (both tables)."""
    os.makedirs(out_dir, exist_ok=True)
    jpath = os.path.join(out_dir, "module_profile.json")
    with open(jpath, "w") as f:
        json.dump(report["rows"], f, indent=2)
        f.write("\n")
    if "components" in report:
        with open(os.path.join(out_dir, "component_profile.json"),
                  "w") as f:
            json.dump(report["components"], f, indent=2)
            f.write("\n")
    tpath = os.path.join(out_dir, "module_profile.txt")
    with open(tpath, "w") as f:
        f.write(report["table"] + "\n")
    logger.info(f"serving device profile written to {jpath}")
    return jpath, tpath
