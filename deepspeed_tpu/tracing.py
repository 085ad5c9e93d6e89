"""Shared span tracing, flight recorder and telemetry export.

ONE tracing core for both halves of the framework: the serving tier
(PR 8 — per-request spans, replica fleet traces) and the training tier
(step spans, checkpoint/resume spans, the goodput ledger).  Both export
through the same three surfaces:

1. **Chrome-trace / Perfetto JSON** — :meth:`SpanTracer.to_chrome` /
   :func:`merge_chrome`.  One *process* per OS process / replica /
   training incarnation, one *track* per timeline row (scheduler,
   device, slot N, micro N, ckpt, steps).
2. **Flight recorder** — every tracer keeps its spans in a bounded
   ring; :class:`FlightRecorder` dumps the recent window when the event
   that made it interesting happens (replica death, fault-point firing,
   training stall/straggler, divergence rollback, preemption).
3. **Prometheus text exposition** — :func:`prometheus_text` renders any
   flat ``health()``/``summary()``/ledger dict for external scrapers.
   Metric names are sanitized and label values escaped per the
   exposition-format rules, so arbitrary dict keys cannot emit
   malformed output.

Span timestamps are **host-side** ``time.monotonic()`` readings shifted
to the unix epoch at export (one offset per tracer, so spans from
different processes — serving replicas or training incarnations
separated by a SIGTERM — line up on the wall clock within NTP skew).
Nothing here touches the device: tracing disabled is the shared
:data:`NULL_TRACER` no-op (zero new jit signatures, token-, loss- and
compile-count-identical — pinned by ``tests/unit/test_trace.py`` for
serving and ``tests/unit/test_train_trace.py`` for training).

``serving/trace.py`` re-exports everything here for backward
compatibility and keeps the serving-only pieces (device-profile
integration).
"""

import contextvars
import json
import os
import re
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

from deepspeed_tpu.resilience import faults

# ---------------------------------------------------------------------
# Event taxonomy: every (tag, value, step) event name the serving AND
# training tiers emit through the monitor/ write_events contract.  This
# is an API — dashboards, the CSV sinks and the Prometheus exposition
# key on these names — so tests/unit/test_monitor.py pins that (a)
# everything ServingMetrics/ClusterMetrics emits is listed here and (b)
# every name here is documented in docs/observability.md; the training
# mirror (tests/unit/test_train_trace.py) pins the supervisor's live
# emissions the same way.  Renaming an event without updating both
# fails the pin, not an operator's dashboard.

EVENT_TAXONOMY = {
    # ------------------------------------------------ serving per-step
    "serving/queue_depth": "requests waiting for a slot, per step",
    "serving/running": "live decode slots, per step",
    "serving/waiting": "queued requests, per step (= queue_depth)",
    "serving/page_utilization": "KV page pool occupancy fraction",
    "serving/device_wait_ms": "host time blocked on the device, per step",
    "serving/host_ms": "host bookkeeping time, per step",
    # request latency
    "serving/ttft_ms": "submit -> first token, per request",
    "serving/token_latency_ms": "inter-token gap, per token",
    "serving/tbt_ms": "time between token bursts (horizon cadence)",
    # batched prefill (one [rows, prefill_chunk] dispatch per step)
    "serving/prefill/rows":
        "prefilling slots one shared prefill dispatch carried",
    "serving/prefill/padded_rows":
        "rows of that dispatch including its row-bucket padding",
    "serving/prefill/tokens":
        "prompt tokens one shared prefill dispatch landed",
    # fused horizons
    "serving/horizon": "fused decode horizon harvested",
    "serving/horizon_tokens": "tokens delivered by one horizon",
    "serving/horizon_wait_ms": "device wait at one horizon's harvest",
    # terminal outcomes (distinct from finished)
    "serving/failed": "request failed (contained per-request error)",
    "serving/shed": "request refused (deadline/capacity)",
    "serving/cancelled": "request cancelled by the client",
    # prefix cache
    "serving/prefix_cache/cached_pages": "pages held by the radix cache",
    "serving/prefix_cache/cached_prefix_tokens":
        "prompt tokens served from cache at one admission",
    "serving/prefix_cache/hit_rate": "admission-time cache hit rate",
    "serving/prefix_cache/prefill_tokens_saved":
        "cumulative prefill tokens not computed",
    "serving/prefix_cache/evicted_pages":
        "cached pages drained under pool pressure",
    "serving/prefix_cache/refused":
        "a prefix cache was asked for and refused (recurrent-state model)",
    # recurrent state beside the page pool; routed (held-expert) layers
    "serving/state/pool_bytes":
        "bytes of per-slot conv/SSM state allocated beside the page pool",
    "serving/state/resets":
        "prefill rows of one dispatch that began at position 0 (state "
        "started from zeros)",
    "serving/moe/assignments":
        "(token, choice) pairs routed since the last reading",
    "serving/moe/held_assignments":
        "of those, pairs on experts this chip holds",
    "serving/moe/held_load_max_over_mean":
        "busiest held expert's pairs over the mean, averaged over "
        "routed-layer calls since the last reading",
    # speculative decoding
    "serving/spec/k": "draft K of one verify round",
    "serving/spec/proposed": "draft tokens scored in one round",
    "serving/spec/accepted": "drafts the target argmax matched",
    "serving/spec/emitted": "tokens one verify round produced",
    "serving/spec/acceptance_rate": "per-round acceptance fraction",
    "serving/spec/rollback_tokens": "KV positions rolled back",
    "serving/spec/degraded": "drafter/verify fault contained",
    "serving/spec/wait_ms": "device wait harvesting a verify round",
    # decoding policy (serving/sampling/: per-slot logit pipeline,
    # lossless speculative sampling, grammar-constrained generation)
    "serving/sampling/sampled_requests":
        "cumulative intakes with a sampled/penalized decoding policy",
    "serving/sampling/grammar_requests":
        "cumulative intakes carrying a grammar constraint",
    "serving/sampling/policy_dispatch":
        "one fused dispatch took the policy twins (value = slots)",
    "serving/sampling/grammar_violation":
        "host grammar cursor rejected an emitted token (request failed)",
    # disaggregation
    "serving/handoff": "one prefill->decode KV chain handed off",
    "serving/handoff_tokens": "prefilled positions transferred",
    # handoff transport (cross-pool chain transfers; DCN-tier bytes)
    "serving/comm/handoff_bytes":
        "exact KV payload bytes one chain transfer moved over DCN",
    "serving/handoff/chunks": "chunk dispatches of one chain transfer",
    "serving/handoff/transfer_ms": "wall ms of one chain transfer",
    "serving/handoff/aborted":
        "chain transfer torn down mid-flight (pages freed both sides)",
    # HBM capacity / page-pool attribution (MemTelemetry; the page-state
    # taxonomy is conservation-exact: slot + prefix_shared + prefix_sole
    # + handoff + unattributed + free == num_pages at every step)
    "serving/mem/slot_pages": "pages held as live-slot KV",
    "serving/mem/prefix_shared_pages":
        "prefix-cache pages shared with >= 1 live reader",
    "serving/mem/prefix_sole_pages":
        "prefix-cache pages held by the cache alone (reclaimable)",
    "serving/mem/handoff_pages":
        "pages parked in prefill->decode handoff chains",
    "serving/mem/draft_pages": "draft-model pool pages in use",
    "serving/mem/unattributed_pages":
        "shared-pool pages held by a peer scheduler (0 standalone)",
    "serving/mem/free_pages": "pages on the free list",
    "serving/mem/free_frac": "free fraction of the page pool",
    "serving/mem/page_seconds":
        "cumulative page-seconds integral across all requests",
    "serving/mem/pressure":
        "one capacity-decision causal chain recorded (value = 1)",
    "serving/mem/pressure_episode":
        "sustained-pressure episode fired (free_frac under threshold)",
    # online serving autotuner (OnlineTuner; bounded nudges of the
    # safely-re-resolvable knobs from the live gauge stream)
    "serving/tune/nudge": "one online-tuner knob nudge applied",
    "serving/tune/decode_horizon":
        "live fused-decode horizon cap after a nudge",
    "serving/tune/spec_k": "live speculation-K ceiling after a nudge",
    "serving/tune/prefix_cache_pages":
        "live prefix-cache retention cap after a nudge",
    # serving topology (construction-time gauges; axis set =
    # MeshConfig's known axes)
    "serving/mesh/data": "mesh data-axis size",
    "serving/mesh/model": "mesh model-axis size",
    "serving/mesh/pipe": "mesh pipe-axis size",
    "serving/mesh/expert": "mesh expert-axis size",
    "serving/mesh/sequence": "mesh sequence-axis size",
    "serving/mesh/kv_pool_bytes_per_device":
        "per-device KV pool footprint",
    # ------------------------------------------- cluster (ClusterMetrics)
    "cluster/finished": "journal entry finished",
    "cluster/failed": "journal entry failed",
    "cluster/shed": "journal entry shed",
    "cluster/cancelled": "journal entry cancelled",
    "cluster/heartbeat_miss": "one missed replica heartbeat",
    "cluster/failover": "replica death detected",
    "cluster/replay": "dead replica's entry requeued onto survivors",
    "cluster/retry": "backpressure admission retry",
    "cluster/handoff": "prefill->decode packet delivered",
    "cluster/handoff_degrade": "handoff failed; requeued unified",
    "cluster/handoff_bytes":
        "KV payload bytes one completed chain transfer moved",
    "cluster/handoff_abort":
        "mid-transfer teardown: partial pages freed, requeued unified",
    "cluster/drain": "replica drain completed",
    "cluster/restart": "replica restarted",
    # ------------------------------------------------ router HA (HaMetrics)
    "router/failovers": "cumulative router takeovers (standby promoted)",
    "router/epoch": "current lease epoch (the fencing token)",
    "router/fenced_writes": "WAL appends rejected from stale epochs",
    "router/wal_records": "records accepted by the journal WAL",
    # ------------------------------------------------ training gauges
    "train/step_time_ms": "mean optimizer-step wall time per gauge window",
    "train/samples_per_s": "ThroughputTimer window samples/sec",
    "train/samples_per_s_avg": "ThroughputTimer running-average samples/sec",
    "train/tokens_per_s": "training tokens/sec over one gauge window",
    "train/tflops_achieved": "achieved model TFLOPS over one gauge window",
    "train/mfu": "model flops utilization (achieved / peak) per window",
    # training watchdogs
    "train/straggler": "EWMA step-time anomaly (value = step seconds)",
    "train/stall": "no-progress timer fired (value = seconds stuck)",
    # goodput ledger (fractions of run wall time; sum to 1)
    "train/goodput/productive": "wall fraction in first-time train steps",
    "train/goodput/compile_warmup":
        "wall fraction in steps that compiled a new executable",
    "train/goodput/checkpoint_stall":
        "wall fraction blocked on checkpoint save/verify/rotate",
    "train/goodput/recompute":
        "wall fraction re-running steps already done before a restore",
    "train/goodput/divergence_retry":
        "wall fraction in NaN-watchdog handling and rollback restores",
    "train/goodput/idle":
        "wall fraction in data loading, drain and host bookkeeping",
    # -------------------------------- resilience lifecycle (supervisor)
    "resilience/checkpoint_saved": "verified checkpoint landed (value = step)",
    "resilience/checkpoint_rotated": "retention removed an old tag",
    "resilience/save_retry": "one failed save attempt was retried",
    "resilience/rollback": "a corrupt/unloadable tag was skipped",
    "resilience/resumed": "an intact tag was restored (value = step)",
    "resilience/preempted": "preemption checkpoint landed; run exiting",
    "resilience/nan_loss": "the divergence watchdog saw a non-finite loss",
    # ------------------------------------- communication (HLO ledger)
    # per-signature static-analysis gauges emitted when the serving
    # comm ledger is computed (ServingScheduler.comm_ledger): bytes are
    # per-device wire bytes of ONE steady-state decode dispatch, per
    # the formulas in docs/observability.md
    "serving/comm/bytes_per_step":
        "wire bytes one steady-state decode dispatch moves per device",
    "serving/comm/bytes_per_token":
        "wire bytes per emitted token at full slot occupancy "
        "(bytes_per_step / (horizon x num_slots))",
    "serving/comm/collectives_per_step":
        "collective executions per decode dispatch (trip-weighted)",
    "serving/comm/ici_bytes_per_step":
        "wire bytes riding intra-slice (ICI-tier) groups per dispatch",
    "serving/comm/dcn_bytes_per_step":
        "wire bytes riding cross-process (DCN-tier) groups per dispatch",
    # per-mesh-axis wire-byte split (axis set = MeshConfig's known axes)
    "serving/comm/axis/data": "wire bytes per dispatch on the data axis",
    "serving/comm/axis/model": "wire bytes per dispatch on the model axis",
    "serving/comm/axis/pipe": "wire bytes per dispatch on the pipe axis",
    "serving/comm/axis/expert":
        "wire bytes per dispatch on the expert axis",
    "serving/comm/axis/sequence":
        "wire bytes per dispatch on the sequence axis",
    # recompile watchdog
    "serving/comm/recompile":
        "steady-state recompile detected (value = cumulative count)",
    # ----------------------- sequence-parallel prefill (long context)
    "serving/seq_prefill/routed":
        "a prompt routed onto the sp path (value = pending tokens)",
    "serving/seq_prefill/reserved_pages":
        "pages the routed prompt pre-reserved for its full chain",
    "serving/seq_prefill/chunk_tokens":
        "prompt tokens one sequence-sharded prefill chunk retired",
    "serving/seq_prefill/degraded":
        "a long prompt stayed on the chunked path (no usable axis)",
    "serving/seq_prefill/shed_reserve_cap":
        "a prompt shed on the reserve cap (value = pages it needed)",
    # ----------------------- multi-tenant serving (quotas + fairness)
    "serving/tenant/active":
        "tenants holding at least one pool page this step",
    "serving/tenant/page_seconds":
        "summed page-seconds billed across all tenant ledgers",
    "serving/tenant/max_share":
        "largest single tenant's fraction of the page pool",
    "serving/tenant/quota_shed":
        "a request shed on its tenant's page quota (after self-drain)",
}

# the eager comms logger's periodic report (comm.log_summary) routes
# per-op aggregates through the monitor stream under comm/<op>/<field>
# — the canonical op set below is taxonomy-pinned (custom op_name
# strings still emit, under their own sanitized names)
for _op in ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
            "ppermute", "broadcast", "barrier"):
    EVENT_TAXONOMY[f"comm/{_op}/calls"] = \
        f"eager {_op} invocations accumulated by the comms logger"
    EVENT_TAXONOMY[f"comm/{_op}/bytes"] = (
        f"cumulative message bytes of eager {_op} calls, op-scaled "
        "exactly like the printed log_summary table (calc_bw_log: "
        "gather/scatter count the full buffer, others per member)")
    EVENT_TAXONOMY[f"comm/{_op}/busbw_gbps"] = (
        f"mean bus bandwidth of eager {_op} calls — the raw "
        "calc_bw_log figure, same unit as the comm-ledger row schema "
        "(the printed table shows bits, x8)")
del _op


# ---------------------------------------------------------------------
# Components: which part of the model a device operation belongs to.
# Every instruction of a compiled program carries the name stack it was
# traced under as ``op_name`` metadata (flax module names and
# ``jax.named_scope``s, e.g.
# ``jit(decode_multi)/horizon/while/body/closed_call/Llama/layers_3/mlp/
# w_down/dot_general``), and a device profile's "XLA Ops" events carry
# it as the ``tf_op`` stat of their metadata.  ONE closed vocabulary
# maps such a path to a component, the same for every model family and
# both engines; the operator's table (``profiling/module_profiler.py``) and
# the benchmark's ``scope.*`` metrics (whose files are pinned to this
# map by ``tests/chip_bench/test_chip_bench_scopes.py``) both read it.
#
# The rule: split the path on ``/``; ``jit(...)`` / ``jvp(...)`` /
# ``transpose(...)`` wrappers, ``while`` / ``body`` / ``cond`` /
# ``branch_N``, layer indices (``layers_3`` -> ``layers``) and primitive
# names are not tokens; THE INNERMOST TOKEN THAT IS IN THE MAP DECIDES:
#
#   .../layers_3/attn/pallas_call                      -> attn_core
#   .../layers_3/attn/q_proj/dot_general               -> attn_proj
#   .../layers_3/attn/cache/scatter                    -> cache
#   .../layers_3/mlp/w_down/dot_general                -> mlp
#   .../transpose(jvp(GPT2))/h_3/mlp/fc_in/dot_general -> mlp, "bwd"
#   .../sample/argmax                                  -> sample
#
# A collective is ``comm`` by its opcode whatever its path; a path with
# no token of the map is ``other``, an operation with no path (one the
# compiler made itself: a copy, an asynchronous slice of a weight)
# ``unattributed``.  Two more forms the compiler gives what it makes: a
# path that ENDS at a loop or a call (``.../horizon/while/body/
# closed_call``: the loop's own metadata on a copy or a prefetch beside
# it; JAX ends every path it emits with a primitive) is ``unattributed``
# too, and an argument's name (``params['layers_3']['moe']['w_up']``:
# a copy of that weight) reads by its keys.  A fusion counts where XLA
# put its metadata: a matmul fused with the next norm's reduction reads
# under ONE of them (on the TPU the matmul's, PERF.md section 7).

COMPONENTS = {
    "embed": ("embed", "embed_tokens", "wte", "wpe", "ln_embed"),
    "norm": ("norm", "input_norm", "post_attn_norm", "pre_ff_norm",
             "ln_1", "ln_2", "kv_a_norm", "residual"),
    "attn_proj": ("wq", "wk", "wv", "wo", "wkv_a", "qkv", "proj",
                  "q_proj", "k_proj", "v_proj", "o_proj", "rope",
                  "mla_absorb", "attn_proj"),
    "attn_core": ("attn", "swa"),
    "cache": ("cache", "pools", "page_table"),
    "mlp": ("mlp", "shared", "shared_up", "shared_down"),
    "router": ("router",),
    # XLA's TPU expansion of ``lax.ragged_dot`` names its kernel's path
    # ``ragged-dot-none`` and drops the scope it was traced under
    "experts": ("experts", "moe", "ragged-dot-none"),
    "ssm": ("ssm", "mamba"),
    "head": ("head", "lm_head", "ln_f", "norm_f"),
    "sample": ("sample", "horizon"),
    "loss": ("loss",),
    "optimizer": ("optimizer", "train_loop"),
    "comm": ("zero_gather",),
    "other": (),
    "unattributed": (),
}
# opcodes (an instruction's own name, ``all-gather-start.7``) that are
# ``comm`` whatever the path; ``async-collective-start`` / ``-done`` are
# the TPU compiler's wrappers of asynchronous gathers and
# reduce-scatters
COLLECTIVE_OPCODES = ("all-gather", "all-reduce", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "async-collective")
_TOKEN_COMPONENT = {tok: comp for comp, toks in COMPONENTS.items()
                    for tok in toks}
_LAYER_INDEX = re.compile(r"_\d+$")
# ``transpose(jvp(loss))`` -> ``loss``: autodiff wraps the outermost
# scope of the function it transforms; ``jit(name)`` holds a function's
# name, never a scope's
_WRAPPED = re.compile(r"^(?:(?!jit\()\w+\()+([^()]*)\)+$")
_ARGUMENT = re.compile(r"^(\w+)((?:\[[^\]]*\])+)$")
_CONTROL_FLOW = ("while", "body", "cond", "closed_call")


def path_parts(op_name):
    """An ``op_name`` as its parts, outermost first: split on ``/``, or
    an argument's name by its keys.  Of an operation merged from several
    (``a/mul;b/add``) the first path is read."""
    op_name = op_name.split(";")[0].rstrip(":")
    arg = _ARGUMENT.match(op_name)
    if arg:
        return [arg.group(1)] + re.findall(r"\['([^']*)'\]", arg.group(2))
    return op_name.split("/")


def component(op_name, opcode=""):
    """The component of :data:`COMPONENTS` an operation belongs to, from
    its ``op_name`` path (``tf_op`` of a device event) and, for
    collectives, its opcode."""
    if opcode.lstrip("%").startswith(COLLECTIVE_OPCODES):
        return "comm"
    parts = path_parts(op_name)
    if not op_name or parts[-1] in _CONTROL_FLOW or \
            parts[-1].startswith("branch_"):
        return "unattributed"
    for part in reversed(parts):
        part = _WRAPPED.sub(r"\1", part)
        comp = _TOKEN_COMPONENT.get(part) or \
            _TOKEN_COMPONENT.get(_LAYER_INDEX.sub("", part))
        if comp is not None:
            return comp
    return "other"


def pass_of(op_name):
    """``"bwd"`` / ``"fwd"`` from a training path's ``transpose(...)`` /
    ``jvp(...)`` wrappers, ``""`` for a path with neither."""
    if "transpose(" in op_name:
        return "bwd"
    return "fwd" if "jvp(" in op_name else ""


# ---------------------------------------------------------------- spans

class _NullSpan:
    """Reusable no-op context manager for the disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Context manager recording one complete ("X") span on exit."""

    __slots__ = ("tracer", "name", "cat", "track", "rid", "args",
                 "process", "t0")

    def __init__(self, tracer, name, cat, track, rid, args, process):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.track = track
        self.rid = rid
        self.args = args
        self.process = process
        self.t0 = time.monotonic()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.tracer.complete(self.name, self.t0, time.monotonic(),
                             cat=self.cat, track=self.track, rid=self.rid,
                             args=self.args, process=self.process)
        return False


class SpanTracer:
    """Low-overhead host-side span recorder with a bounded ring buffer.

    The ring (``capacity`` events) makes every tracer double as its own
    flight recorder: a dump after an incident contains the most recent
    window of spans without any always-on file I/O.  All methods are
    no-ops semantically when ``enabled`` is False — but prefer the
    shared :data:`NULL_TRACER` for the disabled case so call sites pay
    one attribute load, not an allocation.
    """

    def __init__(self, process="serve", enabled=True, capacity=8192):
        self.process = process
        self.enabled = bool(enabled)
        self.capacity = int(capacity)
        # events are flat tuples (ph, name, cat, ts, dur, track, rid,
        # args, process, flow_id) — recording sits on the serving hot
        # path, so the per-span cost is one tuple + one deque append;
        # dict building is deferred to export
        self.events = deque(maxlen=self.capacity)
        self.dropped = 0          # events rotated out of the ring
        # monotonic -> epoch shift, captured once so exported spans from
        # different processes line up on the wall clock
        self._epoch_offset = time.time() - time.monotonic()

    # ------------------------------------------------------- recording
    def _push(self, ev):
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)

    def span(self, name, *, cat="serving", track="scheduler", rid=None,
             args=None, process=None):
        """``with tracer.span("prefill_chunk", track=slot, rid=rid):``"""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, track, rid, args, process)

    def complete(self, name, t0, t1, *, cat="serving", track="scheduler",
                 rid=None, args=None, process=None):
        """Record a finished span from two monotonic timestamps (for
        phases whose start predates the call, e.g. queue wait)."""
        if not self.enabled:
            return
        self._push(("X", name, cat, t0, t1 - t0 if t1 > t0 else 0.0,
                    track, rid, args, process, None))

    def instant(self, name, *, cat="serving", track="scheduler", rid=None,
                args=None, process=None, ts=None):
        if not self.enabled:
            return
        self._push(("i", name, cat,
                    time.monotonic() if ts is None else ts, 0.0,
                    track, rid, args, process, None))

    def counter(self, name, values, *, cat="mem", track="counters",
                rid=None, process=None, ts=None):
        """Perfetto *counter track* sample ("C" event): ``values`` is a
        flat {series: number} dict — Perfetto renders one stacked
        counter track per (process, name) with one series per key (the
        page-pool occupancy split rides this).  Samples are cheap flat
        tuples like spans; the dict is only serialized at export."""
        if not self.enabled:
            return
        self._push(("C", name, cat,
                    time.monotonic() if ts is None else ts, 0.0,
                    track, rid, values, process, None))

    def flow(self, phase, flow_id, name, *, cat="failover",
             track="scheduler", rid=None, args=None, process=None):
        """Chrome-trace flow event: ``phase`` 's' starts an arrow,
        'f' finishes it; events sharing ``flow_id`` are linked (the
        explicit dead-replica -> survivor replay link)."""
        if not self.enabled:
            return
        self._push((phase, name, cat, time.monotonic(), 0.0,
                    track, rid, args, process, flow_id))

    # -------------------------------------------------------- exporting
    def serialized(self, drain=False):
        """Events with epoch-resolved timestamps (µs) but unresolved
        process/track labels — the wire format a worker process ships to
        the router's collector.  ``drain=True`` empties the ring (ship
        each span once)."""
        out = []
        src = self.events
        # snapshot defensively: a flight dump may run on a watchdog
        # thread while the owning thread appends spans — retry the
        # (CPython-atomic in practice) copy rather than let a
        # mutated-during-iteration RuntimeError kill the dumping thread
        for _ in range(4):
            try:
                snapshot = list(src)
                break
            except RuntimeError:
                continue
        else:
            snapshot = []
        for ph, name, cat, ts, dur, track, rid, args, process, fid \
                in snapshot:
            e = {"ph": ph, "name": name, "cat": cat,
                 "ts": (ts + self._epoch_offset) * 1e6,
                 "track": track, "rid": rid, "args": args,
                 "process": process or self.process}
            if ph == "X":
                e["dur"] = dur * 1e6
            if fid is not None:
                e["id"] = fid
            out.append(e)
        if drain:
            src.clear()
        return out

    def to_chrome(self, extra_events=None):
        """The full Chrome-trace JSON object for this tracer (merge
        tracers with :func:`merge_chrome`)."""
        return merge_chrome([self.serialized() + list(extra_events or [])])

    def dump(self, path):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
            f.write("\n")
        return path


class _NullTracer(SpanTracer):
    """The disabled tracer: every method is a no-op, ``span`` returns a
    shared no-op context manager.  One module-level instance is shared
    by every untraced scheduler AND every untraced training engine so
    "tracing off" costs one attribute load and one falsy check per call
    site."""

    def __init__(self):
        super().__init__(process="null", enabled=False, capacity=1)

    def _push(self, ev):     # pragma: no cover — nothing may record
        raise AssertionError("NULL_TRACER must never record events")


NULL_TRACER = _NullTracer()


# --------------------------------------------------------------- phases
# ONE primitive for "this stretch of host code has a name".  A phase is
# an event on the host plane of a device profile (the profiler's clock,
# beside the "XLA Ops" line), seconds in a flat accumulator that
# ``summary()`` reads, and a span of the SpanTracer under the name its
# site always had.  Sites use it as a ``with`` block at their own call
# depth: no decorator and no wrapper, so the Python frames above a
# kernel's trace are the same with it as without.

def annotation(name, **stats):
    """A bare ``jax.profiler.TraceAnnotation`` for code that owns no
    tracer (the engines): ``with tracing.annotation("ds.engine.launch",
    program=name):``.  Without a profiler session it is a no-op of well
    under a microsecond; under one it is an event of the ``.xplane.pb``
    whose keyword arguments arrive as the event's stats."""
    return TraceAnnotation(name, **stats)


class _Phase:
    """One named phase, made once: the context object its site enters
    every time (so a name never nests in itself)."""

    __slots__ = ("owner", "name", "label", "cat", "home", "track", "rid",
                 "stats", "t0", "last_s", "_ann")

    def __init__(self, owner, name, cat, home):
        self.owner = owner
        self.name = name
        self.label = owner.prefix + name
        self.cat = cat
        self.home = home        # the track of a pass that names none
        self.track = None
        self.rid = None
        self.stats = {}
        self.t0 = 0.0
        self.last_s = 0.0       # seconds of the newest pass
        self._ann = None

    def note(self, **stats):
        """Numbers known only once the phase is under way (the
        horizon a dispatch settled on, the tokens a harvest
        delivered)."""
        self.stats.update(stats)
        self._ann.set_metadata(**stats)

    def __enter__(self):
        self._ann = TraceAnnotation(self.label, **self.stats)
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        self._ann = None
        self.last_s = t1 - self.t0
        owner = self.owner
        owner.seconds[self.name] += self.last_s
        owner.counts[self.name] += 1
        if owner.tracer.enabled:
            owner.tracer.complete(
                self.name, self.t0, t1, cat=self.cat,
                track=self.home if self.track is None else self.track,
                rid=self.rid, args=self.stats or None)
        return False


class Phases:
    """The named phases of one owner (a scheduler): ``with
    self.phases("admit"):``, or with small whole numbers that describe
    the pass, ``with self.phases("prefill_chunk", rows=3, tokens=24):``.

    Entering and leaving a phase (1) opens and closes a
    ``TraceAnnotation(prefix + name, **stats)`` — always, there is no
    switch; (2) adds the pass's ``time.monotonic()`` seconds and a
    count to ``seconds[name]`` / ``counts[name]``; (3) where the tracer
    is enabled, records ``tracer.complete(name, t0, t1)`` with the
    stats as its args.  ``spans`` gives the (cat, track) under which a
    name has always been recorded; other names take ``("phase",
    "scheduler")``."""

    def __init__(self, tracer=None, prefix="", spans=None):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.prefix = prefix
        self.spans = dict(spans or {})
        self.seconds = {}
        self.counts = {}
        self._phases = {}

    def __call__(self, name, track=None, rid=None, **stats):
        ph = self._phases.get(name)
        if ph is None:
            cat, trk = self.spans.get(name, ("phase", "scheduler"))
            ph = self._phases[name] = _Phase(self, name, cat, trk)
            self.seconds[name] = 0.0
            self.counts[name] = 0
        ph.stats, ph.track, ph.rid = stats, track, rid
        return ph

    def total(self, *names):
        """Seconds accumulated under ``names`` so far."""
        return sum(self.seconds.get(n, 0.0) for n in names)


# ------------------------------------------------ compile observability

def jit_cache_size(fn):
    """THE compile-count probe: compiled-signature count of a jitted
    callable (0 for ``None`` or a not-yet-jitted callable).  Every
    consumer — ``InferenceEngine.serving_*_compile_count``,
    ``DeepSpeedEngine.train_compile_counts``, the goodput ledger's
    ``compile_warmup`` detector, the recompile watchdog and the test
    pins — reads THIS helper, so "what counts as a compile" has exactly
    one definition."""
    if fn is None:
        return 0
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return 0
    try:
        return int(probe())
    except Exception:       # a torn-down backend must read as 0, not raise
        return 0


class CompileWatchdog:
    """Recompile detection: jit cache-miss events become ``compile``
    spans, and a *steady-state* recompile — signature churn after
    warmup — fires a tracer instant plus a :class:`FlightRecorder`
    dump (the compile-storm failure class, machine-detected instead of
    test-pinned only).

    Lifecycle: the dispatch layer calls :meth:`on_compile` whenever a
    watched callable's :func:`jit_cache_size` grew across a call
    (``wall_s`` is that call's wall time — jit compiles synchronously
    at dispatch, so the first call's wall IS compile + dispatch).  The
    owner ticks :meth:`step` once per scheduler/train step; after
    ``steady_after_steps`` consecutive ticks without a compile the
    watchdog arms itself (or arm explicitly with :meth:`mark_steady` —
    deterministic for tests and drain boundaries).  Once steady, every
    further compile is a detection: ``recompile_storm`` instant,
    ``serving/comm/recompile`` monitor event (when a metrics funnel is
    bound) and one flight dump naming the recompiled function.

    Host bookkeeping only — it never changes what compiles (pinned by
    ``tests/unit/test_comm_telemetry.py``: watchdog on/off runs are
    token-exact with identical compile counts)."""

    def __init__(self, tracer=None, flight_recorder=None,
                 steady_after_steps=64, metrics=None):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.flight_recorder = flight_recorder
        self.metrics = metrics          # ServingMetrics-compatible or None
        self.steady = False
        self.steady_after_steps = None if not steady_after_steps \
            else int(steady_after_steps)
        self._quiet_steps = 0
        self.counts = {}                # fn name -> cumulative compiles
        self.compile_wall_s = 0.0       # cumulative compile-attributed wall
        self.steady_recompiles = 0
        # bounded like every other recorder here (SpanTracer ring,
        # FlightRecorder limit): a persistent compile storm — the very
        # scenario this watchdog detects — must not leak memory
        self.events = deque(maxlen=256)  # (name, n, wall_s, steady)
        self._step_idx = 0

    def bind(self, tracer=None, flight_recorder=None, metrics=None):
        if tracer is not None:
            self.tracer = tracer
        if flight_recorder is not None:
            self.flight_recorder = flight_recorder
        if metrics is not None:
            self.metrics = metrics
        return self

    def mark_steady(self):
        """Warmup is over: from here every new jit signature is churn."""
        self.steady = True

    def step(self, owner=None):
        """One scheduler/train step completed (auto-steady ticker).
        With a shared engine-lifetime watchdog, several schedulers
        tick it — pass ``owner`` (the caller's metrics funnel) so only
        the CURRENT owner's steps advance the quiet counter; N
        co-ticking schedulers would otherwise arm steady state in
        1/N-th of the intended warmup window."""
        if owner is not None and self.metrics is not None and \
                owner is not self.metrics:
            return
        self._step_idx += 1
        if self.steady or self.steady_after_steps is None:
            return
        self._quiet_steps += 1
        if self._quiet_steps >= self.steady_after_steps:
            self.steady = True

    def on_compile(self, name, n, t0, t1, detail=None):
        """``n`` new signature(s) of ``name`` compiled during the call
        spanning ``t0``→``t1`` (monotonic seconds)."""
        total = self.counts.get(name, 0) + int(n)
        self.counts[name] = total
        wall = max(t1 - t0, 0.0)
        self.compile_wall_s += wall
        self._quiet_steps = 0
        self.events.append((name, int(n), wall, self.steady))
        args = {"fn": name, "new_signatures": int(n),
                "cumulative": total, "ms": round(wall * 1e3, 3),
                "steady_state": self.steady}
        if detail:
            args.update(detail)
        self.tracer.complete("compile", t0, t1, cat="compile",
                             track="compile", args=args)
        if not self.steady:
            return
        self.steady_recompiles += 1
        self.tracer.instant("recompile_storm", cat="compile",
                            track="compile", args=args)
        if self.metrics is not None:
            rec = getattr(self.metrics, "record_recompile", None)
            if rec is not None:
                rec(self._step_idx, self.steady_recompiles)
        if self.flight_recorder is not None:
            self.flight_recorder.dump(
                f"recompile:{name}",
                extra={"fn": name, "new_signatures": int(n),
                       "cumulative_compiles": total,
                       "compile_wall_s": round(wall, 4),
                       **({k: v for k, v in (detail or {}).items()})})

    def summary(self):
        return {"compiles": int(sum(self.counts.values())),
                "compile_wall_s": round(self.compile_wall_s, 4),
                "steady": self.steady,
                "steady_recompiles": self.steady_recompiles,
                "per_fn": dict(self.counts)}


# --------------------------------------------------- scoped tracer
# A dynamically-scoped tracer channel for layers whose call signatures
# should not grow a tracer parameter through every seam (the checkpoint
# engine sits behind a pluggable backend API).  The supervisor wraps
# save/load calls in `with scope(tracer):`; checkpoint/engine.py reads
# `current_tracer()` at call time (captured into async-writer closures,
# so the worker thread keeps the caller's tracer).

_scoped = contextvars.ContextVar("ds_tracing_scope", default=NULL_TRACER)


class _TracerScope:
    __slots__ = ("tracer", "_token")

    def __init__(self, tracer):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._token = None

    def __enter__(self):
        self._token = _scoped.set(self.tracer)
        return self.tracer

    def __exit__(self, *exc):
        _scoped.reset(self._token)
        return False


def scope(tracer):
    """``with tracing.scope(tracer): engine.save_checkpoint(...)``"""
    return _TracerScope(tracer)


def current_tracer():
    return _scoped.get()


def merge_chrome(event_lists):
    """Merge serialized event lists (each from :meth:`SpanTracer.
    serialized`) into one Chrome-trace JSON object: processes become
    pids (with ``process_name`` metadata), (process, track) pairs
    become tids (with ``thread_name`` metadata), flows keep their
    ids."""
    pids = {}
    tids = {}
    out = []

    def pid_for(process):
        if process not in pids:
            pids[process] = len(pids) + 1
            out.append({"ph": "M", "name": "process_name",
                        "pid": pids[process], "tid": 0,
                        "args": {"name": str(process)}})
        return pids[process]

    def tid_for(process, track):
        key = (process, track)
        if key not in tids:
            tids[key] = len([k for k in tids if k[0] == process]) + 1
            out.append({"ph": "M", "name": "thread_name",
                        "pid": pid_for(process), "tid": tids[key],
                        "args": {"name": track if isinstance(track, str)
                                 else f"slot {track}"}})
        return tids[key]

    for events in event_lists:
        for ev in events:
            process = ev.get("process") or "serve"
            row = {"name": ev["name"], "cat": ev.get("cat", "serving"),
                   "ph": ev["ph"], "ts": ev["ts"],
                   "pid": pid_for(process),
                   "tid": tid_for(process, ev.get("track", "scheduler"))}
            if ev["ph"] == "X":
                row["dur"] = ev.get("dur", 0.0)
            if ev["ph"] == "i":
                row["s"] = "t"      # thread-scoped instant
            # "C" counter samples need no extra fields: Perfetto keys a
            # counter track on (pid, name) and plots one series per
            # args entry (the page-pool state split)
            if "id" in ev:
                row["id"] = ev["id"]
            args = dict(ev.get("args") or {})
            if ev.get("rid") is not None:
                args["rid"] = ev["rid"]
            if args:
                row["args"] = args
            out.append(row)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


# ------------------------------------------------------ flight recorder

class FlightRecorder:
    """Bounded post-incident dumps of the recent span window.

    Register every tracer in the process (router + one per replica, or
    the training supervisor's tracer); :meth:`dump` writes one JSON
    file per incident into ``out_dir``: the trigger reason, the journal
    entry in flight (when the caller has one — the dead replica's
    replayed request), and the merged recent-span window from every
    registered source.  ``limit`` bounds files per process so an
    incident storm cannot fill a disk.

    Triggers wired by the serving tier:

    * replica death (``ClusterRouter._on_death``),
    * a fault point actually firing (:meth:`arm_fault_observer` hooks
      ``resilience.faults.observe``),
    * an uncontained serving-loop error (``bin/ds_serve``).

    Triggers wired by the training tier (``ResilientTrainer``):

    * the no-progress stall timer and EWMA straggler watchdog,
    * a divergence-watchdog rollback,
    * a checkpoint-corruption rollback during resume,
    * a preemption notice (the final pre-exit window).
    """

    def __init__(self, out_dir, limit=16):
        self.out_dir = out_dir
        self.limit = int(limit)
        self.count = 0
        self.skipped = 0
        self._tracers = {}        # label -> SpanTracer
        self._extra_events = []   # pre-serialized events (dead workers)
        self._fault_observer = None
        self.dumps = []           # paths written
        # dumps arrive from more than one thread now (the training
        # stall watchdog fires from its own daemon thread while the
        # main thread may be dumping a divergence) — the count/limit
        # check and the count-derived filename must be atomic
        self._lock = threading.Lock()

    def register(self, label, source):
        """``source``: a :class:`SpanTracer`, or any callable returning
        a list of pre-serialized events (a ProcessReplica's collected
        worker spans)."""
        self._tracers[label] = source

    def add_events(self, events):
        """Adopt already-serialized span events (e.g. collected from a
        worker process that has since been SIGKILLed)."""
        self._extra_events.extend(events)

    def dump(self, reason, *, journal_entry=None, extra=None):
        """Write one flight record; returns the path (None once
        ``limit`` is reached — the count of skipped dumps is kept).
        Thread-safe: concurrent dumps get distinct indices and never
        exceed ``limit``."""
        with self._lock:
            if self.count >= self.limit:
                self.skipped += 1
                return None
            self.count += 1
            index = self.count
        lists, dropped = [], {}
        for label, src in self._tracers.items():
            lists.append(src.serialized() if hasattr(src, "serialized")
                         else list(src()))
            dropped[label] = getattr(src, "dropped", 0)
        record = {
            "reason": reason,
            "wall_time": time.time(),
            "journal_entry": journal_entry,
            "extra": extra,
            "dropped_spans": dropped,
            "trace": merge_chrome(lists + [self._extra_events]),
        }
        os.makedirs(self.out_dir, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in str(reason))[:64]
        path = os.path.join(self.out_dir,
                            f"flight_{index:03d}_{safe}.json")
        with open(path, "w") as f:
            json.dump(record, f)
            f.write("\n")
        self.dumps.append(path)
        return path

    # ---------------------------------------------------- fault trigger
    def arm_fault_observer(self):
        """Auto-dump whenever a fault point actually FIRES (an armed
        plan's action ran) — the injected chaos is exactly the moment
        the recent-span window is worth keeping."""
        if self._fault_observer is not None:
            return
        def _on_fire(point, ctx):
            self.dump(f"fault:{point}", extra={"ctx": {
                k: v for k, v in ctx.items()
                if isinstance(v, (int, float, str, bool, type(None)))}})
        self._fault_observer = faults.observe(_on_fire)

    def disarm_fault_observer(self):
        if self._fault_observer is not None:
            faults.unobserve(self._fault_observer)
            self._fault_observer = None


# --------------------------------------------------- prometheus export

# Exposition-format rules (https://prometheus.io/docs/instrumenting/
# exposition_formats/): metric and label NAMES match
# [a-zA-Z_:][a-zA-Z0-9_:]*; label VALUES may hold any UTF-8 but
# backslash, double-quote and newline must be escaped.  health() keys
# are arbitrary strings (fault reasons, user tags), so both rules are
# enforced here rather than trusted at every call site.

_PROM_BAD_CHARS = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(prefix, key):
    safe = _PROM_BAD_CHARS.sub("_", str(key))
    return f"{prefix}_{safe}"


def _prom_label_name(key):
    safe = _PROM_BAD_CHARS.sub("_", str(key))
    if not safe or safe[0].isdigit():
        safe = "_" + safe
    return safe


def _prom_label_value(value):
    return (str(value)
            .replace("\\", "\\\\")
            .replace('"', '\\"')
            .replace("\n", "\\n"))


def prometheus_text(metrics, *, prefix="ds_serving", labels=None,
                    help_map=None):
    """Render a flat dict of counters/gauges (``health()`` and/or
    ``summary()`` output, a goodput-ledger dict) in the Prometheus text
    exposition format.

    Non-numeric values (strings, lists, nested dicts, None) are
    skipped — the JSONL health dump carries those; this surface is for
    scrapers.  Booleans export as 0/1.  ``labels`` (dict) are attached
    to every sample, e.g. ``{"replica": "replica0"}``; label values are
    escaped (backslash/quote/newline) and metric/label names sanitized
    (invalid chars -> ``_``) so arbitrary keys cannot emit malformed
    exposition."""
    label_s = ""
    if labels:
        inner = ",".join(
            f'{_prom_label_name(k)}="{_prom_label_value(v)}"'
            for k, v in sorted(labels.items()))
        label_s = "{" + inner + "}"
    lines = []
    for key in sorted(metrics):
        val = metrics[key]
        if isinstance(val, bool):
            val = int(val)
        if not isinstance(val, (int, float)) or val != val:  # skip NaN
            continue
        name = _prom_name(prefix, key)
        if help_map and key in help_map:
            lines.append(f"# HELP {name} {help_map[key]}")
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name}{label_s} {val}")
    return "\n".join(lines) + "\n"


# ----------------------------------------------- metrics HTTP endpoint

def start_metrics_server(health_fn, *, summary_fn=None, port=0,
                         prefix="ds_serving", labels=None,
                         host="127.0.0.1"):
    """Serve the Prometheus exposition of ``health_fn()`` (and
    optionally ``summary_fn()`` under ``<prefix>_summary_*``) over a
    stdlib HTTP endpoint — ``GET /metrics`` for scrapers, ``GET
    /healthz`` for the raw health JSON — so the ``.prom``
    textfile-collector dance (``ds_serve --health-interval``) becomes
    optional.  ``port=0`` binds an ephemeral port; read it back from
    ``server.server_port``.  Runs on a daemon thread; call
    ``server.shutdown()`` to stop.  A failing health callable answers
    500 rather than killing the serving loop's thread."""
    import json as _json
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            try:
                if self.path.split("?")[0] == "/healthz":
                    body = _json.dumps(health_fn()).encode()
                    ctype = "application/json"
                elif self.path.split("?")[0] == "/metrics":
                    text = prometheus_text(health_fn(), prefix=prefix,
                                           labels=labels)
                    if summary_fn is not None:
                        text += prometheus_text(summary_fn(),
                                                prefix=prefix + "_summary",
                                                labels=labels)
                    body = text.encode()
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
            except Exception:   # a broken source must answer, not hang
                self.send_response(500)
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):   # scrapers must not spam stderr
            pass

    server = ThreadingHTTPServer((host, int(port)), _Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server
