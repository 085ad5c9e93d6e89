"""Iteration-level (continuous-batching) scheduler.

Orca (OSDI '22) scheduling over the paged KV cache: requests join and
leave the running batch at token granularity instead of batch
granularity.  Each ``step()`` is one scheduler iteration:

  1. sweep cancellations and expired deadlines (terminal work leaves at
     step boundaries, never mid-dispatch),
  2. admit waiting requests into free slots (admission control: the pool
     must be able to hold the whole prompt, and a deadline the request
     cannot possibly meet sheds it NOW instead of wasting pool pages),
  3. advance every admitted-but-unprefilled slot by ONE prompt chunk
     (chunked prefill — long prompts never stall running decoders for
     more than a chunk), all of them in ONE ``[rows, prefill_chunk]``
     dispatch (rows padded to a row bucket: x4 to 16 rows, x2 above);
     while requests wait for a slot the decoding slots may ride it as
     one-token rows, where the measured step walls say that pays
     (``_plan_ride``); a dispatch they rode with no horizon after it
     stays in flight, and the next step launches its own before it
     pulls this one's tokens (**Look-ahead**, below),
  4. run ONE fused multi-step decode ("horizon") over all running
     slots: up to ``decode_horizon_steps`` tokens per slot in a single
     ``decode_multi`` dispatch, with token feedback, EOS detection and
     length advancement all on device,
  5. emit observability events.

All device work goes through the jit-stable primitives on
``InferenceEngine`` (``prefill_into_slots`` / ``decode_multi``); the
scheduler itself is pure host logic.  When the page pool runs dry,
refcount-free pages held by the prefix cache drain first (they are
reclaimable capacity, not live state); only then is the youngest
running request preempted (recompute-style eviction: its pages
recycle, the request re-queues at the queue head with its
already-emitted tokens folded into the prompt).

**Prefix cache.**  With ``prefix_cache=True`` the scheduler keeps a
radix index (``serving/prefix_cache.py``) over pages donated by
finished requests.  Admission longest-prefix matches each prompt:
matched full pages are shared read-only into the slot's table
(``PagePool`` refcounts), a partially matched page is copied into a
fresh private page on-device (copy-on-write) so the cached original
stays immutable, and chunked prefill resumes from the cached boundary
(``lengths[slot]`` seeds the positions — no new jit signatures).
Prefill compute and page footprint scale with UNIQUE tokens, not total
tokens, on shared-prefix traffic.

**The horizon model.**  A horizon of H steps costs ONE dispatch and one
host round-trip for H tokens — the per-token host loop is amortized
H-fold (the same trick ``generate()`` plays with its bucketed
``lax.scan``).  The price is
granularity: scheduler interventions — admission, cancellation,
deadline shedding, eviction — take effect at horizon boundaries, so H
bounds added reaction latency at roughly H x per-token time.  Horizons
are quantized to a small power-of-two bucket set (compile count stays
bounded) and adapt down when remaining token budgets, the tightest
admitted deadline, or page-pool pressure make a full horizon wasteful
or unaffordable.  Before each dispatch every running slot's pages for
the whole horizon are pre-reserved, so allocation never interrupts the
fused scan.

**Speculative decoding.**  With ``spec_decode="ngram"`` (or ``"draft"``
plus a :class:`~deepspeed_tpu.serving.spec_decode.DraftModelDrafter`)
greedy decode dispatches become draft/verify rounds: a pluggable
drafter proposes up to K tokens per slot (adaptive per-request K,
shrunk on low acceptance and capped under page-pool pressure through
the same pre-reservation path as horizons), one teacher-forced
``verify_multi`` dispatch scores them all, the longest greedy-matching
prefix plus the target's bonus token is emitted, and KV written past
the rejection point rolls back (``truncate_slot``).  Greedy
verification compares against the exact ``temperature=0`` argmax
contract, so output is token-exact vs ``generate()`` and vs
``spec_decode=off`` regardless of drafter quality.  Sampled slots
verify by *lossless* leftover-probability rejection sampling
(``verify_multi_policy``): each draft token is accepted with the
target's probability for it and a rejection resamples the residual, so
the emitted stream is distribution-exact — identical in law to
unspeculated sampling — for ANY drafter that opts in
(``supports_sampling``).

**Decoding policy.**  Every request carries a
:class:`~deepspeed_tpu.serving.sampling.SamplingParams` (temperature /
top-k / top-p / repetition / presence / frequency penalties), a PRNG
seed keying a position-indexed sample stream, and optionally a
grammar constraint (regex / JSON-schema) compiled host-side to a
per-step allowed-token mask.  Policy knobs are traced per-slot device
lanes — a mixed greedy/sampled/penalized batch shares ONE compiled
signature per horizon/K bucket — while a pure-greedy batch under a
greedy default keeps riding the legacy signatures byte-identically.
Constrained slots run horizon-1 barrier steps (their mask is a host
function of emitted tokens) and never draft, but may ride verify
rounds as width-0 one-token decodes.  Spec rounds need host-authoritative
token history to draft from, so every step runs as a barrier step
while a drafter is configured (no horizon chaining — a chained round
never consults the drafter, and chaining plain rounds would starve it
in exactly the steady state spec decode targets); slots with nothing
to propose ride the verify dispatch as plain one-token decodes, and
when NO slot has a proposal the step falls back to the normal fused
horizon dispatch.  ``spec_decode=off`` leaves the PR-3/PR-4 loop
byte-identical.

**Overlap.**  With ``overlap=True`` the scheduler keeps one horizon in
flight: when membership is provably frozen (nothing waiting, nothing
prefilling, no cancel/deadline pressure, next horizon's pages free), it
dispatches horizon k+1 directly off horizon k's on-device carries
(token/active/lengths/emitted), *then* pulls k's token block (started
as an async host copy at dispatch) and runs emit/retire bookkeeping
while the device crunches k+1.  Any membership change falls back to a
conservative barrier: drain in-flight work, apply host-authoritative
state, dispatch fresh.  Per-request terminations discovered while a
chained horizon is in flight (a failing emit callback, a cancel, an
expired deadline) close the request immediately but defer the page
release until the in-flight horizon is harvested — the device may still
be writing that slot's pages.

**Look-ahead.**  Slot-bound, a step whose decoding slots rode the
prefill dispatch and that launches no horizon has nothing left that
needs the sampled tokens, so it leaves them on the device
(``_launch_boundary``; every sampling row's request is ``owed`` one
token).  The next step settles what the dispatch decides whatever its
tokens are (``_advance_in_flight``: a request whose owed token is its
last leaves its slot, a finished prompt decodes from here), sweeps,
admits and plans on that, stages and launches its own dispatch, whose
riders read their input id from the device's per-slot tokens, and only
then pulls (``_pull``): sweep to launch, the one host gap a step, runs
under the device's time.  The barrier step is the same step with the
pull in front; ``_why_pull_now`` says from the step's own state when
that holds (a speculative round follows, a request carries a policy, a
grammar or a hand-off, drain has begun), and a dispatch in flight is
pulled early where growing a row would evict a live slot.
What only a token's VALUE decides -- an end of sequence, and likewise a
cancel, a deadline or a failing callback found at the pull -- costs one
computed row: the next dispatch's row for that slot is dropped at its
pull, and the slot is parked (``_zombies``) with its pages until then.

A step that launches a horizon never blocks on the device with nothing
queued behind what it blocks on either.  Its horizon goes out before
the pull of the step's first tokens, the slots that finished a prompt
reading their first token on the device (``_last_tok_on_device``); and
slot-bound, a step that finds a horizon in flight sweeps, admits, plans
and launches its prefill dispatch before it harvests
(``_why_harvest_first`` / ``_why_harvest_now``), so the device runs
``horizon k-1, prefill k, sample, horizon k, prefill k+1`` back to
back.  A request whose budget the horizon in flight exhausts ends in it
whatever it samples and leaves its slot at that plan; a slot freed by
an end of sequence is admitted into a step later.  Such a prefill
dispatch grows from free pages only: where a row would evict, the
horizon is harvested first.

Failure policy (the serving half of docs/resilience.md):

* **Containment** — an exception attributable to ONE request (its
  prefill dispatch, its token callback, an injected per-request fault)
  fails that request (state ``failed``) and releases its pages; the
  loop and every other request keep going.  Only errors in the shared
  batched decode dispatch — not attributable to a single request — can
  take the loop down.
* **Shedding** — load the system cannot serve is refused distinctly
  from errors (state ``shed``): deadline-infeasible admissions, expired
  deadlines, and page-capacity dead-ends.
* **Cancellation** — ``req.cancel()`` is a flag; the scheduler honors
  it at the next step boundary, releasing pages (state ``cancelled``).
* **Bounded memory** — terminal requests leave the live ``requests``
  map for a bounded ``completed`` history, so a long-running server's
  bookkeeping cannot grow without bound.

All latency accounting uses ``time.monotonic()``: an NTP clock step
must never produce negative or wild TTFT/ITL samples.
"""

import json
import re
import time
from collections import deque

import numpy as np

from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving import mem_telemetry as memtel
from deepspeed_tpu.serving.mem_telemetry import NULL_MEM, MemTelemetry
from deepspeed_tpu.serving.metrics import ServingMetrics
from deepspeed_tpu.serving.page_manager import (PagedKVManager,
                                                PagePoolExhausted,
                                                default_page_size)
from deepspeed_tpu.serving.prefix_cache import PrefixCache
from deepspeed_tpu.serving.sampling import (GREEDY, GrammarConstraintError,
                                            SamplingParams, compile_grammar,
                                            request_key)
from deepspeed_tpu.serving.trace import NULL_TRACER
from deepspeed_tpu.tracing import Phases
from deepspeed_tpu.utils.logging import logger

WAITING, PREFILL, RUNNING, FINISHED = "waiting", "prefill", "running", \
    "finished"
CANCELLED, FAILED, SHED = "cancelled", "failed", "shed"
# HANDOFF: a prefill-worker request whose finished prompt KV (page
# chain + first token) was handed to a decode worker — terminal for
# THIS scheduler, live for the cluster request it belongs to
HANDOFF = "handoff"
TERMINAL = (FINISHED, CANCELLED, FAILED, SHED, HANDOFF)

# the phases of one step() at depth one inside "step": exhaustive and
# disjoint, so their seconds sum to the step's wall (docs/observability.md)
STEP_PHASES = ("chain", "device_wait", "harvest", "sweep", "admit",
               "prefill", "spec_dispatch", "horizon_dispatch", "observe")
# the two phases in which the host only waits for the device
BLOCKED_PHASES = ("device_wait", "first_token_wait")
# (cat, track) of the phases that were SpanTracer spans before they were
# phases: a Perfetto trace keeps its rows
PHASE_SPANS = {"device_wait": ("device", "device"),
               "harvest": ("dispatch", "scheduler"),
               "horizon_dispatch": ("dispatch", "scheduler"),
               "prefill_chunk": ("dispatch", "scheduler")}
# a step longer than this is logged with its split by phase
SLOW_STEP_S = 1.0


def _geometric_buckets(lo, hi, factor=2):
    """``lo, factor*lo, factor**2*lo, ... , hi`` (``hi`` itself always
    included): the small constant set a traced size is quantized to, so
    compiled signatures stay bounded by the set (horizons, spec K,
    prefill rows, sequence-parallel chunks)."""
    buckets, b = [lo], lo
    while b < hi:
        b = min(b * factor, hi)
        buckets.append(b)
    return buckets


# rows up to which a prefill dispatch sits near its weight-read floor
# (16 rows x a 32-token chunk = 512 tokens; a v5e's FLOPs meet a
# weight's bytes at ~240)
PREFILL_COARSE_ROWS = 16


def _prefill_row_buckets(num_slots):
    """Row buckets of the batched prefill dispatch: powers of four up
    to ``PREFILL_COARSE_ROWS``, powers of two above, ``num_slots``
    always last -- no dispatch of more than 16 rows pads by over 2x
    (why: the comment where ``ServingScheduler`` builds the set)."""
    coarse = _geometric_buckets(
        1, min(num_slots, PREFILL_COARSE_ROWS), factor=4)
    return coarse + _geometric_buckets(coarse[-1], num_slots)[1:]


def _bucket_ceil(buckets, n):
    """Smallest bucket covering ``n`` (the largest when none does)."""
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


# what the slot-bound horizon rule did in a step it did not run in:
# (chose under the configured pick, P seconds, D seconds)
_NO_TURNOVER = (False, 0.0, 0.0)
# the first half of a _StepCost form in which the decoding rows rode
# the prefill dispatch: (RIDE, the horizon that followed)
RIDE = "ride"


class _StepCost:
    """What a scheduler step has been costing by its form: the recent
    walls of the cycles in which a prefill dispatch rode, a short deque
    a form.  A form is the decode horizon ``h`` the step carried beside
    the dispatch (boundary work + the prefill's device time + ``h``
    decode passes), or ``(RIDE, h)`` where the rows decoding rode the
    dispatch as one-token rows and a horizon of ``h`` followed (0:
    none did).  ``estimate()`` reads the plain forms as ``wall = P +
    h * D``: ``D`` (one decode pass) is the median slope between the
    buckets' medians, ``P`` (everything else a step costs) what that
    leaves of the bucket sampled last; a ride form is read by its own
    median (``median``), since what a rider costs the dispatch is not
    a decode pass.  Measured, never configured: P / D is 3.5 for one
    model and traffic and 10 for another."""

    KEEP = 16   # walls kept a form: the newest push the oldest out
    ENOUGH = 3  # walls from which a form's median counts (one stalled
                # step among three does not move it)
    MARGIN = 0.05   # the share by which a ride form has to undercut the
                    # best plain one: a form's median moves by 2-3%
                    # between runs of one program, and forms that tie
                    # within that must not trade places by the run

    def __init__(self):
        self.walls = {}     # form -> deque of recent cycle walls, s
        self.last = None    # the horizon sampled last (plain forms)

    def add(self, form, wall_s):
        self.walls.setdefault(
            form, deque(maxlen=self.KEEP)).append(wall_s)
        if not isinstance(form, tuple):
            self.last = form

    def median(self, form):
        """The median wall of ``form``, or None under ``ENOUGH``."""
        walls = self.walls.get(form, ())
        return float(np.median(walls)) if len(walls) >= self.ENOUGH \
            else None

    def estimate(self):
        """``(P, D)`` in seconds, or None while fewer than two buckets
        have ``ENOUGH`` samples or their medians do not rise with the
        horizon."""
        med = {h: float(np.median(w)) for h, w in self.walls.items()
               if len(w) >= self.ENOUGH and not isinstance(h, tuple)}
        if len(med) < 2:
            return None
        hs = sorted(med)
        d = float(np.median([(med[b] - med[a]) / (b - a)
                             for i, a in enumerate(hs)
                             for b in hs[i + 1:]]))
        if d <= 0:
            return None
        last = self.last if self.last in med else hs[-1]
        return max(0.0, med[last] - last * d), d

    def wanting(self, forms):
        """The first of ``forms`` with fewer than ``ENOUGH`` samples,
        or None."""
        for f in forms:
            if len(self.walls.get(f, ())) < self.ENOUGH:
                return f
        return None


class _PoolsRef:
    """Mutable holder for the device-resident KV pools.  The jitted
    primitives are functional — every dispatch consumes the pools and
    returns replacements — so two schedulers sharing one physical pool
    (a disaggregated prefill/decode pair) must also share ONE mutable
    reference to the current arrays, or one side would keep dispatching
    against donated-away buffers."""

    __slots__ = ("pools",)

    def __init__(self, pools):
        self.pools = pools


class QueueFull(RuntimeError):
    """Backpressure: the waiting queue is at max_queue."""


class Request:
    """One generation request flowing through the scheduler."""

    _next_id = 0

    def __init__(self, prompt, max_new_tokens, eos_token_id=None,
                 on_token=None, rid=None, deadline_s=None):
        if rid is None:
            rid = Request._next_id
            Request._next_id += 1
        self.rid = rid
        # span identity: the id every trace span of this request
        # carries.  Locally it is the rid; the cluster router overrides
        # it (via submit's trace_ctx) with the journal rid so one client
        # request's spans share one id across replicas and processes
        self.trace_rid = rid
        self.orig_prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        self.prompt = list(self.orig_prompt)   # grows on preemption
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.on_token = on_token
        self.out_tokens = []
        # tokens sampled for this request on the device and not pulled
        # yet: 1 while the prefill dispatch that computed its next
        # token is in flight across a step boundary (_launch_boundary)
        self.owed = 0
        self.state = WAITING
        self.prefill_pos = 0
        self.cached_prefix_tokens = 0   # prefix-cache reuse at last admit
        # per-request memory attribution (MemTelemetry; 0 when off):
        # pages-held high-water mark and the page-seconds integral —
        # the unit the autotuner's cost model and per-tenant quotas
        # will bill in (reported in ds_serve rows and summary())
        self.pages_hwm = 0
        self.page_seconds = 0.0
        self.error = None            # reason string for failed/shed
        self.handoff = False         # prefill-worker mode (see submit)
        # decoding policy (serving/sampling/): per-request params, PRNG
        # seed, grammar cursor, and the position base for the
        # position-keyed sample stream.  Token n of the request draws
        # from fold_in(PRNGKey(seed), sample_offset + n) — sample_offset
        # counts tokens emitted in a PREVIOUS life of this request
        # (replica failover folds them into the prompt), so replay
        # continues the exact stream instead of restarting it.
        self.sampling = GREEDY
        self.seed = 0
        self.sample_offset = 0
        self.grammar = None          # GrammarConstraint cursor or None
        self.cancelled = False
        self.t_submit = time.monotonic()
        self.deadline = None if deadline_s is None \
            else self.t_submit + float(deadline_s)
        self.t_admit = None
        self.t_first = None
        self.t_last = None
        # multi-tenant serving tier (serving/tenancy/): the owning
        # tenant, the named LoRA adapter it asked for, and the dense
        # adapter-store id (-1 = base model).  All None/-1 with
        # tenancy off — no path reads them then.
        self.tenant = None
        self.adapter = None
        self.adapter_id = -1

    @property
    def remaining_new(self):
        return self.max_new_tokens - len(self.out_tokens) - self.owed

    def cancel(self):
        """Request cancellation; honored at the next step boundary (the
        scheduler releases the pages then). Idempotent; a no-op once
        the request is terminal."""
        self.cancelled = True

    def past_deadline(self, now):
        return self.deadline is not None and now > self.deadline

    def _finished_by(self, tok):
        return (self.eos_token_id is not None and
                tok == self.eos_token_id) or \
            len(self.out_tokens) >= self.max_new_tokens


class ServingScheduler:
    """Continuous-batching serving loop over an ``InferenceEngine``."""

    def __init__(self, engine, *, num_slots=8, num_pages=64, page_size=None,
                 max_pages_per_slot=None, prefill_chunk=16,
                 seq_parallel_threshold=None, prefill_reserve_frac=None,
                 max_queue=256,
                 monitor=None, do_sample=False, temperature=1.0, top_k=0,
                 top_p=1.0, completed_history=4096, decode_horizon_steps=8,
                 overlap=True, prefix_cache=False, prefix_cache_pages=None,
                 spec_decode=None, spec_k=8, spec_drafter=None,
                 kv_dtype=None,
                 shared_pool=None, pools_ref=None, on_handoff=None,
                 tracer=None, mem_telemetry=False, audit_every=None,
                 comm_telemetry=False, compile_watchdog=None,
                 online_tuner=None, tuned_from=None, tenancy=None):
        if page_size is None:
            page_size = default_page_size()
        self.engine = engine
        # per-request span tracing (serving/trace.py).  The default is
        # the shared no-op tracer: with tracing off every call site
        # costs one attribute load and a falsy check — tokens, compile
        # signatures and the hot loop are byte-identical (pinned by
        # tests/unit/test_trace.py).  Tracing is pure host bookkeeping:
        # no device op, no new jit signature, ever.
        # the named phases of step(): each is an event of a device
        # profile, seconds summary() reads, and a span of the tracer
        # (the ``tracer`` setter hands them the tracer, so a replica
        # that swaps tracers swaps theirs too)
        self.phases = Phases(prefix="ds.sched.", spans=PHASE_SPANS)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._slow_steps = 0
        self._slow_step_max_s = 0.0
        self._slow_step_max_blocked_s = 0.0
        self._slow_step_log = deque(maxlen=8)
        self._t_start = time.monotonic()
        self.num_slots = int(num_slots)
        self.prefill_chunk = int(prefill_chunk)
        self.max_queue = int(max_queue)
        # multi-tenant serving tier (serving/tenancy/): a TenantRegistry
        # turns on per-tenant quotas, weighted-fair admission, adapter
        # entitlements and prefix-cache namespaces.  tenancy=None (the
        # default) keeps every scheduler path byte-identical to the
        # pre-tenancy code: no extra arrays, no extra jit signatures
        # (pinned by tests/unit/test_tenancy.py).
        self.tenancy = tenancy if tenancy else None
        if self.tenancy is not None and not mem_telemetry:
            # quotas bill in page-seconds: the PR-11 meter must run
            mem_telemetry = True
        self._adapter_ids = None if self.tenancy is None \
            else np.full(num_slots, -1, np.int32)
        if max_pages_per_slot is None:
            max_pages_per_slot = -(-num_pages // 2) or 1
        self.kv = PagedKVManager(num_pages, page_size, num_slots,
                                 max_pages_per_slot, pool=shared_pool)
        # THE rule for a model that keeps state per slot beside the
        # page pool — a recurrent layer's, a window layer's ring —
        # (engine.refuse_slot_state; ops/ssm/state.py says why):
        # what cannot carry a state is refused BY NAME, never served
        # wrong.  A prefix cache is on by default (bin/ds_serve), so it
        # is switched off with its reason in health(); speculation, the
        # sequence-parallel prefill and a page-chain hand-off were asked
        # for and raise.  Continuous batching, chunked prefill, fused
        # horizons, overlap, slot reuse and recompute-preemption carry a
        # state as they carry pages.
        self.slot_state = bool(getattr(engine, "slot_state", None))
        self._refuse = getattr(engine, "refuse_slot_state",
                               lambda feature: None)
        self.prefix_cache_refused = \
            engine.slot_state_refusal("prefix_cache") \
            if prefix_cache and self.slot_state else None
        if self.prefix_cache_refused is not None:
            prefix_cache = False
        for feature, asked in (
                ("spec_decode", spec_drafter is not None or
                 spec_decode not in (None, False, "off")),
                ("seq_parallel_prefill", bool(seq_parallel_threshold)),
                ("handoff", on_handoff is not None)):
            if asked:
                self._refuse(feature)
        # radix prefix cache: finished requests donate their full pages
        # to a token-keyed index; admissions longest-prefix match and
        # share the chain read-only. Cached pages are reclaimable
        # capacity (LRU-drained under pool pressure), never a leak.
        self.prefix_cache = None if not prefix_cache else PrefixCache(
            self.kv.pool, max_pages=prefix_cache_pages)
        # the device pools live behind a mutable ref so a disaggregated
        # prefill/decode pair (two schedulers, one physical pool) sees
        # each other's functional updates; standalone schedulers own a
        # private ref and behave exactly as before
        if pools_ref is None:
            # kv_dtype overrides the engine's configured kv_cache_dtype
            # for THIS scheduler's pools ("float32"/"bfloat16"/"int8"/
            # "fp8") — the serving autotuner varies it per trial on one
            # engine.  int8/fp8 pools carry parallel per-row f32 scale
            # pools; every host mechanism (COW, donation, truncate,
            # handoff) is dtype-blind because it moves page IDS
            # per-slot state (recurrent, a ring) is sized by the slot
            # count; a family without any never sees the argument
            slots = {"num_slots": self.num_slots} \
                if self.slot_state else {}
            pools_ref = _PoolsRef(engine.init_paged_cache(
                num_pages, page_size, kv_dtype=kv_dtype, **slots))
        elif kv_dtype is not None:
            raise ValueError(
                "kv_dtype cannot be set on a scheduler adopting shared "
                "pools (pools_ref=): the dtype is baked into the shared "
                "arrays — set it where the pools are built")
        self._pools_ref = pools_ref
        # live truth for health()/operators: derived from the allocated
        # leaves, not from config (a shared pool reports what it IS)
        from deepspeed_tpu.ops.quant.kv import kv_dtype_name, page_leaf
        self.kv_dtype_name = kv_dtype_name(next(
            entry for entry in self._pools_ref.pools["layers"]
            if page_leaf(entry) is not None))
        # prefill-worker hook: a request submitted with handoff=True
        # finishes its prompt, emits the boundary token, and hands its
        # page chain to this callback instead of decoding on
        self.on_handoff = on_handoff
        self._pending_attach = deque()  # handoff chains awaiting a slot
        self.draining = False
        # mesh topology snapshot: the pools (and weights) are live on
        # the engine's device mesh now — record the shape and per-device
        # KV footprint once so health()/monitor sinks expose the actual
        # serving topology (page bookkeeping below stays mesh-agnostic:
        # page ids are global, only the KV arrays shard)
        self.mesh_info = engine.serving_mesh_info(
            self.pools, num_slots=num_slots) \
            if hasattr(engine, "serving_mesh_info") else {}
        self.lengths = np.zeros(num_slots, np.int32)
        self.last_tok = np.zeros(num_slots, np.int32)
        self.slot_req = [None] * num_slots
        self.waiting = deque()
        self.requests = {}           # rid -> LIVE request only
        # bounded terminal history: a long-running server retires
        # requests out of the live map instead of keeping them forever
        self.completed = deque(maxlen=int(completed_history))
        self._collect = None         # active run()'s result accumulator
        self.metrics = ServingMetrics(monitor)
        # memory telemetry (serving/mem_telemetry.py): page-state
        # attribution, per-request page-seconds, pressure forensics.
        # Off is the shared NULL_MEM singleton — one attribute load and
        # a falsy check per call site, tokens and compile counts
        # byte-identical (pinned by tests/unit/test_mem_telemetry.py).
        # Pass True for defaults or a MemTelemetry instance for custom
        # pressure thresholds / an attached FlightRecorder.
        if isinstance(mem_telemetry, MemTelemetry):
            if mem_telemetry.metrics is not None:
                # an instance shared by two schedulers would cross-wire
                # their gauges and corrupt both page-seconds clocks —
                # one MemTelemetry per scheduler, always
                raise ValueError(
                    "this MemTelemetry instance is already bound to "
                    "another scheduler; pass mem_telemetry=True (or a "
                    "fresh instance) per scheduler")
            self.mem = mem_telemetry
        elif mem_telemetry:
            self.mem = MemTelemetry()
        else:
            self.mem = NULL_MEM
        if self.mem.enabled:
            self.mem.bind(self.metrics, self.tracer)
            # page-granular churn events ride the pool's observer hook
            # (None when telemetry is off — the zero-cost path)
            self.kv.pool.observer = self.mem.on_pool_event
        # refcount invariant auditor: with audit_every=N every N-th
        # BARRIER step cross-checks pool refcounts against the slot
        # tables + prefix trie + parked handoff chains and raises
        # AuditError on a leak/double-free/orphan.  A shared
        # (disaggregated) pool is audited structurally only — peers
        # hold references this scheduler cannot see; the exact census
        # runs fleet-side via ClusterRouter.audit().
        self.audit_every = None if not audit_every else int(audit_every)
        self._pool_shared = shared_pool is not None
        # COMMS+COMPILE observability (the third telemetry axis after
        # time [PR 8/9] and memory [PR 11]).  comm_telemetry=True arms
        # (a) the engine's HLO comm-ledger capture — the static bytes-
        # per-axis analysis comm_ledger() computes on demand — and (b)
        # a recompile watchdog: every jit cache miss becomes a
        # `compile` span, and signature churn after warmup fires a
        # tracer instant + flight dump (compile-storm detection).  Off
        # is a None check per dispatch; tokens and compile counts are
        # byte-identical (pinned by tests/unit/test_comm_telemetry.py).
        # Pass a tracing.CompileWatchdog instance for custom warmup /
        # an attached FlightRecorder.
        from deepspeed_tpu.tracing import CompileWatchdog
        self.comm_telemetry = bool(comm_telemetry)
        if isinstance(compile_watchdog, CompileWatchdog):
            wd = compile_watchdog
            if wd.tracer is NULL_TRACER:
                wd.tracer = self.tracer
            if wd.metrics is None:
                wd.metrics = self.metrics
        elif compile_watchdog or comm_telemetry:
            # REUSE the engine's existing watchdog when one is armed:
            # compile counters, steady state and the flight-recorder
            # wiring are ENGINE-lifetime facts — a replica fleet (or a
            # rolling restart) sharing one engine must not reset storm
            # detection or orphan the counts with every fresh
            # scheduler.  The tracer/metrics funnels rebind to the
            # newest scheduler (last-wins, like the capture itself).
            wd = getattr(engine, "_compile_watchdog", None)
            if wd is None:
                wd = CompileWatchdog(tracer=self.tracer,
                                     metrics=self.metrics)
            else:
                wd.bind(tracer=self.tracer
                        if self.tracer is not NULL_TRACER else None,
                        metrics=self.metrics)
        else:
            wd = None
        self.compile_watchdog = wd
        # the watchdog/capture live on the (possibly shared) ENGINE:
        # last scheduler wins, and a telemetry-OFF scheduler DISARMS
        # stale state a dropped telemetry-on scheduler left behind —
        # otherwise its dispatches would keep paying the probes and
        # feeding a dead scheduler's watchdog (zero-cost-off contract)
        if hasattr(engine, "set_compile_watchdog"):
            if wd is not None or \
                    getattr(engine, "_compile_watchdog", None) is not None:
                engine.set_compile_watchdog(wd)
        if hasattr(engine, "enable_comm_telemetry"):
            if self.comm_telemetry:
                engine.enable_comm_telemetry()
            elif getattr(engine, "_comm_capture", None) is not None:
                engine.enable_comm_telemetry(False)
        self._comm_summary = None       # comm_ledger()'s health cache
        if self.mesh_info:
            self.metrics.record_mesh(self.mesh_info)
        # the window a ring holds and its bytes a slot, (0, 0) without
        self._window, ring_bytes = getattr(
            engine, "window_ring", lambda: (0, 0))()
        if self.slot_state:
            self.metrics.record_state_pool(
                self.mesh_info.get("state_pool_bytes_total", 0),
                paged_bytes_per_token=engine.kv_page_bytes(page_size)
                // page_size, window_bytes_per_slot=ring_bytes)
        # a latent page pool's bytes a token, published and as stored
        self.metrics.record_latent_pool(*getattr(
            engine, "latent_bytes_per_token", lambda: (0, 0))())
        if self.prefix_cache_refused is not None:
            self.metrics.record_prefix_refused()
        # the paged_prefill kernel's own count of a dispatch's pages
        # and grid steps (None: no page pool)
        self._count_key_blocks = getattr(
            engine, "prefill_key_block_counter", lambda *_: None)(
                self.pools, self.prefill_chunk)
        self.step_idx = 0
        self._ema_step_s = None      # EWMA of step wall time (health)
        # admission feasibility uses the MEDIAN of a recent window, not
        # the EWMA: one jit-compile step (seconds) would otherwise
        # dominate the estimate for dozens of steps and shed perfectly
        # serviceable deadline-bearing requests after every cold start
        self._step_window = deque(maxlen=16)
        # the step's form under slot-bound load (_plan_ride,
        # _pick_horizon): what a step has been costing by form, whether
        # this step's admission left requests waiting, and the clock its
        # samples are cut by (the last harvest's end, the end of a step
        # whose decode pass was its prefill dispatch -- _cycle_open says
        # so to the next step --, or the step's start)
        self._step_cost = _StepCost()
        self._slot_bound = False
        self._cycle_t0 = 0.0
        self._cycle_open = False
        self._prefill_rode = False
        self._turnover = _NO_TURNOVER   # the newest pick
        # the horizon that follows if this step's decoding rows ride its
        # prefill dispatch (None: they do not), and the rows that did
        self._ride = None
        self._riders = 0
        self._last_error = None
        # Router-HA fence state, set by the owning replica/worker:
        # the highest router epoch this scheduler has served under and
        # how many stale-epoch dispatches/requests were fenced off
        self.ha_epoch = None
        self.ha_fenced = 0
        self.sampling = dict(do_sample=do_sample, temperature=temperature,
                             top_k=top_k, top_p=top_p)
        # Decoding-policy subsystem (serving/sampling/): `self.sampling`
        # stays the LEGACY greedy path's static kwargs; every request
        # additionally carries a per-request SamplingParams (defaulting
        # to the scheduler-level knobs above).  A dispatch whose batch
        # is pure greedy — and whose scheduler default is greedy — rides
        # the legacy signatures byte-identically; anything else routes
        # through the policy twins (decode_multi_policy /
        # verify_multi_policy), where every knob is a traced per-slot
        # lane: ONE compiled signature per horizon/K bucket regardless
        # of the greedy/sampled/penalized/constrained mix.
        self.default_sampling = SamplingParams(
            do_sample=do_sample, temperature=temperature, top_k=top_k,
            top_p=top_p)
        self._default_greedy = self.default_sampling.is_greedy
        # per-slot policy mirrors, staged into device lanes at dispatch
        # (no-op encodings for greedy slots — see sampling/params.py)
        self._samp_temps = np.zeros(num_slots, np.float32)
        self._samp_topk = np.zeros(num_slots, np.int32)
        self._samp_topp = np.ones(num_slots, np.float32)
        self._samp_rep = np.ones(num_slots, np.float32)
        self._samp_pres = np.zeros(num_slots, np.float32)
        self._samp_freq = np.zeros(num_slots, np.float32)
        self._samp_keys = np.zeros((num_slots, 2), np.uint32)
        self._tok_counts = None      # lazy [num_slots, vocab] int32
        self._grammar_masks = None   # lazy [num_slots, vocab] bool
        self._grammar_cache = {}     # spec json -> prototype cursor
        # fused decode horizons: power-of-two buckets up to the max so
        # varying horizon choices share a bounded set of compiled
        # signatures (decode_horizon_steps=1 recovers the legacy
        # one-token-per-step loop exactly)
        self.decode_horizon_steps = max(1, int(decode_horizon_steps))
        self.horizon_buckets = _geometric_buckets(
            1, self.decode_horizon_steps)
        # batched prefill: every prefilling slot's next chunk rides ONE
        # [rows, prefill_chunk] dispatch per boundary step; the row
        # count pads up to a bucket (the last is num_slots), so the
        # compile count is pinned by the bucket set exactly like decode
        # horizons.  The set bounds two costs.  Signatures: every
        # bucket is one more trace + load of the whole model at
        # start-up (1.1-2.0 s each on a v5e host, PERF.md PR 28), so up
        # to 16 rows the step is x4 -- 16 rows x 32 = 512 tokens is
        # ~20 ms on a 16-layer 7B against a 9 ms weight read, and the
        # worst padding (5 -> 16 rows) wastes ~10 ms.  A padding row:
        # above 512 tokens the dispatch is compute-bound and a padding
        # row costs what a prompt row costs (17 rows in a 64-row
        # bucket wasted 50 of Falcon-H1's 105 ms, every step, PERF.md
        # PR 41), so from 16 rows up the step is x2
        self.prefill_row_buckets = _prefill_row_buckets(self.num_slots)
        # ---- sequence-parallel prefill routing (long-context path) ----
        # prompts with >= seq_parallel_threshold tokens left to prefill
        # route through engine.prefill_sequence_parallel: the chunk
        # shards over the mesh's `sequence` axis, so one step retires
        # axis_size x the per-device chunk rows.  The transport
        # (ulysses vs ring) was resolved ONCE by the engine against the
        # mesh + model (sharding.resolve_sequence_plan); an unusable
        # axis degrades every routed prompt to the chunked loop with a
        # `serving/seq_prefill/degraded` breadcrumb instead of failing.
        # Chunk lengths quantize to power-of-two multiples of the axis
        # size up to prefill_chunk * axis_size, so the compile count is
        # pinned by the bucket set exactly like decode horizons.
        self.seq_parallel_threshold = int(seq_parallel_threshold or 0)
        self.seq_plan = None
        self.sp_chunk_buckets = []
        self._sp_degrade_reason = None
        if self.seq_parallel_threshold > 0:
            plan = getattr(engine, "seq_parallel_plan", lambda: None)()
            if plan is not None and plan.usable:
                self.seq_plan = plan
                self.sp_chunk_buckets = _geometric_buckets(
                    plan.size, self.prefill_chunk * plan.size)
            else:
                self._sp_degrade_reason = None if plan is None \
                    else plan.reason
        # fairness: cap the pages ONE prefilling request may pre-reserve
        # up front to this fraction of the pool (None = num_pages — the
        # admission-time free-pages check is then the only gate).  A
        # routed prompt whose full chain exceeds the cap is shed with
        # an explicit reason instead of starving every waiting admission
        # behind a monopolized pool.
        self.prefill_reserve_frac = None if prefill_reserve_frac is None \
            else float(prefill_reserve_frac)
        self.prefill_reserve_cap = self.kv.pool.num_pages \
            if self.prefill_reserve_frac is None else \
            max(1, int(self.kv.pool.num_pages * self.prefill_reserve_frac))
        self.overlap = bool(overlap)
        self._inflight = deque()       # dispatched horizons, FIFO, depth<=2
        # prefill dispatches whose sampled tokens are still on the
        # device (_launch_boundary), oldest first: one across a step
        # boundary, two between a step's launch and its pull; and the
        # sampled tokens by slot, the device's last_tok, that the next
        # dispatch's riders read their input id from
        self._pf_flight = deque()
        self._dev_tok = None
        self._boundary_now = False     # this step's dispatch samples a row
        self._harvest_first = None     # why this step harvested first
        self._zombies = set()          # slots terminated host-side while a
                                       # chained horizon still runs them
        self._chain_budgets = None     # budgets baseline for the live chain
        self._eos_ids = np.full(num_slots, -1, np.int32)
        self._tok_window = deque(maxlen=32)   # per-token wall time samples
        # speculative decoding: a drafter proposes K tokens per slot,
        # ONE verify_multi dispatch scores them (greedy-only — the
        # acceptance test replays the temperature=0 argmax contract, so
        # sampled mode disables spec rather than silently changing the
        # sampled stream)
        self.spec_k = max(1, int(spec_k))
        self.spec_k_buckets = _geometric_buckets(1, self.spec_k)
        self._spec = None
        self.spec_mode = "off"
        greedy = not do_sample or not temperature
        if spec_decode not in (None, False, "off", "ngram", "draft"):
            # validate the mode string unconditionally — a typo must not
            # slip through just because a custom drafter was supplied
            # (custom drafters pass spec_decode=None and name themselves
            # via their .name attribute)
            raise ValueError(f"unknown spec_decode mode {spec_decode!r}; "
                             "pick 'ngram', 'draft' (+spec_drafter) or "
                             "'off'")
        if spec_decode in ("off", False):
            pass  # explicit off wins even when a drafter is supplied
        elif spec_drafter is not None:
            self._spec = spec_drafter
            self.spec_mode = spec_decode or getattr(spec_drafter, "name",
                                                    "custom")
        elif spec_decode in ("ngram",):
            from deepspeed_tpu.serving.spec_decode import NgramDrafter
            self._spec = NgramDrafter()
            self.spec_mode = "ngram"
        elif spec_decode == "draft":
            raise ValueError(
                "spec_decode='draft' needs a spec_drafter="
                "DraftModelDrafter(...) carrying the draft engine")
        # Capability gate (replacing the old greedy-only gate): lossless
        # leftover-probability verification makes speculation
        # distribution-exact under ANY sampling policy, so sampled+spec
        # composes whenever the drafter opts in (`supports_sampling` —
        # True for the stock point-mass drafters).  A drafter without
        # the capability only loses SAMPLED slots' proposals; with a
        # sampled scheduler-wide default that is every slot, so spec is
        # disabled up front with a distinct reason.
        if self._spec is not None and not greedy and \
                not getattr(self._spec, "supports_sampling", False):
            self._spec = None
            self.spec_mode = "off (drafter lacks supports_sampling)"
        # online autotuner (autotuning/serving/online.py): bounded
        # nudges of the safely-re-resolvable knobs (decode horizon,
        # spec-K ceiling, prefix-cache retention split) from the live
        # gauges, applied at BARRIER steps only.  Off is None — one
        # falsy check per step, tokens and compile counts byte-identical
        # (pinned by tests/unit/test_serving_autotune.py).  Pass True
        # for defaults or an OnlineTuner instance for custom
        # thresholds; an instance already bound elsewhere is rejected
        # at bind (the MemTelemetry sharing rule).
        if online_tuner is True:
            from deepspeed_tpu.autotuning.serving.online import OnlineTuner
            online_tuner = OnlineTuner()
        self.online = online_tuner if online_tuner else None
        if self.online is not None:
            self.online.bind(self)
        # provenance of a tuner-emitted config (ds_serve --tuned-config
        # PATH): echoed through health() so an operator can tell a
        # hand-set config from a searched one
        self.tuned_from = tuned_from

    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, value):
        self._tracer = self.phases.tracer = value

    @property
    def pools(self):
        return self._pools_ref.pools

    @pools.setter
    def pools(self, value):
        self._pools_ref.pools = value

    # ------------------------------------------------------------- intake
    def submit(self, prompt, max_new_tokens=32, eos_token_id=None,
               on_token=None, deadline_s=None, handoff=False,
               trace_ctx=None, sampling=None, seed=None, grammar=None,
               sample_offset=0, tenant=None, adapter=None):
        """Queue a request; raises :class:`QueueFull` at max_queue (the
        backpressure signal callers turn into 429/retry). ``deadline_s``
        is a relative budget: a request that cannot finish inside it is
        shed instead of served late.  ``handoff=True`` marks a
        prefill-worker request: it stops after the boundary token and
        hands its KV page chain to ``on_handoff`` (disaggregated
        serving).  ``trace_ctx`` (``{"trace_id": ..., "attempt": n}``)
        propagates a cluster-level trace id so this scheduler's spans
        for the request share the journal rid across replicas.

        Decoding policy (per request): ``sampling`` is a
        :class:`~deepspeed_tpu.serving.sampling.SamplingParams` or wire
        dict overriding the scheduler-level default; ``seed`` keys the
        request's position-keyed PRNG stream (default 0 — deterministic
        and replayable); ``grammar`` is a constraint spec
        (``{"regex": ...}`` / ``{"json_schema": ...}`` /
        ``{"response_format": "json_object"}``) compiled host-side to a
        per-step allowed-token mask; ``sample_offset`` counts tokens a
        previous life of this request already emitted (failover replay
        folds them into the prompt), so the PRNG stream and grammar
        cursor CONTINUE instead of restarting.

        Tenancy (``tenancy=`` on the scheduler): ``tenant`` names the
        owning :class:`~deepspeed_tpu.serving.tenancy.TenantConfig`
        (required — every request must be attributable for quota and
        billing); ``adapter`` optionally names a LoRA adapter from the
        tenant's entitlement set (None = base model)."""
        if self.draining:
            raise QueueFull("scheduler is draining (shutdown/restart in "
                            "progress); resubmit elsewhere")
        t_cfg, adapter_id = self._resolve_tenant(tenant, adapter)
        if len(self.waiting) >= self.max_queue:
            raise QueueFull(
                f"waiting queue at max_queue={self.max_queue}")
        need = len(np.asarray(prompt).reshape(-1)) + int(max_new_tokens)
        cap = min(self.kv.max_tokens_per_slot(),
                  self.kv.pool.num_pages * self.kv.page_size)
        if need > cap:
            raise ValueError(
                f"request of {need} tokens exceeds per-slot capacity {cap} "
                "(min(max_pages_per_slot, num_pages) * page_size)")
        if handoff:
            self._refuse("handoff")
        req = Request(prompt, max_new_tokens, eos_token_id, on_token,
                      deadline_s=deadline_s)
        req.handoff = bool(handoff)
        if t_cfg is not None:
            req.tenant = t_cfg.name
            req.adapter = adapter
            req.adapter_id = adapter_id
        if trace_ctx is not None and trace_ctx.get("trace_id") is not None:
            req.trace_rid = trace_ctx["trace_id"]
        self._apply_policy(req, sampling, seed, grammar, sample_offset)
        self._check_adapter_policy(req)
        if req.max_new_tokens <= 0:
            # parity with generate(max_new_tokens=0): nothing to emit —
            # but it still counts as completed, so health()/summary
            # reconcile with the per-request rows ds_serve reports
            req.state = FINISHED
            self.completed.append(req)
            self.metrics.record_completion(self.step_idx)
            return req
        self.requests[req.rid] = req
        self.waiting.append(req)
        return req

    # ------------------------------------------------- decoding policy
    def _apply_policy(self, req, sampling, seed, grammar, sample_offset):
        """Attach the per-request decoding policy at intake (submit /
        attach_handoff).  Grammar compilation is host work and can
        raise — intake is the right place to reject a bad spec, before
        any pages are held.  A replayed request (``sample_offset > 0``,
        or handoff tokens already in ``out_tokens``) advances the fresh
        grammar cursor through everything previously emitted, so the
        constraint state survives preemption and failover exactly."""
        req.sampling = SamplingParams.from_dict(
            sampling, defaults=self.default_sampling)
        req.seed = 0 if seed is None else int(seed)
        req.sample_offset = max(0, int(sample_offset))
        if grammar is not None:
            req.grammar = self._compile_grammar(grammar, req.eos_token_id)
            if req.sample_offset:
                req.grammar.replay(req.prompt[-req.sample_offset:])
            if req.out_tokens:
                req.grammar.replay(req.out_tokens)
        if req.sampling.needs_policy or req.grammar is not None:
            self.metrics.record_policy_request(
                self.step_idx, sampled=not req.sampling.is_greedy,
                grammar=req.grammar is not None)

    def _compile_grammar(self, spec, eos_token_id):
        """Spec dict -> fresh :class:`GrammarConstraint` cursor.  The
        DFA + token-mask compilation is cached per (spec, eos) — many
        requests sharing one schema share one TokenDFA (and its lazily
        built per-state mask rows); each request gets its own cursor."""
        if hasattr(spec, "token_mask"):     # pre-built cursor
            return spec
        key = (json.dumps(spec, sort_keys=True),
               None if eos_token_id is None else int(eos_token_id))
        proto = self._grammar_cache.get(key)
        if proto is None:
            proto = compile_grammar(spec, self._vocab_size(),
                                    eos_token_id=eos_token_id)
            self._grammar_cache[key] = proto
        return proto.fresh()

    def _vocab_size(self):
        v = self.mesh_info.get("vocab_size")
        if v is None:
            cfg = getattr(getattr(self.engine, "module", None), "cfg",
                          None)
            v = getattr(cfg, "vocab_size", None)
        if v is None:
            raise RuntimeError(
                "engine does not expose vocab_size; the decoding-policy "
                "tables (token counts / grammar masks) need it")
        return int(v)

    @staticmethod
    def _req_needs_policy(req):
        return req.sampling.needs_policy or req.grammar is not None

    def _batch_needs_policy(self, slots):
        """True when this dispatch must take the policy twins: any
        request samples/penalizes/constrains, or the scheduler-wide
        default is sampled (explicit-greedy requests under a sampled
        default still ride the policy path — its greedy lanes are
        argmax-exact — so the legacy kwargs are never repurposed)."""
        return (not self._default_greedy) or any(
            self._req_needs_policy(self.slot_req[s]) for s in slots)

    def _ensure_policy_tables(self):
        if self._tok_counts is None:
            v = self._vocab_size()
            self._tok_counts = np.zeros((self.num_slots, v), np.int32)
            self._grammar_masks = np.ones((self.num_slots, v), bool)

    def _seed_slot_policy(self, slot, req):
        """Stage one admitted request's policy into the slot mirrors.
        Counts seed from the request's TRUE token history
        (``orig_prompt + out_tokens`` — after a preemption the folded
        prompt already contains the emitted tokens, after a handoff the
        boundary token lives only in ``out_tokens``; the union covers
        both without double counting)."""
        if not (self._req_needs_policy(req) or
                self._tok_counts is not None):
            return
        self._ensure_policy_tables()
        sp = req.sampling
        self._samp_temps[slot] = sp.staged_temperature
        self._samp_topk[slot] = 0 if sp.is_greedy else sp.top_k
        self._samp_topp[slot] = 1.0 if sp.is_greedy else sp.top_p
        self._samp_rep[slot] = sp.repetition_penalty
        self._samp_pres[slot] = sp.presence_penalty
        self._samp_freq[slot] = sp.frequency_penalty
        self._samp_keys[slot] = request_key(req.seed)
        v = self._tok_counts.shape[1]
        hist = np.asarray(req.orig_prompt + req.out_tokens, np.int64)
        hist = hist[(hist >= 0) & (hist < v)]
        self._tok_counts[slot] = np.bincount(hist, minlength=v)[:v]
        self._grammar_masks[slot] = True if req.grammar is None \
            else req.grammar.token_mask()

    def _policy_args(self, running):
        """The staged per-slot policy arrays one dispatch consumes.
        ``tok_base`` is each request's absolute position base —
        ``sample_offset + len(out_tokens)`` — so the device's in-scan
        fold index (``tok_base + emitted``) is position-keyed across
        batching, chaining, preemption and failover."""
        self._ensure_policy_tables()
        base = np.zeros(self.num_slots, np.int32)
        for s in running:
            req = self.slot_req[s]
            base[s] = req.sample_offset + len(req.out_tokens)
        return dict(keys=self._samp_keys, tok_base=base,
                    temps=self._samp_temps, top_ks=self._samp_topk,
                    top_ps=self._samp_topp, rep_pens=self._samp_rep,
                    pres_pens=self._samp_pres, freq_pens=self._samp_freq,
                    counts=self._tok_counts, mask=self._grammar_masks)

    def _note_emitted(self, slot, req, tok):
        """Host policy bookkeeping for ONE delivered token: the count
        mirror and the grammar cursor.  A grammar rejection raises
        GrammarConstraintError into the caller's per-request
        containment (it is attributable to exactly this request)."""
        if self._tok_counts is not None and \
                0 <= tok < self._tok_counts.shape[1]:
            self._tok_counts[slot, tok] += 1
        if req.grammar is not None and not req.grammar.finished:
            try:
                req.grammar.advance(tok)
            except GrammarConstraintError:
                self.metrics.record_grammar_violation(self.step_idx,
                                                      req.rid)
                raise

    def _grammar_finished(self, req):
        """A constrained request finishes when its cursor is done (eos
        consumed, or the DFA has no continuation left) — even if the
        model never emits eos."""
        return req.grammar is not None and req.grammar.done

    # ----------------------------------------------------------- tenancy
    def _resolve_tenant(self, tenant, adapter):
        """Intake-side tenancy resolution -> (TenantConfig, adapter_id).
        With tenancy on every request must name a registered tenant (an
        unattributable request cannot be quota-gated or billed); with
        tenancy off the kwargs must stay unused."""
        if self.tenancy is None:
            if tenant is not None or adapter is not None:
                raise ValueError(
                    "tenant=/adapter= need ServingScheduler(tenancy="
                    "TenantRegistry(...)); this scheduler has no tenancy")
            return None, -1
        if tenant is None:
            raise ValueError(
                "tenancy is on: every submit()/attach_handoff() must "
                "name its tenant= for quota accounting and billing")
        return self.tenancy.resolve(tenant, adapter)

    def _check_adapter_policy(self, req):
        """Multi-LoRA rides the LEGACY greedy signatures only (the
        per-slot adapter gather is threaded through prefill /
        decode_multi / verify_multi, not the policy twins).  With
        adapters loaded, a policy-needing request — or a sampled
        scheduler default — would force the whole batch onto the policy
        path and silently drop its peers' adapter deltas, so it is
        rejected at intake instead."""
        if self.tenancy is None or self.tenancy.store is None or \
                not len(self.tenancy.store):
            return
        if self._req_needs_policy(req) or not self._default_greedy:
            raise ValueError(
                "multi-LoRA serving rides the greedy decode path: "
                "per-request sampling/grammar (and a sampled scheduler "
                "default) cannot batch with adapter slots — serve "
                "policy traffic from a scheduler without adapters")

    def _req_ns(self, req):
        """Prefix-cache namespace for one request: ``None`` (the legacy
        shared root) with tenancy off, else ``(tenant namespace,
        adapter)`` — cached KV depends on the adapter weights that
        wrote it, so the adapter is part of the key (the isolation
        oracle in tests/unit/test_tenancy.py)."""
        if self.tenancy is None or req.tenant is None:
            return None
        return self.tenancy.namespace(req.tenant, req.adapter)

    def _tenant_namespaces(self, tenant):
        """Every radix namespace a tenant's pages can live under: the
        base-model namespace plus one per entitled adapter."""
        t = self.tenancy.get(tenant)
        return [self.tenancy.namespace(t, a)
                for a in (None,) + tuple(t.adapters)]

    def _tenant_pages(self, tenant):
        """A tenant's CONCURRENT page footprint — the unit its
        ``page_quota`` caps: live slot pages + parked handoff chains +
        its namespaces' cached prefix pages, each physical page counted
        once (a cache page a live slot shares is still one page)."""
        held = set()
        for s in range(self.num_slots):
            r = self.slot_req[s]
            if r is not None and r.tenant == tenant:
                held.update(self.kv._slot_pages[s])
        for r in self._pending_attach:
            if r.tenant == tenant:
                held.update(r._attach[0])
        if self.prefix_cache is not None:
            for ns in self._tenant_namespaces(tenant):
                held.update(self.prefix_cache.ns_iter_pages(ns))
        return len(held)

    def _tenant_live(self, tenant):
        """True while the tenant has pages that will free on their own
        (running slots or parked handoff chains) — the at-quota case
        where its queue head WAITS instead of being shed."""
        return any(r is not None and r.tenant == tenant
                   for r in self.slot_req) or \
            any(r.tenant == tenant for r in self._pending_attach)

    def _adapter_args(self):
        """The (adapter_ids, device pack) side inputs one legacy
        dispatch carries.  (None, None) — the pre-tenancy leafless
        pytree, SAME jit signature — unless tenancy is on with a
        non-empty adapter store; with adapters loaded every dispatch
        carries the pack (ids are traced data, so adapter churn and
        base-only batches share one signature per horizon bucket)."""
        if self.tenancy is None or self.tenancy.store is None or \
                not len(self.tenancy.store):
            return None, None
        return self._adapter_ids, self.tenancy.store.pack()

    def _release_adapter(self, slot):
        if self._adapter_ids is not None:
            self._adapter_ids[slot] = -1

    def _pick_waiting(self, skip=frozenset()):
        """The next admission candidate (still IN ``self.waiting``):
        plain FIFO head with tenancy off; with tenancy on, weighted
        deficit round-robin over the per-tenant FIFO heads, costed in
        pages (``skip`` holds tenants parked at quota this round), so a
        burst tenant converges to its weight share of admissions and
        cannot starve a lighter one (the starvation oracle)."""
        if self.tenancy is None:
            return self.waiting[0] if self.waiting else None
        heads = {}
        for r in self.waiting:
            if r.tenant not in skip and r.tenant not in heads:
                heads[r.tenant] = r
        if not heads:
            return None
        costs = {t: max(1, self.kv.pool.pages_for_tokens(len(r.prompt)))
                 for t, r in heads.items()}
        return heads[self.tenancy.next_tenant(costs)]

    def _check_quota(self, req, need, protect):
        """Quota gate for one candidate admission.  Returns ``"admit"``,
        ``"wait"`` (at quota, but the tenant's own live/parked work
        will free pages — park its queue this round), or a shed-reason
        string (the request can never fit the quota).  A tenant over
        quota drains its OWN namespaces' cached pages first; it can
        never evict another tenant's pages (capacity isolation)."""
        if self.tenancy is None:
            return "admit"
        quota = self.tenancy.get(req.tenant).page_quota
        if quota is None:
            return "admit"
        if need > quota:
            return (f"tenant page quota: request needs {need} pages, "
                    f"{req.tenant}'s quota is {quota}")
        held = self._tenant_pages(req.tenant)
        over = held + need - quota
        if over > 0 and self.prefix_cache is not None:
            drained = 0
            for ns in self._tenant_namespaces(req.tenant):
                drained += self.prefix_cache.evict(over - drained,
                                                   protect, ns=ns)
                if drained >= over:
                    break
            if drained:
                self.metrics.record_cache_eviction(self.step_idx, drained)
                over -= drained
        if over <= 0:
            return "admit"
        if self._tenant_live(req.tenant):
            return "wait"
        return (f"tenant page quota: {req.tenant} holds {held} page(s) "
                f"+ {need} needed > quota {quota} with nothing left "
                "to drain")

    # --------------------------------------------------------- accounting
    def _emit(self, req, tok):
        # fault point: a raised exception here is attributable to THIS
        # request — the containment wrappers fail it, not the loop
        faults.fire("serve.request", step=self.step_idx, rid=req.rid)
        now = time.monotonic()
        tok = int(tok)
        req.out_tokens.append(tok)
        if req.t_first is None:
            req.t_first = now
            self.metrics.record_first_token(self.step_idx,
                                            now - req.t_submit)
        else:
            self.metrics.record_token(self.step_idx, now - req.t_last)
        req.t_last = now
        if req.on_token is not None:
            req.on_token(req, tok)

    def _finalize(self, req, state, reason=None):
        """Move a request from live bookkeeping to the bounded terminal
        history ("drain on retire")."""
        req.state = state
        if reason is not None:
            req.error = reason
        self.requests.pop(req.rid, None)
        self.completed.append(req)
        if self.tenancy is not None and req.tenant is not None:
            # chargeback at retirement: the PR-11 page-seconds integral
            # (and the hwm/token counters) land on the tenant's ledger
            # exactly once, whatever the terminal state
            self.tenancy.bill(req.tenant, page_seconds=req.page_seconds,
                              pages_hwm=req.pages_hwm,
                              tokens=len(req.out_tokens))
            if state in (FINISHED, HANDOFF):
                self.tenancy.note(req.tenant, "completed")
            elif state == SHED:
                self.tenancy.note(req.tenant, "shed")
        if self.tracer.enabled:
            # one span per request covering its whole scheduler life —
            # the top-level row a per-request trace view groups under
            args = {"state": state, "tokens": len(req.out_tokens)}
            if reason is not None:
                args["reason"] = reason
            self.tracer.complete("request", req.t_submit, time.monotonic(),
                                 cat="request", rid=req.trace_rid,
                                 args=args)

    def _donate_pages(self, slot, req):
        """Retirement hands the slot's FULL pages to the prefix cache
        instead of freeing them.  The true token sequence is
        ``orig_prompt + out_tokens`` — NOT ``req.prompt``, which after a
        preemption already contains the then-emitted tokens folded in
        (keying on it would duplicate them and donate pages under keys
        their KV does not match).  The KV-valid length drops the final
        sampled token (eos / budget boundary): it was never fed back, so
        its KV was never written — donating past it would break the
        coherence invariant.  Pages the cache declines (duplicate
        chains, cap) and the partial tail are released normally."""
        seq = req.orig_prompt + req.out_tokens
        # (a token still owed by a dispatch in flight is the final one:
        # no full page reaches it, so the keys are all on the host)
        n_full = max(0, len(seq) + req.owed - 1) // self.kv.page_size
        pages = self.kv.take_slot_pages(slot)
        keep, tail = pages[:n_full], pages[n_full:]
        leftover = self.prefix_cache.insert(
            seq, keep, ns=self._req_ns(req)) if keep else []
        self.kv.pool.free(leftover + tail)

    def _spec_release(self, slot, req):
        """Drop any drafter state for a vacated slot (every terminal and
        preemption path funnels through here, so a stateful drafter —
        the draft model's private KV pages — cannot leak)."""
        if self._spec is not None and req is not None:
            try:
                self._spec.on_release(slot, req)
            except Exception:   # a broken drafter must not break retire
                pass

    def _vacate(self, slot):
        """The slot's half of a retirement: its pages go to the prefix
        cache or back to the pool and the slot is free to admit into."""
        req = self.slot_req[slot]
        self._spec_release(slot, req)
        if self.prefix_cache is not None:
            self._donate_pages(slot, req)
        else:
            self.kv.release_slot(slot)
        self.slot_req[slot] = None
        self.lengths[slot] = 0
        self._release_adapter(slot)

    def _finish(self, req):
        """The request's half of a retirement."""
        self._finalize(req, FINISHED)
        if self._collect is not None:
            # run()'s result set stays complete even after the bounded
            # history evicts this request
            self._collect[req.rid] = list(req.out_tokens)
        self.metrics.record_completion(self.step_idx)

    def _retire(self, slot):
        req = self.slot_req[slot]
        rec = self._flight_of(slot)
        if rec is None:
            self._vacate(slot)
        else:
            # a prefill dispatch in flight computes one more row for
            # this slot (its token is dropped at that dispatch's pull):
            # the pages stay the slot's until then
            self._park(slot, rec, donate=req)
        self._finish(req)

    def _flight_of(self, slot):
        """The newest dispatch in flight that computes for the request
        now in ``slot`` past a token the host has not seen: a prefill
        dispatch that samples a row for it, or a horizon launched off
        the device's copy of its newest token (``ahead``); or None."""
        req = self.slot_req[slot]
        for rec in reversed(self._inflight):
            if slot in rec["ahead"] and rec["reqs"][slot] is req:
                return rec
        for rec in reversed(self._pf_flight):
            if any(s == slot and r is req for _, s, r, _ in rec["rows"]):
                return rec
        return None

    def _park(self, slot, rec, donate=None):
        """Empty ``slot`` of its request but keep its pages until the
        in-flight dispatch ``rec`` is pulled (``donate``: the finished
        request whose pages then go to the prefix cache)."""
        self._spec_release(slot, self.slot_req[slot])
        self.slot_req[slot] = None
        self._release_adapter(slot)
        self._zombies.add(slot)
        rec["release_after"].add(slot)
        if donate is not None:
            rec["donate"][slot] = donate

    def _release_parked(self, rec):
        """The dispatch ``rec`` is off the device: the slots parked on
        it give up their pages (a finished request's to the prefix
        cache) and may be admitted into."""
        for slot in rec["release_after"]:
            req = rec["donate"].get(slot)
            if req is not None and self.prefix_cache is not None:
                self._donate_pages(slot, req)
            else:
                self.kv.release_slot(slot)
            self.lengths[slot] = 0
            self._zombies.discard(slot)

    def _close_slot(self, slot, state, reason):
        """Terminal removal of a live slot for cancel/shed/fail: release
        pages at the step boundary, record the reason distinctly."""
        req = self.slot_req[slot]
        self._spec_release(slot, req)
        self.kv.release_slot(slot)
        self.slot_req[slot] = None
        self.lengths[slot] = 0
        self._release_adapter(slot)
        self._record_closed(req, state, reason)

    def _record_closed(self, req, state, reason):
        """The request's half of a cancel / shed / fail."""
        self._finalize(req, state, reason)
        self.metrics.record_terminal(self.step_idx, state, req.rid, reason)
        if state == FAILED:
            self._last_error = f"rid={req.rid}: {reason}"

    def _drop_waiting(self, req, state, reason):
        self._finalize(req, state, reason)
        self.metrics.record_terminal(self.step_idx, state, req.rid, reason)

    def _preempt_youngest(self, protect=None, chain=None):
        """Evict the most recently admitted live request (vLLM's
        recompute preemption), re-queueing it at the queue head. Returns
        the freed slot or None if there was nothing to evict.
        ``chain`` is the caller's pressure causal chain: the eviction is
        recorded on it with the victim's rid, so a forensics reader can
        answer "who was evicted, for whom, and after what"."""
        candidates = [s for s in range(self.num_slots)
                      if self.slot_req[s] is not None and s != protect]
        if not candidates:
            candidates = [protect] if protect is not None and \
                self.slot_req[protect] is not None else []
        if not candidates:
            return None
        if self.tenancy is not None and protect is not None and \
                self.slot_req[protect] is not None:
            # capacity isolation: a grower whose tenant is at/over its
            # quota preempts ITS OWN youngest request when it has one —
            # a quota-capped tenant never evicts another tenant's work
            grower = self.slot_req[protect].tenant
            quota = None if grower is None \
                else self.tenancy.get(grower).page_quota
            if quota is not None and self._tenant_pages(grower) >= quota:
                own = [s for s in candidates
                       if self.slot_req[s].tenant == grower]
                if own:
                    candidates = own
        victim = max(candidates, key=lambda s: self.slot_req[s].t_admit)
        req = self.slot_req[victim]
        if chain is not None:
            chain.add("evict", victim_slot=victim, victim_rid=req.rid,
                      pages_freed=len(self.kv._slot_pages[victim]))
        self._spec_release(victim, req)
        self.kv.release_slot(victim)
        self.slot_req[victim] = None
        self.lengths[victim] = 0
        self._release_adapter(victim)
        req.state = WAITING
        req.prompt = req.orig_prompt + req.out_tokens
        req.prefill_pos = 0
        self.waiting.appendleft(req)
        self.metrics.record_preemption(self.step_idx)
        if self.tenancy is not None and req.tenant is not None:
            self.tenancy.note(req.tenant, "preempted")
        return victim

    def _reclaim_cached(self, n_pages, protect=frozenset()):
        """Drain up to ``n_pages`` refcount-free cached pages (LRU) back
        into the free list.  Returns pages actually freed (0 when the
        cache is off, empty, or fully pinned by live sharers)."""
        if self.prefix_cache is None or n_pages <= 0:
            return 0
        freed = self.prefix_cache.evict(n_pages, protect)
        if freed:
            self.metrics.record_cache_eviction(self.step_idx, freed)
        return freed

    def _grow_or_evict(self, slot, target_len):
        """ensure_capacity with the reclaim/eviction policy behind it:
        under pool pressure, refcount-free CACHED pages drain first
        (they are reclaimable capacity, not live state), then the
        legacy preempt-the-youngest eviction runs. Returns False when
        ``slot`` itself was preempted. Raises
        :class:`PagePoolExhausted` on a genuine dead-end (cache drained
        AND no evictable victim) — callers shed the slot's request
        rather than letting the loop die.  Every pressure resolution
        records a causal chain on the memory telemetry (trigger ->
        drained cache pages -> evicted victim rid -> outcome); the
        no-pressure fast path records nothing."""
        req = self.slot_req[slot]
        chain = None
        try:
            faults.fire("serve.page_alloc", step=self.step_idx, slot=slot,
                        rid=None if req is None else req.rid)
        except PagePoolExhausted:
            # an injected exhaustion episode models pool pressure: the
            # cache must drain before any victim is shed — only a
            # drained cache makes the episode terminal
            if self.mem.enabled:
                chain = self._open_pressure_chain(
                    "grow", slot, req, target_len,
                    injected_exhaustion=True)
            drained = self._reclaim_cached(self.kv.pool.num_pages)
            if chain is not None and drained:
                chain.add("cache_drain", pages=drained)
            if not drained:
                if chain is not None:
                    chain.close("dead_end")
                raise
        while not self.kv.ensure_capacity(slot, target_len):
            if chain is None and self.mem.enabled:
                chain = self._open_pressure_chain("grow", slot, req,
                                                  target_len)
            # reclaim the whole known shortfall in ONE batched drain
            # (evict() amortizes its tree scans per layer, not per page)
            short = self.kv.pages_needed(slot, target_len) - \
                self.kv.pool.free_pages
            drained = self._reclaim_cached(max(1, short))
            if drained:
                if chain is not None:
                    chain.add("cache_drain", pages=drained)
                continue
            if self._inflight:
                # a prefill dispatch that goes out ahead of a horizon's
                # harvest grows from free pages only: the horizon's
                # slots give up theirs (retirements), or are evicted,
                # once its tokens are on the host
                self._harvest_first = "pages"
                self._harvest_all()
                continue
            if self._pf_flight:
                # a victim re-queues with its emitted tokens folded
                # into its prompt: every token has to be on the host
                self._pull_prefill("eviction")
                continue
            victim = self._preempt_youngest(protect=slot, chain=chain)
            if victim is None:
                if chain is not None:
                    chain.close("dead_end")
                raise PagePoolExhausted(
                    f"cannot grow slot {slot} to {target_len} tokens: "
                    "pool exhausted with no evictable request")
            if victim == slot:
                if chain is not None:
                    chain.close("self_preempted")
                return False
        if chain is not None:
            chain.close("grown")
        return True

    def _open_pressure_chain(self, trigger, slot, req, target_len,
                             **extra):
        return self.mem.chain(
            trigger, step=self.step_idx, slot=slot,
            rid=None if req is None else req.trace_rid,
            target_len=int(target_len),
            pages_needed=self.kv.pages_needed(slot, target_len),
            free_pages=self.kv.pool.free_pages, **extra)

    # ----------------------------------------------------- failure policy
    def _estimated_service_steps(self, req):
        """Scheduler iterations this request still needs if admitted
        now: remaining prefill chunks + one decode horizon per
        ``decode_horizon_steps`` remaining tokens (ignores queueing
        ahead of it — a deliberately optimistic bound, so shedding only
        fires on certainly-hopeless requests).  ``decode_horizon_steps``
        is what a step carries while nothing waits for a slot and the
        most it carries when something does (``_pick_horizon`` then
        asks ``_service_steps`` what each smaller bucket would cost),
        so this stays the optimistic bound under both.  With the prefix
        cache on, tokens a hit would skip are subtracted — a request
        the cache makes feasible must not be shed for the prefill it
        will never run (match() is a pure host trie walk, cheap enough
        to price in here)."""
        pending = max(0, len(req.prompt) - req.prefill_pos)
        if self.prefix_cache is not None and req.prefill_pos == 0 \
                and pending > 1:
            full, _, plen = self.prefix_cache.match(
                req.prompt, limit=len(req.prompt) - 1,
                ns=self._req_ns(req))
            pending = max(1, pending - len(full) * self.kv.page_size
                          - plen)
        return self._service_steps(pending, req.remaining_new,
                                   self.decode_horizon_steps)

    def _service_steps(self, pending, new_tokens, horizon):
        """Scheduler iterations that ``pending`` prompt tokens and
        ``new_tokens`` output tokens take at decode horizon
        ``horizon``: one a prefill chunk, one a horizon."""
        chunk = self.prefill_chunk
        if self.seq_plan is not None and self.seq_parallel_threshold > 0 \
                and pending >= self.seq_parallel_threshold:
            # priced at the widest sp bucket: routed prompts retire
            # axis_size x prefill_chunk tokens per step
            chunk = self.sp_chunk_buckets[-1]
        return -(-pending // chunk) + -(-max(1, new_tokens) // horizon)

    def _step_s_estimate(self):
        """Robust per-step wall-time estimate for admission decisions:
        median over a recent window (compile spikes must not starve
        admissions), None until there are at least two samples."""
        if len(self._step_window) < 2:
            return None
        return float(np.median(self._step_window))

    def _infeasible(self, req, now):
        est = self._step_s_estimate()
        if req.deadline is None or est is None:
            return False
        eta = now + self._estimated_service_steps(req) * est
        return eta > req.deadline

    def _sweep(self, now):
        """Step-boundary honoring of cancellations and deadlines, for
        both queued and running requests.  ``now`` is the phase's single
        timestamp: every decision in one sweep prices time identically."""
        for slot in range(self.num_slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            if req.cancelled:
                self._close_slot_or_defer(slot, CANCELLED, "cancelled")
            elif req.past_deadline(now):
                self._close_slot_or_defer(slot, SHED,
                                          "deadline expired mid-flight")
        if any(r.cancelled or r.past_deadline(now) for r in self.waiting):
            keep = deque()
            for req in self.waiting:
                if req.cancelled:
                    self._drop_waiting(req, CANCELLED, "cancelled")
                elif req.past_deadline(now):
                    self._drop_waiting(req, SHED,
                                       "deadline expired in queue")
                else:
                    keep.append(req)
            self.waiting = keep

    # -------------------------------------------------------------- step
    def step(self):
        """One scheduler iteration; returns True if any work remains.

        One iteration dispatches (and harvests) one fused decode
        *horizon* — up to ``decode_horizon_steps`` tokens per running
        slot — rather than a single token.  Boundary work (sweep, admit,
        prefill) runs on every step whose host state is authoritative,
        i.e. every step that is not a purely chained continuation of an
        in-flight horizon."""
        self.step_idx += 1
        phases = self.phases
        before = dict(phases.seconds)
        with phases("step") as ph_step:
            if not self._inflight and not self._cycle_open and \
                    not self._pf_flight:
                # nothing on the device: the cycle _step_cost times
                # starts with this step (else at the last harvest's
                # end, the end of a step that rode and carried no
                # horizon, or the last pull of a prefill dispatch that
                # was in flight across a step boundary)
                self._cycle_t0 = ph_step.t0
            self._cycle_open = False
            # fault point: slow-step / loop-level fault injection. Fires
            # per HORIZON since the fused-decode change — with
            # decode_horizon_steps > 1 a "step" covers up to that many
            # tokens (docs/resilience.md documents the timing change).
            faults.fire("serve.step", step=self.step_idx)

            t_wait, pulled = 0.0, 0
            chained = False
            # why the horizon in flight is harvested before this step's
            # prefill dispatch is launched (None: it is not, or there is
            # none); counted with the dispatch (_prefill_dispatch)
            self._harvest_first = None
            if self._inflight:
                if self.overlap:
                    # overlap: put the NEXT horizon on the device before
                    # doing this one's host bookkeeping
                    with phases("chain"):
                        chained = self._try_chain()
                if chained:
                    w, n = self._harvest()
                    t_wait += w
                    pulled += n
                else:
                    self._harvest_first = self._why_harvest_first()
            if not chained:
                if self._harvest_first is not None:
                    # the barrier order: the horizon's tokens first
                    w, n = self._harvest_all()
                    t_wait += w
                    pulled += n
                if self._pf_flight and self.draining:
                    # drain has begun: today's order, the pull first
                    with phases("prefill"):
                        self._pull_prefill("drain")
                # 1. cancellations + deadlines leave at the boundary
                with phases("sweep") as ph:
                    now = ph.t0
                    self._sweep(now)
                    # what the dispatch in flight settles whatever its
                    # tokens are: admission sees those slots free
                    self._advance_in_flight()
                # 2. admit waiting requests into free slots (retirement
                # happens at harvest, so slots are already recycled);
                # handoff chains go first — their pages are already held
                with phases("admit"):
                    self._admit_attached(now)
                    self._admit(now)
                    self._plan_ride(now)
                if self._inflight:
                    # the horizon is still in flight: the prefill
                    # dispatch goes out ahead of its harvest, unless the
                    # plan just made needs the horizon's tokens
                    self._harvest_first = self._why_harvest_now()
                    if self._harvest_first is not None:
                        w, n = self._harvest_all()
                        t_wait += w
                        pulled += n
                        with phases("admit"):
                            self._admit(now)
                            self._plan_ride(now)
                bound, free = self._slot_bound, self.slot_req.count(None)
                # 3. one prompt chunk per prefilling slot (chunked
                # prefill), and where _plan_ride said so the next token
                # of every decoding slot as a one-token row beside them
                with phases("prefill"):
                    self._prefill()
                # the horizon that dispatch went out ahead of: its emit
                # loop and retirements run under the dispatch's time
                w, n = self._harvest_all()
                t_wait += w
                pulled += n
                if self._riders and self.waiting and \
                        self.slot_req.count(None) > free:
                    # a rider's last token retired it at the boundary:
                    # its slot is admitted into now, as after a harvest,
                    # so no slot stands empty between steps while
                    # requests wait (the admitted prompt's first chunk
                    # is the next step's either way)
                    with phases("admit"):
                        self._admit(now)
                # 4. dispatch ONE fused decode horizon over running slots
                # (none where the rows rode and the plan was no horizon)
                launched = self._dispatch()
                if self._riders and not launched:
                    # this step's decode pass was its prefill dispatch:
                    # its tokens stay on the device across the step
                    # boundary (or were pulled: the cycle ends here)
                    if not self._pf_flight:
                        self._close_ride_cycle()
                elif self._pf_flight:
                    # the boundary's tokens, behind the horizon that was
                    # launched off the device's copy of them
                    with phases("prefill"):
                        self._pull_prefill()
                if bound:
                    self.metrics.record_slot_bound_step(
                        bool(self._riders) and not launched)
                if not self.overlap and self._inflight:
                    w, n = self._harvest()
                    t_wait += w
                    pulled += n

            # 5. observability
            with phases("observe") as ph:
                dt = ph.t0 - ph_step.t0
                self._step_window.append(dt)
                if pulled:
                    self._tok_window.append(dt / pulled)
                self._ema_step_s = dt if self._ema_step_s is None \
                    else 0.8 * self._ema_step_s + 0.2 * dt
                n_running = sum(r is not None for r in self.slot_req)
                self.metrics.record_step(
                    self.step_idx, queue_depth=len(self.waiting),
                    running=n_running, waiting=len(self.waiting),
                    page_utilization=self.kv.utilization(),
                    device_wait_s=t_wait, host_s=max(0.0, dt - t_wait),
                    cached_pages=None if self.prefix_cache is None
                    else self.prefix_cache.cached_pages)
                if self.mem.enabled:
                    # rolling page-state attribution + per-request
                    # page-seconds + sustained-pressure detection (one
                    # host sweep per step)
                    self.mem.on_step(self)
                if self.tenancy is not None and not chained:
                    # scalar tenancy gauges per barrier step; the
                    # per-tenant split rides health()["tenants"]
                    # (scalar-only sinks)
                    pages = {t: self._tenant_pages(t)
                             for t in self.tenancy.tenants}
                    self.metrics.record_tenants(
                        self.step_idx,
                        active=sum(1 for p in pages.values() if p),
                        page_seconds=sum(u.page_seconds for u in
                                         self.tenancy.usage.values()),
                        max_share=max(pages.values())
                        / self.kv.pool.num_pages)
                if self.audit_every and not chained and \
                        self.step_idx % self.audit_every == 0:
                    # barrier steps only: a chained step's host view is
                    # not authoritative, but page refcounts are — we
                    # still skip it to keep audit cadence aligned with
                    # host-authoritative bookkeeping (and off the
                    # overlap hot path)
                    self.audit()
                if self.compile_watchdog is not None:
                    # auto-steady ticker: after steady_after_steps quiet
                    # steps the watchdog arms and further signature
                    # churn is a detection, not warmup (owner-gated: on
                    # a shared engine only the current owner's steps
                    # advance the counter)
                    self.compile_watchdog.step(owner=self.metrics)
                if self.online is not None and not chained:
                    # online tuner nudges ride BARRIER steps only: knob
                    # changes must land on host-authoritative state,
                    # never while a chained horizon's stale snapshot is
                    # in flight.  Every nudge stays inside the
                    # construction-time bucket sets, so the
                    # compiled-signature story is untouched.
                    self.online.on_step(self)
        if ph_step.last_s > min(self._slow_step_max_s, SLOW_STEP_S):
            self._note_long_step(ph_step.last_s, before)
        return bool(self.waiting) or n_running > 0 or \
            bool(self._inflight) or bool(self._pending_attach) or \
            bool(self._pf_flight)

    def _note_long_step(self, wall_s, before):
        """The longest step so far, and every step over SLOW_STEP_S,
        with the step's own split by phase (the accumulators' growth
        since ``before``): whether the host was blocked on the device
        (``device_wait`` + ``first_token_wait``), in its own loop, or
        in neither (``other_s``: inside ``step()`` and in no phase)."""
        secs = self.phases.seconds
        split = {k: v - before.get(k, 0.0) for k, v in secs.items()
                 if k != "step" and v > before.get(k, 0.0)}
        blocked = sum(split.get(k, 0.0) for k in BLOCKED_PHASES)
        if wall_s > self._slow_step_max_s:
            self._slow_step_max_s = wall_s
            self._slow_step_max_blocked_s = blocked
        if wall_s <= SLOW_STEP_S:
            return
        self._slow_steps += 1
        rec = {"step": self.step_idx, "wall_s": round(wall_s, 4),
               "blocked_s": round(blocked, 4),
               "other_s": round(wall_s - sum(
                   split.get(k, 0.0) for k in STEP_PHASES), 4),
               "phases_s": {k: round(v, 4) for k, v in split.items()}}
        self._slow_step_log.append(rec)
        logger.warning("slow scheduler step %s", json.dumps(rec))
        if self.tracer.enabled:
            self.tracer.instant("slow_step", args=rec)

    # ------------------------------------------------- boundary phases
    def _admit(self, now):
        at_quota = set()   # tenants parked this round: at quota, with
                           # their own live/parked pages still draining
        for slot in range(self.num_slots):
            if self.slot_req[slot] is not None or slot in self._zombies:
                continue
            req = hit = None
            need, protect = 0, frozenset()
            while self.waiting:
                req = self._pick_waiting(at_quota)
                if req is None:
                    break
                # deadline-aware admission: shed what cannot finish in
                # time instead of admitting it and wasting pool pages
                if self._infeasible(req, now):
                    self.waiting.remove(req)
                    self._drop_waiting(
                        req, SHED,
                        f"deadline infeasible at admission "
                        f"(needs ~{self._estimated_service_steps(req)} "
                        f"steps at "
                        f"{self._step_s_estimate() * 1e3:.1f} ms/step)")
                    req = None
                    continue
                hit = None
                if self.prefix_cache is not None:
                    # longest-prefix match, capped at len(prompt)-1 so
                    # at least one prompt token remains to prefill (the
                    # boundary logits the first sampled token comes
                    # from); namespaced per (tenant, adapter) — a
                    # cross-tenant identical prompt can never hit
                    hit = self.prefix_cache.match(
                        req.prompt, limit=len(req.prompt) - 1,
                        ns=self._req_ns(req))
                # admission control: the UNIQUE part of the prompt must
                # fit now — matched full pages are shared, not
                # allocated, and refcount-free cached pages count as
                # reclaimable capacity (drained on demand, with the
                # matched chain protected)
                need = self.kv.pool.pages_for_tokens(len(req.prompt))
                protect = frozenset()
                if hit is not None:
                    need -= len(hit[0])
                    protect = frozenset(
                        id(n) for n in hit[0] +
                        ([hit[1]] if hit[1] is not None else []))
                verdict = self._check_quota(req, need, protect)
                if verdict == "admit":
                    break
                if verdict == "wait":
                    # backlogged at quota: its own retirements will
                    # free pages — park the tenant, try the next one
                    at_quota.add(req.tenant)
                else:
                    self.waiting.remove(req)
                    self._drop_waiting(req, SHED, verdict)
                    self.metrics.record_quota_shed(self.step_idx)
                req = None
            if req is None:
                break
            short = need - self.kv.pool.free_pages
            if short > 0:
                chain = self.mem.chain(
                    "admission", step=self.step_idx, rid=req.trace_rid,
                    pages_needed=need,
                    free_pages=self.kv.pool.free_pages) \
                    if self.mem.enabled else None
                # pre-check with the EXACT drainable count (under the
                # same protect set the drain will honor) before touching
                # the cache: a shortfall the drain provably cannot cover
                # must not destroy the cache every step while the head
                # request stays blocked anyway
                if self.prefix_cache is None or short > \
                        self.prefix_cache.reclaimable_pages(protect):
                    if chain is not None:
                        chain.close("blocked")
                    break
                drained = self._reclaim_cached(short, protect)
                if chain is not None:
                    chain.add("cache_drain", pages=drained)
                    chain.close("admitted" if drained >= short
                                else "blocked")
                if drained < short:
                    break
            self.waiting.remove(req)
            self.slot_req[slot] = req
            req.state = PREFILL
            # one timestamp per phase: admission decisions within a step
            # price time identically (no per-slot clock reads)
            if req.t_admit is None:
                self.metrics.record_queue_wait(now - req.t_submit)
            req.t_admit = now
            if self.tracer.enabled:
                # the queue-wait phase closes at admission: submit->admit
                self.tracer.complete("queued", req.t_submit, now,
                                     cat="lifecycle", rid=req.trace_rid,
                                     args={"slot": slot})
            self._eos_ids[slot] = -1 if req.eos_token_id is None \
                else int(req.eos_token_id)
            self._seed_slot_policy(slot, req)
            if self.tenancy is not None:
                self._adapter_ids[slot] = req.adapter_id
                self.tenancy.note(req.tenant, "admitted")
            self.lengths[slot] = 0
            req.cached_prefix_tokens = 0
            if hit is not None:
                try:
                    self._attach_prefix(slot, req, hit)
                except Exception as e:   # containment: the attach (incl.
                    # the COW device copy) is per-request work — fail
                    # ONE request, never the admission loop
                    self._close_slot(slot, FAILED,
                                     f"{type(e).__name__}: {e}")
            if self.slot_req[slot] is req:
                self._route_seq_parallel(slot, req)
        # slot-bound: admission left requests waiting for want of a slot
        # or of pages (a tenant parked at its quota waits for pages
        # too), so their time to a first token is set by how fast slots
        # turn over.  Recorded HERE, where admission knows it: a submit
        # racing the rest of the step has not been refused anything
        self._slot_bound = bool(self.waiting)

    def _attach_prefix(self, slot, req, hit):
        """Map a matched cached chain into the admitted slot: full pages
        are shared read-only (refcount++), a partially matched page is
        duplicated on-device into a fresh PRIVATE page (copy-on-write —
        decode will append into it, and the cached original must stay
        immutable for its other readers).  Prefill then resumes from the
        cached boundary: ``lengths[slot]`` seeds the position/rotary
        offset, so the jit signature is untouched."""
        full_nodes, pnode, plen = hit
        cached = 0
        if full_nodes:
            self.kv.attach_prefix(slot,
                                  self.prefix_cache.acquire(full_nodes))
            cached = len(full_nodes) * self.kv.page_size
        if pnode is not None and self.kv.pool.can_allocate(1):
            page = self.kv.pool.allocate(1)[0]
            # adopt BEFORE the device copy: if the copy throws, the
            # containment close releases the page with the slot instead
            # of leaking it
            self.kv.adopt_page(slot, page)
            with self.tracer.span("cow_copy", track=slot,
                                  rid=req.trace_rid,
                                  args={"src_page": pnode.page,
                                        "dst_page": page}
                                  if self.tracer.enabled else None):
                self.pools = self.engine.copy_page(self.pools, pnode.page,
                                                   page)
            self.prefix_cache.touch(pnode)
            self.prefix_cache.cow_copies += 1
            cached += plen
        if cached:
            self.prefix_cache.tokens_reused += cached
            self.lengths[slot] = cached
            req.prefill_pos = cached
            req.cached_prefix_tokens = cached
        # one lookup per ADMISSION, counted when the outcome is known —
        # a hit iff tokens were actually reused (match() itself is
        # pure, so a capacity-blocked request re-matched every step
        # cannot inflate the rate, and health()'s hit rate counts the
        # same event as metrics.summary()'s)
        self.prefix_cache.lookups += 1
        if cached:
            self.prefix_cache.hits += 1
            if self.tracer.enabled:
                self.tracer.instant("prefix_hit", track=slot,
                                    rid=req.trace_rid,
                                    args={"cached_tokens": cached,
                                          "prompt_tokens":
                                          len(req.prompt)})
        self.metrics.record_prefix(self.step_idx, cached, len(req.prompt))

    def _route_seq_parallel(self, slot, req):
        """Admission-time routing onto the sequence-parallel prefill
        path.  A routed prompt pre-reserves its FULL page chain up
        front: the wide sharded chunks retire ``axis_size`` pages of KV
        per dispatch, and an allocation stall mid-chunk would waste the
        whole collective.  Reservation is fairness-capped
        (``prefill_reserve_frac``): a prompt whose chain exceeds the
        cap is shed with an explicit reason, because holding most of
        the pool through a long prefill starves every short request
        behind it.  Degrades (no usable axis, reservation
        self-preempted) fall back to the chunked loop with a
        breadcrumb — routing is an optimization, never a correctness
        gate."""
        req.seq_parallel = False
        pending = len(req.prompt) - req.prefill_pos
        if self.seq_parallel_threshold <= 0 \
                or pending < self.seq_parallel_threshold:
            return
        if req.adapter_id >= 0:
            # the sp closure carries no adapter side input: an adapter
            # request degrades to the chunked loop (which does) with a
            # breadcrumb — routing is an optimization, never a
            # correctness gate
            self.metrics.record_seq_prefill_degrade(self.step_idx)
            if self.tracer.enabled:
                self.tracer.instant(
                    "seq_prefill_degrade", track=slot, rid=req.trace_rid,
                    args={"reason": "lora adapter slot"})
            return
        if self.seq_plan is None:
            self.metrics.record_seq_prefill_degrade(self.step_idx)
            if self.tracer.enabled:
                self.tracer.instant(
                    "seq_prefill_degrade", track=slot, rid=req.trace_rid,
                    args={"reason": self._sp_degrade_reason})
            return
        need = self.kv.pages_needed(slot, len(req.prompt))
        if need > self.prefill_reserve_cap:
            self.metrics.record_seq_prefill_shed(self.step_idx, need)
            self._close_slot(
                slot, SHED,
                f"seq-parallel reserve cap: prompt needs {need} pages, "
                f"cap is {self.prefill_reserve_cap} of "
                f"{self.kv.pool.num_pages}")
            return
        try:
            if not self._grow_or_evict(slot, len(req.prompt)):
                # reservation pressure evicted THIS request; it is back
                # in the waiting queue and will re-route on re-admission
                return
        except (PagePoolExhausted, ValueError) as e:
            self._close_slot(slot, SHED, f"page capacity: {e}")
            return
        req.seq_parallel = True
        self.metrics.record_seq_prefill_route(self.step_idx, pending, need)
        if self.tracer.enabled:
            self.tracer.instant(
                "seq_prefill_route", track=slot, rid=req.trace_rid,
                args={"tokens": pending, "reserved_pages": need,
                      "impl": self.seq_plan.impl})

    def _prefill(self):
        """One prompt chunk per prefilling slot, all in ONE ``[rows,
        prefill_chunk]`` dispatch per boundary step (a step reads the
        weights once for prefill, not once per slot).  Host preparation
        (page growth, chunk slicing) is attributable to ONE request, so
        containment wraps it per slot: a per-request failure frees the
        slot and moves on.  The dispatch itself is shared work like the
        decode horizon: an error there is not attributable to one
        request and surfaces loudly.  Requests routed to
        sequence-parallel prefill keep their own one-row dispatch of a
        wide sharded chunk.  Slots finishing their prompt this step
        sample their first token in ONE batched device call over the
        dispatch's whole logits block.

        Where ``_plan_ride`` said so, the decoding slots ride the same
        dispatch (``_riders``): a slot's next token is a row of one
        valid column, ``last_tok[slot]`` at position ``lengths[slot]``,
        and its boundary logits are that token's -- one decode step
        computed by the prefill program, sampled with the finishing
        rows.  A rider moves ``lengths`` and never ``prefill_pos``: its
        token is an emitted token, not a prompt token."""
        self._prefill_rode = False   # until _prefill_dispatch says so
        self._riders = 0
        self._boundary_now = False   # until a row of the dispatch samples
        rows = []        # (slot, req, chunk) riding the shared dispatch
        blocks = []      # (logits [n, vocab], [(row, slot, req)] to sample)
        for slot in range(self.num_slots):
            req = self.slot_req[slot]
            if req is None or req.state != PREFILL:
                continue
            try:
                if self._sp_routed(req):
                    logits = self._prefill_seq_parallel(slot, req)
                    if logits is not None:
                        blocks.append((logits, [(0, slot, req)]))
                    continue
                chunk = req.prompt[req.prefill_pos:
                                   req.prefill_pos + self.prefill_chunk]
                if self._grow_or_evict(slot, req.prefill_pos + len(chunk)):
                    rows.append((slot, req, chunk))
                # else self-preempted: back in the queue
            except PagePoolExhausted as e:
                self._close_slot_or_defer(slot, SHED, f"page capacity: {e}")
            except Exception as e:   # containment: fail one, not all
                self._close_slot_or_defer(slot, FAILED,
                                          f"{type(e).__name__}: {e}")
        if rows and self._ride is not None:
            rows += self._ride_rows()
        # a later row's growth may have evicted an earlier row's slot:
        # its pages are gone (maybe already another row's), so it must
        # not ride the dispatch
        rows = [(s, r, c) for s, r, c in rows
                if self.slot_req[s] is r and r.state in (PREFILL, RUNNING)]
        if all(r.state == RUNNING for _, r, _ in rows):
            rows = []    # no prompt row is left: no dispatch to ride
        rec = None
        if rows:
            logits = self._prefill_dispatch(rows)
            done = []
            for i, (slot, req, chunk) in enumerate(rows):
                self.lengths[slot] += len(chunk)
                if req.state == RUNNING:
                    done.append((i, slot, req))
                    continue
                req.prefill_pos += len(chunk)
                if req.prefill_pos == len(req.prompt):
                    done.append((i, slot, req))
            rec = self._boundary(logits, done)
            self._boundary_now = rec is not None
        stays = False
        if rec is not None:
            why = self._why_pull_now(rec)
            stays = why is None
            if stays:
                self._launch_boundary(rec)
            else:
                self.metrics.record_lookahead_fallback(why)
        # the tokens of the dispatch the last step left in flight: this
        # step's is on the device behind it (none was launched: "other")
        self._pull_prefill(None if rows else "other", newer=rec)
        for logits, done in blocks:
            self._pull(self._boundary(logits, done))
        if not stays:
            self._pull(rec)

    def _sp_routed(self, req):
        """Whether ``req``'s prompt takes the sequence-parallel path's
        own dispatches and not a row of the shared one."""
        return getattr(req, "seq_parallel", False) and \
            self.seq_plan is not None

    def _may_ride(self, req):
        """Whether a RUNNING request's next token can be a row of the
        prefill dispatch: a plain decode step and nothing else -- no
        grammar mask to restage, no hand-off owed, not a prompt the
        sequence-parallel path prefilled."""
        return req.grammar is None and not req.handoff and \
            not getattr(req, "seq_parallel", False) and \
            req.remaining_new >= 1

    def _ride_rows(self):
        """The decoding slots as rows of this step's prefill dispatch,
        ``(slot, req, [last_tok[slot]])``, each after its page for that
        one token is there, under the per-slot containment of a prompt
        row: a rider whose growth fails is shed alone."""
        rows = []
        for slot in range(self.num_slots):
            req = self.slot_req[slot]
            if req is None or req.state != RUNNING or \
                    not self._may_ride(req):
                continue
            try:
                if self._grow_or_evict(slot, int(self.lengths[slot]) + 1):
                    rows.append((slot, req, [int(self.last_tok[slot])]))
            except PagePoolExhausted as e:
                self._close_slot_or_defer(slot, SHED, f"page capacity: {e}")
            except Exception as e:   # containment: fail one, not all
                self._close_slot_or_defer(slot, FAILED,
                                          f"{type(e).__name__}: {e}")
        return rows

    def _prefill_dispatch(self, rows):
        """Pack ``rows`` into the smallest row bucket and launch the
        shared prefill dispatch; returns its [bucket, vocab] boundary
        logits (row i belongs to ``rows[i]``).  Padding rows carry
        ``n_valid == 0`` and a live slot id: they write nothing.  The
        counters' ``rows`` and ``tokens`` are PROMPT rows and prompt
        tokens; riders are counted beside them (``riders``), and in the
        keys and pairs the paged layers' attention really handles."""
        padded = _bucket_ceil(self.prefill_row_buckets, len(rows))
        ids = np.zeros((padded, self.prefill_chunk), np.int32)
        slots = np.full(padded, rows[0][0], np.int32)
        n_valid = np.zeros(padded, np.int32)
        # a rider's input id is its slot's newest token: last_tok, or,
        # while the dispatch that sampled it is in flight (req.owed),
        # the device's copy of it (_dev_tok, by slot)
        src = np.full(padded, -1, np.int32)
        for i, (slot, req, chunk) in enumerate(rows):
            ids[i, :len(chunk)] = chunk
            slots[i] = slot
            n_valid[i] = len(chunk)
            if req.state == RUNNING:
                ids[i, 0] = self.last_tok[slot]
                if req.owed:
                    src[i] = slot
        riders = sum(req.state == RUNNING for _, req, _ in rows)
        tokens = int(n_valid.sum()) - riders
        self._prefill_rode = True
        self._riders = riders
        # launched with the dispatch before it not yet back on the host:
        # a boundary's tokens, or a whole horizon's
        lookahead = bool(self._pf_flight or self._inflight)
        if self._harvest_first is not None:
            self.metrics.record_lookahead_fallback(self._harvest_first)
        with self.phases("prefill_chunk", rows=len(rows) - riders,
                         padded_rows=padded, tokens=tokens,
                         riders=riders, lookahead=int(lookahead)):
            if (src >= 0).any():
                ids = self._ids_from_device(ids, src)
            a_ids, a_pack = self._adapter_args()
            logits, self.pools = self.engine.prefill_into_slots(
                ids, slots, n_valid, self.kv.table, self.lengths,
                self.pools, adapter_ids=a_ids, adapters=a_pack)
        # a chunk of n columns from position s reads s + n keys of a
        # paged layer and scores n * s + n * (n + 1) / 2 pairs, on the
        # pages up to position s + n - 1 (a padding row: one page), in
        # the grid steps the kernel's own module counts
        starts = [int(self.lengths[slot]) for slot, _, _ in rows]
        pad = [0] * (padded - len(rows))
        self.metrics.record_prefill_dispatch(
            self.step_idx, rows=len(rows) - riders, padded_rows=padded,
            tokens=tokens, riders=riders, lookahead=lookahead,
            kv_tokens=sum(starts) + tokens + riders,
            kv_pairs=sum(s * len(c) + len(c) * (len(c) + 1) // 2
                         for s, (_, _, c) in zip(starts, rows)),
            **self._window_prefill_counts(starts, rows),
            **(self._count_key_blocks(
                starts + pad, [len(c) for _, _, c in rows] + pad,
                max_pages=self.kv.table.shape[1])
               if self._count_key_blocks else {}))
        if self.slot_state:
            # a row whose first position is 0 started from zeros
            # whatever its slot held (ops/ssm/state.py)
            fresh = starts.count(0)
            if fresh:
                self.metrics.record_state_resets(self.step_idx, fresh)
        return logits

    def _window_prefill_counts(self, starts, rows):
        """What a window layer needs of a prefill dispatch: a row from
        position ``s`` with ``n`` columns reads the keys its first
        query sees and its own (positions ``s - window + 1 .. s + n -
        1``, from 0 at the earliest) and scores, for column j, the last
        ``window`` of ``s + j + 1`` keys."""
        if not self._window:
            return {}
        w = self._window
        lens = [len(c) for _, _, c in rows]
        return dict(
            window_tokens=sum(min(s + n, w + n - 1)
                              for s, n in zip(starts, lens)),
            window_pairs=sum(min(s + j + 1, w) for s, n in zip(starts, lens)
                             for j in range(n)))

    def _ids_from_device(self, ids, src):
        """``ids`` with column 0 of row r read from the device's token
        of slot ``src[r]`` (``src[r] >= 0``): the sampled tokens of the
        dispatches in flight are filed by slot first (``_dev_tok``, the
        device's ``last_tok``), then the rows gather theirs -- two
        small programs a row bucket, inside this dispatch's phase."""
        self._file_sampled()
        return self.engine.prefill_ids(ids, src, self._dev_tok)

    def _last_tok_on_device(self, ahead):
        """``last_tok`` for a horizon's launch with the slots ``ahead``
        reading the device's copy (``_dev_tok``): their newest token is
        a boundary sample that has not been pulled."""
        self._file_sampled()
        owed = np.zeros(self.num_slots, bool)
        owed[ahead] = True
        return self.engine.decode_tokens(self.last_tok, owed, self._dev_tok)

    def _file_sampled(self):
        """File the sampled tokens of the dispatches in flight by slot
        (``_dev_tok``), once a dispatch."""
        if self._dev_tok is None:
            self._dev_tok = self.engine.slot_tokens(self.num_slots)
        for rec in self._pf_flight:
            if rec["slots"] is not None:
                self._dev_tok = self.engine.keep_sampled(
                    self._dev_tok, rec["toks"], rec["slots"])
                rec["slots"] = None

    def _prefill_seq_parallel(self, slot, req):
        """One wide sequence-sharded chunk of ONE routed request (its
        own dispatch); returns its [1, vocab] boundary logits when the
        prompt finished, else None."""
        width = _bucket_ceil(self.sp_chunk_buckets,
                             len(req.prompt) - req.prefill_pos)
        chunk = req.prompt[req.prefill_pos:req.prefill_pos + width]
        n_valid = len(chunk)
        if not self._grow_or_evict(slot, req.prefill_pos + n_valid):
            return None       # self-preempted: back in the queue
        ids = np.zeros((1, width), np.int32)
        ids[0, :n_valid] = chunk
        with self.phases("prefill_chunk", track=slot, rid=req.trace_rid,
                         tokens=n_valid, pos=req.prefill_pos,
                         seq_parallel=1):
            logits, self.pools = self.engine.prefill_sequence_parallel(
                ids, slot, n_valid, self.kv.table, self.lengths,
                self.pools)
        self.metrics.record_seq_prefill_chunk(self.step_idx, n_valid)
        self.lengths[slot] += n_valid
        req.prefill_pos += n_valid
        return logits if req.prefill_pos == len(req.prompt) else None

    def _boundary(self, logits, finishing):
        """The record of one prefill dispatch's boundary: the first
        tokens of the requests whose prompt finished in it and the next
        tokens of the slots that rode it (``_ride_rows``; RUNNING,
        where a finishing row is PREFILL).  ``logits`` is the
        dispatch's whole [n, vocab] block and ``finishing`` lists
        ``(row, slot, req)``; None where no row samples.  The sample
        runs over EVERY row of the block (one program per row bucket,
        never per finishing count or row index); non-finishing and
        padding rows' tokens are dropped on the host.  Nothing runs
        here: ``_pull`` samples and emits at once (a barrier step, the
        order every step had), or ``_launch_boundary`` puts the sample
        on the device now and ``_pull`` takes its tokens one dispatch
        later."""
        # a later slot's growth (a sequence-parallel reservation) may
        # have evicted an earlier finishing slot — drop stale entries
        # BEFORE the batched sample (the policy-table gathers index by
        # slot, so a vacated slot must not reach them)
        rows = [(i, s, r, r.state == RUNNING) for i, s, r in finishing
                if self.slot_req[s] is r and r.state in (PREFILL, RUNNING)]
        if not rows:
            return None
        return {"logits": logits, "rows": rows, "toks": None, "slots": None,
                "left": set(), "release_after": set(), "donate": {},
                # the step's decode pass was this dispatch and nothing
                # else: the cycle _step_cost times ends at its pull
                "ride_only": self._rode_only(),
                "policy": self._batch_needs_policy([s for _, s, _, _ in
                                                    rows])}

    def _why_pull_now(self, rec):
        """Why the shared dispatch's boundary ``rec`` has to be pulled
        before anything else is launched (None: it need not).  Read off
        the step's own state: its tokens may stay on the device where
        what follows reads them there -- the decode horizon of this
        step, launched off the device's copy (``_last_tok_on_device``)
        and ahead of the pull that ends the step, or, where the rows
        decoding rode the dispatch and no horizon follows, the next
        step's dispatch -- and nothing reads them on the host.
        ``drain``: shutdown has begun; ``other``: the scheduler was
        built with ``overlap=False``; ``spec``: a speculative round
        follows, whose drafter reads ``out_tokens``; ``policy``: a
        request samples under a decoding policy or a grammar (penalty
        counts and masks are functions of the emitted tokens), owes a
        hand-off, or was routed sequence-parallel."""
        if self.draining:
            return "drain"
        if not self.overlap:
            return "other"
        if self._spec is not None:
            return "spec"
        if rec["policy"] or not self._all_plain():
            return "policy"
        return None

    def _all_plain(self):
        """Whether every request in a slot takes the plain programs and
        the plain order of a boundary: the scheduler's greedy default,
        no decoding policy, grammar or hand-off, not routed
        sequence-parallel."""
        return self._default_greedy and not any(
            self._req_needs_policy(r) or r.handoff or
            getattr(r, "seq_parallel", False)
            for r in self.slot_req if r is not None)

    def _why_harvest_first(self):
        """Why the horizon in flight at a step's start is harvested
        before the step sweeps, admits and launches its prefill dispatch
        (None: the dispatch goes out first, ``_why_harvest_now`` and
        page pressure permitting).  The chunks of the slots in PREFILL
        depend on no token of the horizon, so the host's boundary work
        can run under the horizon's time and the device finds the
        dispatch queued when the horizon ends.  What that order costs is
        an admission: a slot the harvest frees is admitted into one step
        later.  ``not_slot_bound``: the last admission left nobody
        waiting, so an arrival during the horizon could have its first
        chunk in this step's dispatch and must not wait a step for it
        (slot-bound, whoever waits had no slot before the harvest
        either); ``drain``, ``spec``: as ``_why_pull_now``; ``policy``:
        a request in a slot is not plain (``_all_plain``), tenancy
        quotas, hand-off chains waiting to attach, or a
        sequence-parallel route (its reservation evicts)."""
        if self.draining:
            return "drain"
        if self._spec is not None:
            return "spec"
        if not self._slot_bound:
            return "not_slot_bound"
        if self.tenancy is not None or self._pending_attach or \
                (self.seq_plan is not None and
                 self.seq_parallel_threshold > 0) or \
                not self._all_plain():
            return "policy"
        return None

    def _why_harvest_now(self):
        """The second half of ``_why_harvest_first``, once the step has
        admitted and planned with the horizon still in flight:
        ``no_prefill``: no slot is in PREFILL, there is nothing to
        launch ahead, and the slots the harvest frees are admitted into
        in this step; ``ride``: the plan is for the decoding slots to
        ride the dispatch, and their input ids are the horizon's
        tokens; ``policy``: a request just admitted is not plain."""
        if not any(r is not None and r.state == PREFILL
                   for r in self.slot_req):
            return "no_prefill"
        if self._ride is not None:
            return "ride"
        if not self._all_plain():
            return "policy"
        return None

    def _harvest_all(self):
        """Harvest every horizon in flight, oldest first; returns the
        sums of ``_harvest``'s ``(device_wait_s, tokens_delivered)``."""
        wait, pulled = 0.0, 0
        while self._inflight:
            w, n = self._harvest()
            wait += w
            pulled += n
        return wait, pulled

    def _launch_boundary(self, rec):
        """Put ``rec``'s sample on the device behind its dispatch and
        leave the tokens there: every row owes its request one token,
        and what is launched next reads its input ids from the device
        (``_ids_from_device``, ``_last_tok_on_device``), without the
        host."""
        toks = self._sample(rec)
        # row -> slot for the device's per-slot tokens (num_slots: a
        # row that samples for nobody), filed by the next dispatch
        rec["slots"] = np.full(int(np.shape(toks)[0]), self.num_slots,
                               np.int32)
        for i, slot, req, _ in rec["rows"]:
            rec["slots"][i] = slot
            req.owed += 1

    def _sample(self, rec):
        """Launch ``rec``'s batched sample (its tokens stay on the
        device until they are pulled) and return the device array."""
        toks = rec["toks"] = self.engine.sample_launch(
            rec["logits"], **self.sampling)
        # the programs that keep a sample on the device are built where
        # a bucket's sample first runs
        self.engine.warm_token_feedback(toks, self.prefill_chunk,
                                        self.num_slots)
        return toks

    def _advance_in_flight(self):
        """What the prefill dispatch in flight settles whatever its
        tokens turn out to be, applied before the next step's admission
        and plan: a request whose owed token is its last leaves its
        slot (the pages go on now: the device runs dispatches in order,
        and a page's next owner writes it after this dispatch), and a
        request whose prompt the dispatch finished decodes from here.
        An end of sequence, a cancel, a deadline or a failing callback
        is found at the pull, one dispatch later: that row of the next
        dispatch is computed and dropped (``_pull``)."""
        for rec in self._pf_flight:
            for i, slot, req, _ in rec["rows"]:
                if self.slot_req[slot] is not req:
                    continue           # closed by the sweep: parked
                if req.remaining_new <= 0:
                    self._vacate(slot)
                    rec["left"].add(i)
                elif req.state == PREFILL:
                    req.state = RUNNING
        # and the horizon a prefill dispatch is about to be launched
        # ahead of: a request whose budget it exhausts ends in it
        # whatever it samples, so its slot is admitted into now and not
        # a step late (its length then is kept for the harvest's counts)
        for rec in self._inflight:
            for slot in rec["slots"]:
                req = rec["reqs"][slot]
                if self.slot_req[slot] is req and \
                        req.remaining_new <= rec["max_advance"][slot]:
                    rec["left"][slot] = int(self.lengths[slot])
                    self._vacate(slot)

    def _pull_prefill(self, why=None, newer=None):
        """Pull every prefill dispatch in flight, oldest first (``why``:
        the reason the next dispatch could not be launched before it,
        counted; None where it was).  ``newer`` is the boundary of the
        dispatch just launched behind them: where it stays in flight it
        is in flight from here on, so a slot whose request ends at one
        of these pulls is parked on it."""
        pulled = list(self._pf_flight)
        self._pf_flight.clear()
        if newer is not None and newer["toks"] is not None:
            self._pf_flight.append(newer)
        for rec in pulled:
            if why is not None:
                self.metrics.record_lookahead_fallback(why)
            self._pull(rec)

    def _pull(self, rec):
        """The host half of a boundary: the blocking pull of ``rec``'s
        sampled tokens (``first_token_wait``; the sample itself where
        ``_launch_boundary`` has not run it) and the emit loop
        (``first_token``), in row order.  A record that was in flight
        across a step boundary may hold rows the host has since
        overtaken: a request the sweep closed, or one that ended on the
        token before (its slot was parked, its row computed on pages
        the slot still held): their tokens are dropped
        (``prefill_overrun_rows``), and the pages parked on this record
        go on afterwards.  A cancel or a deadline such a record's pull
        finds is honored as at a harvest: the token past it dropped."""
        if rec is None:
            return
        rows, flown = rec["rows"], rec["toks"] is not None
        # the batched sample is shared work (like the decode dispatch);
        # emit/callback stays contained per request below.  The host
        # waits here until the prefill dispatch is done (one that was in
        # flight has the next one queued behind it)
        with self.phases("first_token_wait", rows=len(rows)) as ph:
            if rec["policy"]:
                toks = self._sample_under_policy(rec)
            else:
                if not flown:
                    self._sample(rec)
                toks = np.asarray(rec["toks"])
        t_done = ph.t0 + ph.last_s
        overrun = 0
        with self.phases("first_token") as ph:
            now = ph.t0
            for i, slot, req, rider in rows:
                req.owed -= flown
                # left its slot at the last plan (_advance_in_flight):
                # this is its last token, and there is no slot to close
                left = i in rec["left"]
                if not left and (self.slot_req[slot] is not req or
                                 req.state not in (PREFILL, RUNNING)):
                    overrun += flown   # closed since the launch
                    continue
                tok, closed = int(toks[i]), None
                if flown and req.cancelled:
                    closed = (CANCELLED, "cancelled")
                elif flown and req.past_deadline(now):
                    closed = (SHED, "deadline expired mid-flight")
                else:
                    try:
                        if rider:
                            # a rider's token is a burst of one
                            self.metrics.record_tbt(
                                self.step_idx,
                                time.monotonic() - req.t_last)
                        self._emit(req, tok)
                        if not left:
                            self._note_emitted(slot, req, tok)
                    except Exception as e:
                        closed = (FAILED, f"{type(e).__name__}: {e}")
                if closed is not None:
                    overrun += closed[0] != FAILED   # its token dropped
                    if left:
                        self._record_closed(req, *closed)
                    else:
                        self._close_slot_or_defer(slot, *closed)
                    continue
                if left:
                    self._finish(req)
                elif req._finished_by(tok) or self._grammar_finished(req):
                    self._retire(slot)
                elif req.handoff and self.on_handoff is not None:
                    self._do_handoff(slot, req, tok)
                else:
                    self.last_tok[slot] = tok
                    req.state = RUNNING
                    if req.grammar is not None:
                        self._grammar_masks[slot] = \
                            req.grammar.token_mask()
            self._release_parked(rec)
        if flown:
            self.metrics.record_lookahead_pull(overrun)
            if rec["ride_only"]:
                self._close_ride_cycle(at=t_done)

    def _sample_under_policy(self, rec):
        """``rec``'s boundary tokens under the decoding policy: same
        pipeline, same position-keyed stream as the fused decode (token
        0 of the request draws from fold_in(key, sample_offset)).
        Per-row lanes cover the whole block; a non-finishing row
        borrows the first finishing slot's lanes (its draw is
        independent of the other rows' and dropped by the caller)."""
        self._ensure_policy_tables()
        n = int(np.shape(rec["logits"])[0])
        sl = np.full(n, rec["rows"][0][1], np.int64)
        idx = np.zeros(n, np.int32)
        for i, s, r, _ in rec["rows"]:
            sl[i] = s
            idx[i] = r.sample_offset + len(r.out_tokens)
        return self.engine.sample_from_logits_policy(
            rec["logits"], self._samp_keys[sl], idx, self._samp_temps[sl],
            self._samp_topk[sl], self._samp_topp[sl], self._samp_rep[sl],
            self._samp_pres[sl], self._samp_freq[sl],
            self._tok_counts[sl], self._grammar_masks[sl])

    # ------------------------------------------------ disaggregated KV
    def _do_handoff(self, slot, req, tok):
        """Prefill-worker epilogue: the prompt's KV is complete and the
        boundary token is emitted — detach the slot's page chain (pool
        references travel with it) and hand (pages, prefilled length,
        boundary token) to ``on_handoff`` for a decode worker to adopt.
        The callback is cluster code and therefore contained: if it
        raises, the pages go back to the pool and THIS request fails —
        never the prefill loop."""
        pages = self.kv.take_slot_pages(slot)
        plen = int(self.lengths[slot])
        self.slot_req[slot] = None
        self.lengths[slot] = 0
        self._release_adapter(slot)
        try:
            self.on_handoff(req, pages, plen, tok)
        except Exception as e:
            self.kv.pool.free(pages)
            self._finalize(req, FAILED, f"handoff: {type(e).__name__}: {e}")
            self.metrics.record_terminal(self.step_idx, FAILED, req.rid,
                                         req.error)
            return
        self._finalize(req, HANDOFF)
        self.metrics.record_handoff(self.step_idx, plen)
        if self.tracer.enabled:
            self.tracer.instant("handoff_out", cat="handoff", track=slot,
                                rid=req.trace_rid,
                                args={"tokens": plen,
                                      "pages": len(pages)})

    def attach_handoff(self, prompt, pages, length, first_tok, *,
                       max_new_tokens, eos_token_id=None, on_token=None,
                       deadline_s=None, trace_ctx=None, sampling=None,
                       seed=None, grammar=None, sample_offset=0,
                       tenant=None, adapter=None):
        """Decode-worker intake for a prefill worker's donated chain:
        the request joins with its prompt KV already written (``pages``
        cover ``length`` prefilled positions in the SHARED pool) and its
        first token already emitted by the prefill worker.  It slots in
        as a RUNNING decoder — no prefill dispatch ever runs here — at
        the next admission boundary.  Until a slot frees up the chain
        waits in ``_pending_attach`` still holding its pages (bounded:
        the cluster router only hands off what the decode side's queue
        can absorb)."""
        self._refuse("handoff")
        if self.draining:
            raise QueueFull("scheduler is draining; handoff refused")
        t_cfg, adapter_id = self._resolve_tenant(tenant, adapter)
        req = Request(prompt, max_new_tokens, eos_token_id, on_token,
                      deadline_s=deadline_s)
        if t_cfg is not None:
            # failover/disaggregation preserves attribution: the decode
            # side keeps billing the SAME tenant the prefill side did
            req.tenant = t_cfg.name
            req.adapter = adapter
            req.adapter_id = adapter_id
        if trace_ctx is not None and trace_ctx.get("trace_id") is not None:
            req.trace_rid = trace_ctx["trace_id"]
        now = time.monotonic()
        # the boundary token was emitted (and TTFT recorded) by the
        # prefill worker; seeding t_first keeps _emit on the inter-token
        # branch so this scheduler never double-counts a first token
        req.out_tokens = [int(first_tok)]
        req.t_first = req.t_last = now
        req.prefill_pos = len(req.prompt)
        # policy continuity across the handoff: the prefill worker drew
        # the boundary token at position sample_offset + 0; out_tokens
        # already holds it, so this side's next draw lands at +1 with
        # the SAME offset, and _apply_policy replays the grammar cursor
        # through it
        self._apply_policy(req, sampling, seed, grammar, sample_offset)
        self._check_adapter_policy(req)
        req._attach = (list(pages), int(length), int(first_tok))
        if req.remaining_new <= 0:
            self.kv.pool.free(req._attach[0])
            req.state = FINISHED
            self.completed.append(req)
            self.metrics.record_completion(self.step_idx)
            return req
        self.requests[req.rid] = req
        self._pending_attach.append(req)
        return req

    def _admit_attached(self, now):
        """Seed pending handoff chains into free slots ahead of the
        waiting queue (their pages are already allocated — parking them
        longer than necessary only starves the pool)."""
        for slot in range(self.num_slots):
            if not self._pending_attach:
                return
            if self.slot_req[slot] is not None or slot in self._zombies:
                continue
            req = self._pending_attach.popleft()
            pages, length, tok = req._attach
            if req.cancelled or req.past_deadline(now):
                self.kv.pool.free(pages)
                state = CANCELLED if req.cancelled else SHED
                reason = "cancelled" if req.cancelled \
                    else "deadline expired before attach"
                self._finalize(req, state, reason)
                self.metrics.record_terminal(self.step_idx, state,
                                             req.rid, reason)
                continue
            try:
                self.kv.adopt_chain(slot, pages)
            except Exception as e:   # containment: a chain this slot
                # table cannot hold fails ONE request, not the loop
                self.kv.pool.free(pages)
                self._finalize(req, FAILED, f"{type(e).__name__}: {e}")
                self.metrics.record_terminal(self.step_idx, FAILED,
                                             req.rid, req.error)
                continue
            self.slot_req[slot] = req
            self.lengths[slot] = length
            self.last_tok[slot] = tok
            self._eos_ids[slot] = -1 if req.eos_token_id is None \
                else int(req.eos_token_id)
            self._seed_slot_policy(slot, req)
            if self.tenancy is not None:
                self._adapter_ids[slot] = req.adapter_id
                self.tenancy.note(req.tenant, "admitted")
            self.metrics.record_queue_wait(now - req.t_submit)
            req.t_admit = now
            req.state = RUNNING
            if self.tracer.enabled:
                self.tracer.instant("handoff_in", cat="handoff",
                                    track=slot, rid=req.trace_rid,
                                    args={"prefilled": length})

    # ----------------------------------------------------------- drain
    def begin_drain(self, shed_waiting=False):
        """Enter drain mode: ``submit``/``attach_handoff`` refuse new
        work (QueueFull — the router's signal to route elsewhere) while
        everything already accepted keeps being served.  With
        ``shed_waiting`` the not-yet-admitted queue is shed NOW with a
        distinct reason instead of silently vanishing at process exit —
        the ds_serve SIGTERM contract."""
        self.draining = True
        if shed_waiting:
            while self.waiting:
                self._drop_waiting(self.waiting.popleft(), SHED,
                                   "shutdown drain: still queued")
            while self._pending_attach:
                req = self._pending_attach.popleft()
                self.kv.pool.free(req._attach[0])
                self._finalize(req, SHED, "shutdown drain: still queued")
                self.metrics.record_terminal(self.step_idx, SHED, req.rid,
                                             req.error)

    def drain(self, grace_s=None, shed_waiting=True):
        """Drain for shutdown/restart: stop admitting new work, finish
        what is in flight within ``grace_s`` (None = no deadline), then
        shed — distinctly, with reasons — whatever the grace budget
        could not cover.  Returns ``{"finished": n, "shed": n}`` for the
        requests that were live when the drain began."""
        before = self.metrics.completed
        shed_before = self.metrics.shed
        t_drain = time.monotonic()
        self.begin_drain(shed_waiting=shed_waiting)
        deadline = None if grace_s is None \
            else time.monotonic() + float(grace_s)
        while deadline is None or time.monotonic() < deadline:
            if not self.step():
                break
        # grace exhausted with work still live: harvest every in-flight
        # horizon first (the device may still be writing those pages),
        # then shed the survivors instead of losing them silently
        while self._inflight:
            self._harvest()
        self._pull_prefill("drain")
        for slot in range(self.num_slots):
            if self.slot_req[slot] is not None:
                self._close_slot(slot, SHED, "shutdown drain: grace "
                                 "budget exhausted mid-flight")
        while self.waiting:
            self._drop_waiting(self.waiting.popleft(), SHED,
                               "shutdown drain: grace budget exhausted")
        while self._pending_attach:
            req = self._pending_attach.popleft()
            self.kv.pool.free(req._attach[0])
            self._finalize(req, SHED, "shutdown drain: grace budget "
                           "exhausted")
            self.metrics.record_terminal(self.step_idx, SHED, req.rid,
                                         req.error)
        counts = {"finished": self.metrics.completed - before,
                  "shed": self.metrics.shed - shed_before}
        if self.tracer.enabled:
            self.tracer.complete("drain", t_drain, time.monotonic(),
                                 cat="lifecycle", args=dict(counts))
        return counts

    # -------------------------------------------------- horizon decode
    def _bucket_floor(self, h):
        out = 1
        for b in self.horizon_buckets:
            if b <= h:
                out = b
        return out

    def _horizon_cap(self, running, now):
        """The largest useful horizon over ``running``, quantized to
        the bucket set: capped by ``decode_horizon_steps``, by the
        largest remaining token budget among running slots (scan steps
        past every budget are pure waste) and by the tightest live
        deadline (a horizon overshooting a deadline generates tokens
        the sweep will throw away).  A grammar-constrained slot pins
        the batch to horizon 1: its allowed-token mask is a
        host-compiled function of the tokens emitted so far, so the
        device may take at most one constrained step per staged mask."""
        if any(self.slot_req[s].grammar is not None for s in running):
            return 1
        h = min(self.decode_horizon_steps,
                max(self.slot_req[s].remaining_new for s in running))
        deadlines = [self.slot_req[s].deadline for s in running
                     if self.slot_req[s].deadline is not None]
        if deadlines and self._tok_window:
            per_tok = float(np.median(self._tok_window))
            if per_tok > 0:
                slack = min(deadlines) - now
                h = max(1, min(h, int(slack / per_tok)))
        return self._bucket_floor(h)

    def _pick_horizon(self, running, now):
        """The decode horizon of this step's dispatch, under one of two
        regimes the scheduler reads off its own state.

        **Nothing waits for a slot** (``_admit`` admitted everything):
        the rows decoding are the ones a user watches, and the horizon
        is the largest useful one (``_horizon_cap``).

        **Slot-bound** (``_admit`` left requests waiting for a slot or
        for pages): every waiting request's time to a first token is
        set by how fast slots turn over, so the pick above is only a
        cap, and the horizon is the bucket under it that finishes the
        requests now in slots in the least time (``_turnover_horizon``):
        a step carries one prefill dispatch for every prefilling row
        and ``h`` weight passes for the few rows decoding, and with
        long prompts the passes are most of the step.  Where the rows
        decoding rode this step's prefill dispatch (``_riders``) the
        horizon is the one ``_plan_ride`` chose to follow the ride
        with: 0, no horizon at all, where every running slot has had
        its token of this step at the boundary.

        The same tokens come out in the same order either way (a
        horizon is a scan of single steps); ``_reserve`` may still
        shrink the horizon under page pressure afterwards."""
        cap = self._horizon_cap(running, now)
        if self._riders:
            # P and D stay as _plan_ride's pick read them
            self._turnover = (self._ride < cap,) + self._turnover[1:]
            return self._ride
        self._turnover = _NO_TURNOVER
        return self._turnover_horizon(cap)[1] if self._slot_bound else cap

    def _turnover_horizon(self, cap, ride=()):
        """The form ``(rode, h)`` of a slot-bound step that turns slots
        over fastest: ``h`` a horizon bucket no larger than ``cap``
        beside the prefill dispatch, or, where rows can ride it, one of
        the horizons ``ride`` after the ride (0: none; in the order they
        are sampled in).  The requests now in slots are the sample of
        the traffic the scheduler has: a slot serves one of them in
        ``steps_i`` steps of ``T`` seconds, so the form with the least
        ``T x sum_i steps_i`` serves such requests at the highest
        rate.  ``steps_i`` is request i's whole
        life, its prompt's chunks plus ``ceil(max_new_i / (h + rode))``
        (``_service_steps``, the arithmetic admission prices a request
        with; a ride is one token of every step).  ``T(h) = P + h * D``
        is ``_step_cost``'s measured wall of a step that carries ``h``
        decode passes; a ride form's ``T`` is the median of its own
        cycles, because what a rider costs is the prefill kernel's
        (a 128-row query tile against every live page of its slot, and
        a first-token pull in every step) and no multiple of ``D``.
        Long prompts and short outputs pull the horizon down (``sqrt(o
        * P / (c * D))`` for ``c`` chunks and ``o`` tokens a request)
        and make the second weight pass of a step worth dropping; chat
        lengths leave it at 4 to 8; where riders hold many pages and a
        decode pass is cheap the ride loses, and its walls say so.  A
        tie goes to the larger bucket, and a ride form has to undercut
        the best plain one by ``_StepCost.MARGIN`` (forms within the
        walls' noise of each other would trade places by the run, and
        the plain ones are the older program).  While there is no
        estimate the step rides the largest bucket that still wants
        samples (``cap`` itself on a scheduler that has just started,
        then the next one down: six horizons give two buckets their
        medians, and no shorter horizon is tried than that takes); with
        every bucket sampled and no estimate, ``cap`` stands.  With an
        estimate, a ride form that wants samples is sampled the same
        way, the smallest bucket after the ride first: two forms, so
        learning that riding loses costs six steps."""
        buckets = [b for b in reversed(self.horizon_buckets) if b <= cap]
        est = self._step_cost.estimate()
        if est is None:
            best, p, d = self._step_cost.wanting(buckets) or cap, 0.0, 0.0
        else:
            p, d = est
            live = [(len(r.orig_prompt) - r.cached_prefix_tokens,
                     r.max_new_tokens)
                    for r in self.slot_req if r is not None]

            def life(per_step):
                return sum(self._service_steps(n, o, per_step)
                           for n, o in live)
            forms = [(RIDE, h) for h in ride]
            best = self._step_cost.wanting(forms)
            if best is None:
                cost = {b: (p + b * d) * life(b) for b in buckets}
                best = min(cost, key=cost.get)
                rides = {f: self._step_cost.median(f) * life(1 + f[1])
                         for f in forms}
                cheapest = min(rides, key=rides.get, default=None)
                if cheapest is not None and rides[cheapest] < \
                        (1 - self._step_cost.MARGIN) * cost[best]:
                    best = cheapest
        if isinstance(best, tuple):
            self._turnover = (best[1] < cap, p, d)
            return True, best[1]
        self._turnover = (best < cap, p, d)
        return False, best

    def _plan_ride(self, now):
        """Once a step, after ``_admit`` has said whether anyone waits
        and before ``_prefill``: whether the slots decoding take their
        next token as one-token rows of this step's prefill dispatch
        (``_ride`` = the horizon that then follows, else None).  A
        ``[rows, prefill_chunk]`` dispatch reads every weight once
        whatever its rows hold, and the rows its bucket pads with are
        the slots that decode: riding makes the step's second read of
        the weights shorter by a pass, or drops it.  It is one more
        choice of the slot-bound rule (``_turnover_horizon``), made
        from the walls this scheduler measured, and only there: with
        nothing waiting, no prompt row to ride beside, a drafter
        configured or no slot that may ride (``_may_ride``), the step
        is what it was.  No horizon at all is a choice only where every
        running slot rides: a step gives each at least one token."""
        self._ride = None
        if not self._slot_bound or self._spec is not None:
            return
        running = self._running_slots()
        riders = [s for s in running if self._may_ride(self.slot_req[s])]
        if not riders or not any(
                r is not None and r.state == PREFILL
                and not self._sp_routed(r) for r in self.slot_req):
            return
        after = self.horizon_buckets[:1] + \
            [0] * (len(riders) == len(running))
        rode, h = self._turnover_horizon(
            self._horizon_cap(running, now), ride=after)
        if rode:
            self._ride = h

    def _rode_only(self):
        """Whether this step's decode pass was its prefill dispatch and
        the plan is for no horizon to follow (``_pick_horizon``)."""
        return bool(self._riders) and self._ride == 0

    def _running_slots(self, among=None):
        """The slots of ``among`` (all of them) that hold a RUNNING
        request."""
        return [s for s in (range(self.num_slots) if among is None
                            else among)
                if self.slot_req[s] is not None
                and self.slot_req[s].state == RUNNING]

    def _close_ride_cycle(self, at=None):
        """The end of a step whose decode pass was its prefill dispatch
        and that launched no horizon: the device is done (the boundary
        sample's pull waited for it), so the cycle ``_step_cost`` times
        ends here, as a harvest ends one that carried a horizon.  A
        dispatch that was in flight across a step boundary ends its
        cycle at its pull (``at``), and the next begins there: pull to
        pull, one dispatch's time on a device that is kept busy."""
        now = time.monotonic() if at is None else at
        self._step_cost.add((RIDE, 0), now - self._cycle_t0)
        self._cycle_t0, self._cycle_open = now, True

    def _reserve(self, running, horizon):
        """Pre-reserve every running slot's pages for the whole horizon
        so growth never interrupts the fused scan.  Under pool pressure
        the horizon shrinks bucket-by-bucket before any eviction runs;
        at horizon 1 the legacy evict/shed policy applies unchanged.
        Returns (horizon, surviving slots)."""
        reclaimable = None   # lazy: the cache can't change mid-loop
        h0 = horizon
        chain = None
        while horizon > 1:
            need = sum(self.kv.pages_needed(
                s, int(self.lengths[s]) +
                min(horizon, self.slot_req[s].remaining_new))
                for s in running)
            avail = self.kv.pool.free_pages
            if need > avail and self.prefix_cache is not None:
                # refcount-free cached pages are reclaimable capacity:
                # don't shrink the horizon while a drain would cover it
                # (the exact tree walk only runs when free pages alone
                # don't already answer the question, and once per
                # dispatch)
                if reclaimable is None:
                    reclaimable = self.prefix_cache.reclaimable_pages()
                avail += reclaimable
            if need <= avail:
                break
            if chain is None and self.mem.enabled:
                chain = self.mem.chain(
                    "reserve", step=self.step_idx, slots=len(running),
                    horizon=h0, pages_needed=need,
                    free_pages=self.kv.pool.free_pages,
                    reclaimable=reclaimable or 0)
            horizon = self._bucket_floor(horizon - 1)
        if chain is not None:
            chain.add("horizon_shrink", from_h=h0, to_h=horizon)
            chain.close("shrunk")
        kept = []
        for slot in running:
            req = self.slot_req[slot]
            if req is None or req.state != RUNNING:
                continue   # evicted by an earlier slot's growth
            budget = min(horizon, req.remaining_new)
            try:
                if self._grow_or_evict(slot,
                                       int(self.lengths[slot]) + budget):
                    kept.append(slot)
            except PagePoolExhausted as e:
                self._close_slot(slot, SHED, f"page capacity: {e}")
            except Exception as e:   # same containment as prefill: the
                self._close_slot(slot, FAILED,  # growth is per-slot work
                                 f"{type(e).__name__}: {e}")
        # a later slot's growth can evict an earlier kept slot too
        return horizon, self._running_slots(kept)

    # --------------------------------------------- speculative decoding
    def _spec_bucket(self, k):
        """Smallest spec-K bucket >= k (compile count stays bounded by
        the bucket set, like horizons)."""
        for b in self.spec_k_buckets:
            if b >= k:
                return b
        return self.spec_k_buckets[-1]

    def _spec_bucket_floor(self, k):
        """Largest spec-K bucket <= k (the pressure-shrink ladder)."""
        out = 1
        for b in self.spec_k_buckets:
            if b <= k:
                out = b
        return out

    def _update_spec_k(self, req, proposed, accepted):
        """Per-request adaptive K: EWMA of the per-round acceptance
        fraction; shrink a bucket when drafts mostly miss (each
        rejected draft column is wasted verify compute + a rolled-back
        KV write), grow back toward ``spec_k`` when they mostly hit."""
        if proposed <= 0:
            return
        rate = accepted / proposed
        prev = getattr(req, "_spec_accept", None)
        req._spec_accept = rate if prev is None else 0.5 * prev + 0.5 * rate
        k = getattr(req, "_spec_k", self.spec_k)
        if req._spec_accept < 0.35:
            k = max(1, k // 2)
        elif req._spec_accept > 0.75:
            k = min(self.spec_k, max(1, k) * 2)
        req._spec_k = self._spec_bucket(k)

    def _collect_drafts(self, running):
        """Ask the drafter for proposals, per-request containment
        included: the ``serve.spec_verify`` fault point fires per
        request here, and an exception from it (or from the drafter)
        degrades THAT request to normal decode — sticky via
        ``_spec_off`` — without touching the loop or its peers."""
        items = []
        for slot in running:
            req = self.slot_req[slot]
            if getattr(req, "_spec_off", False):
                continue
            if req.grammar is not None:
                # a draft column's validity depends on the mask AFTER
                # the previous column — one staged mask per dispatch
                # cannot cover K speculative steps.  The slot rides the
                # verify round as a width-0 one-token decode (the bonus
                # token is drawn under its fresh mask).
                continue
            if not req.sampling.is_greedy and \
                    not getattr(self._spec, "supports_sampling", False):
                # per-request capability gate: a drafter that has not
                # opted into lossless sampled verification only loses
                # THIS slot's proposals, never the round
                continue
            # never draft past the request's budget (the verify bonus
            # token supplies the last one) or the slot's page table
            k = min(getattr(req, "_spec_k", self.spec_k),
                    req.remaining_new - 1,
                    self.kv.max_tokens_per_slot() - int(self.lengths[slot])
                    - 1)
            if k <= 0:
                continue
            try:
                faults.fire("serve.spec_verify", step=self.step_idx,
                            slot=slot, rid=req.rid)
                items.append((slot, req, k))
            except Exception as e:
                req._spec_off = True
                self.metrics.record_spec_degrade(
                    self.step_idx, req.rid, f"{type(e).__name__}: {e}")
        if not items:
            return {}
        try:
            drafts = self._spec.propose(items)
        except Exception:
            # the batch call hides WHICH request blew up — re-propose
            # item by item so the offender(s) degrade sticky while
            # innocent peers keep their drafts (containment: fail one
            # request's speculation, never the round, never the loop)
            drafts = {}
            for item in items:
                slot, req = item[0], item[1]
                try:
                    drafts.update(self._spec.propose([item]))
                except Exception as e:
                    req._spec_off = True
                    self.metrics.record_spec_degrade(
                        self.step_idx, req.rid,
                        f"{type(e).__name__}: {e}")
        out = {}
        for s, _, _ in items:
            # no truthiness on the proposal — a drafter handing back a
            # numpy array would raise on `or`/bool() here, OUTSIDE the
            # containment try/excepts above, and kill the whole loop
            d = drafts.get(s)
            out[s] = [int(t) for t in d] if d is not None and len(d) else []
        return out

    def _dispatch_spec(self, running):
        """One draft/verify round over the running slots.  Returns True
        when a verify dispatch was launched (or the round consumed the
        step by closing slots); False falls back to the normal fused
        horizon — the cold-start/no-proposal path, where the plain
        loop (including overlap) is strictly better."""
        t_prop = time.monotonic()
        drafts = self._collect_drafts(running)
        if self.tracer.enabled:
            self.tracer.complete("spec_propose", t_prop, time.monotonic(),
                                 cat="spec",
                                 args={"proposing": sum(
                                     1 for d in drafts.values() if d)})
        proposing = [s for s in running if drafts.get(s)]
        if not proposing:
            return False
        # mixed-batch gate: a verify round runs every NON-proposing
        # slot as a 1-token decode, so when proposers are a minority
        # of the batch the plain fused horizon (decode_horizon_steps
        # tokens for EVERY slot) out-produces the round server-wide —
        # fall back and let the minority ride it this step.  Abandoned
        # proposals are safe to discard: the ngram drafter is
        # stateless and DraftModelDrafter._sync truncates
        # never-harvested draft KV (same contract as the round-level
        # fault degrade below).
        if 2 * len(proposing) < len(running):
            return False
        k = self._spec_bucket(max(len(d) for d in drafts.values()))
        # page pre-reservation, spec flavor: a verify writes
        # widths[s]+1 positions (rollback releases the surplus), so
        # shrink the K bucket before any eviction would run — same
        # policy ladder as the horizon pre-reservation
        reclaimable = None
        k0 = k
        chain = None
        while k > 1:
            need = sum(self.kv.pages_needed(
                s, int(self.lengths[s]) + min(len(drafts.get(s, ())), k)
                + 1) for s in running)
            avail = self.kv.pool.free_pages
            if need > avail and self.prefix_cache is not None:
                if reclaimable is None:
                    reclaimable = self.prefix_cache.reclaimable_pages()
                avail += reclaimable
            if need <= avail:
                break
            if chain is None and self.mem.enabled:
                chain = self.mem.chain(
                    "spec_reserve", step=self.step_idx,
                    slots=len(running), spec_k=k0, pages_needed=need,
                    free_pages=self.kv.pool.free_pages,
                    reclaimable=reclaimable or 0)
            k = self._spec_bucket_floor(k - 1)
        if chain is not None:
            chain.add("spec_k_shrink", from_k=k0, to_k=k)
            chain.close("shrunk")
        kept = []
        for slot in running:
            req = self.slot_req[slot]
            if req is None or req.state != RUNNING:
                continue
            w = min(len(drafts.get(slot, ())), k)
            try:
                if self._grow_or_evict(slot, int(self.lengths[slot]) + w
                                       + 1):
                    kept.append(slot)
            except PagePoolExhausted as e:
                self._close_slot(slot, SHED, f"page capacity: {e}")
            except Exception as e:
                self._close_slot(slot, FAILED, f"{type(e).__name__}: {e}")
        running = self._running_slots(kept)
        if not running:
            return True
        try:
            # dispatch-level fault point: a raised verify failure
            # degrades the whole round to normal decode (the loop and
            # every request survive; tokens stay exact either way)
            faults.fire("serve.spec_verify", step=self.step_idx)
        except Exception as e:
            self.metrics.record_spec_degrade(
                self.step_idx, None, f"{type(e).__name__}: {e}")
            return False
        draft_arr = np.zeros((self.num_slots, k), np.int32)
        widths = np.zeros(self.num_slots, np.int32)
        active = np.zeros(self.num_slots, bool)
        budgets = np.zeros(self.num_slots, np.int32)
        for s in running:
            d = drafts.get(s, [])[:k]
            draft_arr[s, :len(d)] = d
            widths[s] = len(d)
            active[s] = True
            budgets[s] = self.slot_req[s].remaining_new
        self._chain_budgets = budgets
        t_disp = time.monotonic()
        if self._batch_needs_policy(running):
            pol = self._policy_args(running)
            out = self.engine.verify_multi_policy(
                self.last_tok, draft_arr, active, self.kv.table,
                self.lengths, self.pools, widths=widths, budgets=budgets,
                eos_ids=self._eos_ids, **pol)
            (toks, valid, tok_end, active_end, lengths_end, emitted_end,
             accepted, _counts_end, pools) = out
            self.metrics.record_policy_dispatch(self.step_idx,
                                                len(running))
        else:
            a_ids, a_pack = self._adapter_args()
            out = self.engine.verify_multi(
                self.last_tok, draft_arr, active, self.kv.table,
                self.lengths, self.pools, widths=widths, budgets=budgets,
                eos_ids=self._eos_ids, adapter_ids=a_ids,
                adapters=a_pack)
            (toks, valid, tok_end, active_end, lengths_end, emitted_end,
             accepted, pools) = out
        self.pools = pools
        for arr in (toks, valid):
            if hasattr(arr, "copy_to_host_async"):
                arr.copy_to_host_async()
        self._inflight.append({
            "spec": True,
            "slots": list(running),
            "reqs": {s: self.slot_req[s] for s in running},
            "horizon": k + 1,
            "widths": {s: int(widths[s]) for s in running},
            "accepted": accepted,
            "toks": toks, "valid": valid, "tok_end": tok_end,
            "active_end": active_end, "lengths_end": lengths_end,
            "emitted_end": emitted_end, "release_after": set(),
            "donate": {}, "ahead": (), "left": {},
            "t_dispatch": time.monotonic(),
        })
        if self.tracer.enabled:
            self.tracer.complete("spec_verify_dispatch", t_disp,
                                 time.monotonic(), cat="spec",
                                 args={"k": k, "slots": len(running)})
        return True

    def _dispatch(self):
        """Reserve pages and launch one fused horizon over every running
        slot; returns whether a dispatch was launched (none where the
        rows rode the prefill dispatch and no horizon follows).  The
        batched dispatch is shared — an error here is NOT attributable
        to one request and must surface loudly.

        Where this step's prefill boundary is still on the device
        (``_launch_boundary``) the horizon is launched before its pull:
        a request whose prompt the dispatch finished decodes from here
        on (``_advance_in_flight``), and the slots whose newest token
        is that sample's (``req.owed``: ``ahead``) read it on the
        device.  What the pull then finds -- an end of sequence, a
        cancel, a deadline, a failing callback -- leaves the horizon's
        row for that slot to be dropped at the harvest, the slot parked
        on it until then."""
        if not self._rode_only():
            self._advance_in_flight()
        running = self._running_slots()
        if not running:
            return False
        if self._spec is not None:
            with self.phases("spec_dispatch"):
                took = self._dispatch_spec(running)
            if took:
                return True
        running = self._running_slots(running)
        if not running:
            return False
        # host side of the dispatch: page reservation + argument staging
        # + launching the fused scan (the device's share of the horizon
        # shows up as device_wait at harvest)
        with self.phases("horizon_dispatch") as ph:
            horizon = self._pick_horizon(running, ph.t0)
            if horizon:
                horizon, running = self._reserve(running, horizon)
            picked, p_s, d_s = self._turnover
            # (a reservation that evicted has pulled the boundary)
            ahead = [s for s in running if self.slot_req[s].owed]
            ph.note(horizon=horizon, slots=len(running),
                    slot_bound=int(self._slot_bound), riders=self._riders,
                    ahead=len(ahead),
                    p_ms=round(p_s * 1e3, 3), d_ms=round(d_s * 1e3, 3))
            if not running or not horizon:
                return False
            if self._boundary_now:
                self.metrics.record_horizon_after_boundary(
                    before_pull=bool(self._pf_flight))
            toks = self._last_tok_on_device(ahead) if ahead \
                else self.last_tok
            active = np.zeros(self.num_slots, bool)
            active[running] = True
            budgets = np.zeros(self.num_slots, np.int32)
            for s in running:
                budgets[s] = self.slot_req[s].remaining_new
            # budgets baseline for any chained continuation: the
            # device's `emitted` carry counts from THIS dispatch
            self._chain_budgets = budgets
            if self._batch_needs_policy(running):
                pol = self._policy_args(running)
                out = self.engine.decode_multi_policy(
                    toks, active, self.kv.table, self.lengths,
                    self.pools, horizon=horizon, budgets=budgets,
                    eos_ids=self._eos_ids, **pol)
                self.metrics.record_policy_dispatch(self.step_idx,
                                                    len(running))
            else:
                pol = None
                a_ids, a_pack = self._adapter_args()
                out = self.engine.decode_multi(
                    toks, active, self.kv.table, self.lengths,
                    self.pools, horizon=horizon, budgets=budgets,
                    eos_ids=self._eos_ids, adapter_ids=a_ids,
                    adapters=a_pack, **self.sampling)
            self._commit_dispatch(
                out, running, horizon,
                {s: self.slot_req[s] for s in running}, policy=pol,
                turnover=picked,
                cycle_t0=self._cycle_t0 if self._prefill_rode else None,
                form=(RIDE, horizon) if self._riders else horizon,
                ahead=frozenset(ahead))
        return True

    def _commit_dispatch(self, out, running, horizon, reqs, policy=None,
                         turnover=False, cycle_t0=None, form=None,
                         ahead=frozenset()):
        if policy is not None:
            # the policy twin returns a counts carry before the pools:
            # a chained continuation stages IT (device truth mid-chain)
            # instead of the host mirror
            (toks, valid, tok_end, active_end, lengths_end, emitted_end,
             counts_end, pools) = out
            policy = dict(policy, counts_end=counts_end)
        else:
            (toks, valid, tok_end, active_end, lengths_end, emitted_end,
             pools) = out
        self.pools = pools
        for arr in (toks, valid):
            # overlap: the host copy starts NOW, so the harvest one
            # horizon later rarely stalls on the device
            if hasattr(arr, "copy_to_host_async"):
                arr.copy_to_host_async()
        self._inflight.append({
            "slots": list(running), "reqs": reqs, "horizon": horizon,
            # per-slot upper bound on length advance during this horizon
            # (drives the NEXT chained reservation; actual advance is
            # only known at harvest)
            "max_advance": {s: int(min(horizon, reqs[s].remaining_new))
                            for s in running},
            "toks": toks, "valid": valid, "tok_end": tok_end,
            "active_end": active_end, "lengths_end": lengths_end,
            "emitted_end": emitted_end, "release_after": set(),
            "donate": {}, "policy": policy, "t_dispatch": time.monotonic(),
            # slot -> its length at the launch, for the requests that
            # left their slot before the harvest (_advance_in_flight)
            "left": {},
            # the slots launched off the device's copy of a token the
            # host had not pulled (_last_tok_on_device): one the pull
            # then closes is parked here, its row dropped at the harvest
            "ahead": ahead,
            # whether the slot-bound rule chose this horizon below the
            # configured pick, where the cycle _step_cost times began
            # (None: no prefill dispatch rode it, or it is chained) and
            # the form it files the cycle under
            "turnover": turnover, "cycle_t0": cycle_t0, "form": form,
        })

    def _try_chain(self):
        """Dispatch the next horizon straight off the in-flight
        horizon's device carries — no host round-trip — when membership
        is provably frozen: nothing waiting or prefilling, no
        cancel/deadline pressure, and the next horizon's worst-case page
        growth fits in FREE pages.  A chained dispatch never evicts:
        eviction while the device is still writing a victim's pages
        would corrupt the new owner's cache.  Returns True when the
        chained horizon was dispatched."""
        prev = self._inflight[-1]
        if self._spec is not None or prev.get("spec"):
            # spec rounds need host-authoritative token history (the
            # drafter reads out_tokens) and a host-side rollback per
            # verify — every spec step is a barrier step by design
            return False
        if self.waiting:
            return False
        live = [r for r in self.slot_req if r is not None]
        if any(r.state == PREFILL for r in live):
            return False
        if any(r.cancelled or r.deadline is not None for r in live):
            return False
        cont = [s for s in prev["slots"]
                if self.slot_req[s] is prev["reqs"][s] and
                prev["reqs"][s].state == RUNNING and
                s not in self._zombies]
        if not cont:
            return False
        if any(prev["reqs"][s].grammar is not None for s in cont):
            # a constrained slot's next allowed-token mask depends on
            # the in-flight horizon's tokens (host-compiled DFA): every
            # constrained step is a barrier step
            return False
        if all(prev["reqs"][s].remaining_new - prev["max_advance"][s] <= 0
               for s in cont):
            # the in-flight horizon exhausts every continuing slot's
            # budget: the chained dispatch would scan H steps over
            # all-frozen slots and emit nothing — take the barrier path
            return False
        # remaining_new is an upper bound here (the in-flight horizon's
        # tokens are not appended yet): safe for horizon sizing and page
        # reservation, both of which only over-provision
        horizon = self._bucket_floor(
            min(self.decode_horizon_steps,
                max(prev["reqs"][s].remaining_new for s in cont)))
        targets, need = {}, 0
        for s in cont:
            req = prev["reqs"][s]
            cap = len(req.orig_prompt) + req.max_new_tokens
            targets[s] = min(int(self.lengths[s]) + prev["max_advance"][s]
                             + horizon, cap)
            need += self.kv.pages_needed(s, targets[s])
        short = need - self.kv.pool.free_pages
        if short > 0:
            # a chained dispatch never evicts a live slot (the device
            # may still be writing the victim's pages) — but cache-only
            # pages are not referenced by any LIVE row of an in-flight
            # dispatch (frozen rows read them at worst, and frozen
            # output is discarded), so draining them here is safe and
            # keeps the overlap alive under a warm cache.  Pre-check
            # the exact drainable count so a hopeless chain attempt
            # does not flush the cache on its way to the barrier.
            chain = self.mem.chain(
                "chain", step=self.step_idx, slots=len(cont),
                pages_needed=need,
                free_pages=self.kv.pool.free_pages) \
                if self.mem.enabled else None
            if self.prefix_cache is None or \
                    short > self.prefix_cache.reclaimable_pages():
                # provably-uncoverable shortfall: the most common
                # reason overlap degrades to a barrier step — it must
                # leave a forensics chain like every other capacity
                # decision, not vanish silently
                if chain is not None:
                    chain.close("barrier_fallback")
                return False
            drained = self._reclaim_cached(short)
            if chain is not None:
                chain.add("cache_drain", pages=drained)
                chain.close("drained" if drained >= short
                            else "barrier_fallback")
            if drained < short:
                return False
        try:
            for s in cont:
                faults.fire("serve.page_alloc", step=self.step_idx,
                            slot=s, rid=prev["reqs"][s].rid)
                if not self.kv.ensure_capacity(s, targets[s]):
                    return False
        except PagePoolExhausted:
            return False   # injected exhaustion: take the barrier path
        active = prev["active_end"]
        if self._zombies:
            # freeze slots whose requests were terminated host-side
            # while the previous horizon still had them active
            import jax.numpy as jnp
            keep = np.ones(self.num_slots, bool)
            keep[list(self._zombies)] = False
            active = jnp.logical_and(active, jnp.asarray(keep))
        pol = prev.get("policy")
        if pol is not None:
            # same path as the in-flight horizon, same staged params
            # (membership is frozen, so the slot mirrors are unchanged);
            # tok_base stays the chain-start base — the device's
            # `emitted` carry keeps the position stream continuous —
            # and counts continue from the device carry
            out = self.engine.decode_multi_policy(
                prev["tok_end"], active, self.kv.table,
                prev["lengths_end"], self.pools, horizon=horizon,
                budgets=self._chain_budgets, eos_ids=self._eos_ids,
                emitted=prev["emitted_end"], keys=pol["keys"],
                tok_base=pol["tok_base"], temps=pol["temps"],
                top_ks=pol["top_ks"], top_ps=pol["top_ps"],
                rep_pens=pol["rep_pens"], pres_pens=pol["pres_pens"],
                freq_pens=pol["freq_pens"], counts=pol["counts_end"],
                mask=pol["mask"])
            self.metrics.record_policy_dispatch(self.step_idx, len(cont))
            chain_pol = {k: pol[k] for k in
                         ("keys", "tok_base", "temps", "top_ks", "top_ps",
                          "rep_pens", "pres_pens", "freq_pens", "counts",
                          "mask")}
        else:
            chain_pol = None
            # membership is frozen across a chain, so the slot->adapter
            # map (and therefore the staged ids) is unchanged
            a_ids, a_pack = self._adapter_args()
            out = self.engine.decode_multi(
                prev["tok_end"], active, self.kv.table,
                prev["lengths_end"], self.pools, horizon=horizon,
                budgets=self._chain_budgets, eos_ids=self._eos_ids,
                emitted=prev["emitted_end"], adapter_ids=a_ids,
                adapters=a_pack, **self.sampling)
        self._commit_dispatch(out, cont, horizon,
                              {s: prev["reqs"][s] for s in cont},
                              policy=chain_pol)
        if self.tracer.enabled:
            self.tracer.instant("horizon_chained", cat="dispatch",
                                args={"horizon": horizon,
                                      "slots": len(cont)})
        return True

    def _harvest(self):
        """Pull the oldest in-flight horizon's token block and run the
        host bookkeeping: emit (streaming callbacks + metrics), retire,
        honor cancellations/deadlines/emit-failures discovered mid-
        horizon, and release any deferred pages parked on this horizon.
        Returns (device_wait_s, tokens_delivered)."""
        rec = self._inflight.popleft()
        spec = int(bool(rec.get("spec")))
        # time blocked pulling the token block is the device's (+ the
        # copy's) share of this horizon; 0 means the overlapped copy
        # had already landed
        with self.phases("device_wait", horizon=rec["horizon"],
                         spec=spec) as ph:
            toks = np.asarray(rec["toks"])    # blocks until the device
            valid = np.asarray(rec["valid"])  # (and async copy) catch up
        wait = ph.last_s
        # this horizon is done: one cycle ends here and the next begins
        self._cycle_t0 = ph.t0 + wait
        if rec.get("cycle_t0") is not None:
            self._step_cost.add(rec["form"],
                                self._cycle_t0 - rec["cycle_t0"])
        # host bookkeeping share of the harvest (emit callbacks, retire,
        # rollback) — the counterpart of device_wait above
        with self.phases("harvest", horizon=rec["horizon"],
                         spec=spec) as ph:
            now = ph.t0
            pulled = live_rows = kv_tokens = live_pages = win_tokens = 0
            overrun = 0
            for slot in rec["slots"]:
                req = rec["reqs"][slot]
                # left its slot at this step's plan (_advance_in_flight):
                # these are its last tokens, and there is no slot to close
                left = slot in rec["left"]
                if req.state in TERMINAL or not (
                        left or self.slot_req[slot] is req):
                    # closed at an earlier boundary (zombie), or at the
                    # pull this horizon was launched ahead of
                    overrun += slot in rec["ahead"]
                    continue

                def close(state, reason, slot=slot, req=req, left=left):
                    if left:
                        self._record_closed(req, state, reason)
                    else:
                        self._close_slot_or_defer(slot, state, reason)
                if req.cancelled:
                    # tokens generated past the cancel are dropped: honored
                    # at the horizon boundary, like the legacy step boundary
                    close(CANCELLED, "cancelled")
                    continue
                if req.past_deadline(now):
                    close(SHED, "deadline expired mid-flight")
                    continue
                n = int(valid[slot].sum())
                # step j of the n this slot emits at attends over the
                # length it began the horizon with and its j + 1 new tokens,
                # i.e. the pages up to its cursor at position length + j
                length = rec["left"][slot] if left \
                    else int(self.lengths[slot])
                live_rows += n
                kv_tokens += n * length + n * (n + 1) // 2
                live_pages += sum((length + j) // self.kv.page_size + 1
                                  for j in range(n))
                if self._window:
                    win_tokens += sum(min(length + j + 1, self._window)
                                      for j in range(n))
                if n and req.t_last is not None:
                    # horizon-granularity time-between-tokens: the client-
                    # visible burst cadence (per-token gaps within a burst
                    # are ~0 and still land in tpot)
                    self.metrics.record_tbt(self.step_idx, now - req.t_last)
                for i in range(rec["horizon"]):
                    if not valid[slot, i]:
                        continue
                    tok = int(toks[slot, i])
                    try:
                        self._emit(req, tok)
                        pulled += 1   # only tokens actually DELIVERED count
                        # policy bookkeeping rides the same containment: a
                        # grammar rejection of a delivered token fails THIS
                        # request (the device mask should make it
                        # impossible — reaching it means corrupted state)
                        if not left:
                            self._note_emitted(slot, req, tok)
                    except Exception as e:  # per-request emit/callback fault
                        close(FAILED, f"{type(e).__name__}: {e}")
                        break
                    if req._finished_by(tok) or self._grammar_finished(req):
                        # the device froze the slot at this same token, so
                        # its pages are read-only in any chained horizon:
                        # immediate release is safe.  A grammar cursor with
                        # no continuation (done) finishes the request even
                        # without eos — the constrained output is complete.
                        if left:
                            self._finish(req)
                        else:
                            self._retire(slot)
                        break
                if self.slot_req[slot] is req and req.state == RUNNING and \
                        req.grammar is not None:
                    # refresh the staged mask for the next (barrier)
                    # dispatch — constrained slots run horizon-1 unchained,
                    # so the mask is always exactly one token fresh
                    self._grammar_masks[slot] = req.grammar.token_mask()
                if n and self.tracer.enabled:
                    # one span per (slot, horizon) burst on the slot's own
                    # track: dispatch -> harvest, n tokens delivered.  This
                    # is the per-request timeline row (rid-keyed), emitted
                    # even when the request just retired/closed above.
                    self.tracer.complete(
                        "decode_burst" if not rec.get("spec")
                        else "spec_round", rec["t_dispatch"], now,
                        cat="decode", track=slot, rid=req.trace_rid,
                        args={"tokens": n, "horizon": rec["horizon"]})
                if self.slot_req[slot] is req and req.state == RUNNING:
                    self.lengths[slot] += n
                    if n:
                        self.last_tok[slot] = int(toks[slot][valid[slot]][-1])
            if rec.get("spec"):
                self._harvest_spec(rec, valid)
            self._release_parked(rec)
            if overrun:
                self.metrics.record_lookahead_pull(overrun)
            if rec.get("spec"):
                self.metrics.record_spec_wait(self.step_idx, wait)
            else:
                self.metrics.record_horizon(self.step_idx, rec["horizon"],
                                            pulled, wait, live_rows, kv_tokens,
                                            live_pages, self.kv.table.size,
                                            window_tokens=win_tokens,
                                            turnover=rec["turnover"])
            ph.note(tokens=pulled)
        return wait, pulled

    def _harvest_spec(self, rec, valid):
        """Spec-round epilogue: roll the KV back to the emitted
        boundary (``truncate_slot`` — pages written for rejected drafts
        recycle), feed the drafter its acceptance outcome, adapt each
        request's K, and record the round's telemetry.  Runs after the
        shared emit/retire loop, so ``lengths`` already counts only
        emitted tokens and ``out_tokens`` is current."""
        accepted = np.asarray(rec["accepted"])
        proposed = acc_total = rollbacks = rollback_tokens = 0
        for slot in rec["slots"]:
            req = rec["reqs"][slot]
            w = rec["widths"][slot]
            n = int(valid[slot].sum())
            acc = int(accepted[slot])
            proposed += w
            acc_total += acc
            discard = max(0, (w + 1) - n)
            if discard:
                rollbacks += 1
                rollback_tokens += discard
            req._spec_proposed = getattr(req, "_spec_proposed", 0) + w
            req._spec_hits = getattr(req, "_spec_hits", 0) + acc
            self._update_spec_k(req, w, acc)
            if self.slot_req[slot] is req and req.state == RUNNING:
                # live slot: release pages past the accepted boundary
                # (a retiring slot's surplus pages were already freed —
                # or donated minus the invalid tail — at retire)
                self.kv.truncate_slot(slot, int(self.lengths[slot]))
                if self._spec is not None:
                    try:
                        self._spec.on_verified(slot, req, n, acc)
                    except Exception as e:   # containment, as ever
                        req._spec_off = True
                        self.metrics.record_spec_degrade(
                            self.step_idx, req.rid,
                            f"{type(e).__name__}: {e}")
        self.metrics.record_spec(
            self.step_idx, proposed=proposed, accepted=acc_total,
            emitted=int(valid.sum()), rollbacks=rollbacks,
            rollback_tokens=rollback_tokens, k=rec["horizon"] - 1,
            slot_rounds=sum(1 for s in rec["slots"]
                            if rec["widths"][s] > 0))

    def _close_slot_or_defer(self, slot, state, reason):
        """Terminal removal discovered at a horizon boundary, or while
        a prefill dispatch is in flight.  If a chained horizon is still
        in flight with this slot unfrozen, or a prefill dispatch in
        flight samples a row for it, the device may be writing the
        slot's pages: close the request's bookkeeping NOW (state,
        metrics, history) but hold the pages until that horizon is
        harvested, that dispatch pulled."""
        rec = self._inflight[-1] if self._inflight \
            else self._flight_of(slot)
        if rec is None:
            self._close_slot(slot, state, reason)
            return
        req = self.slot_req[slot]
        self._park(slot, rec)
        self._record_closed(req, state, reason)

    def run(self, max_steps=100000):
        """Drive step() until idle; returns {rid: generated tokens} for
        requests that FINISHED (failed/shed/cancelled requests are
        reported distinctly — see ``health()`` and each request's
        ``.state``/``.error``). The result set is exact for everything
        that finished during (or before) this call even when the bounded
        ``completed`` history has rotated old entries out."""
        self._collect = {r.rid: list(r.out_tokens) for r in self.completed
                         if r.state == FINISHED}
        t0 = time.monotonic()
        try:
            for _ in range(max_steps):
                if not self.step():
                    break
        finally:
            results, self._collect = self._collect, None
        self._wall_s = time.monotonic() - t0
        # max_steps exhausted with live work is a legitimate outcome (a
        # bounded drain): finished requests are returned, the rest stay
        # queued/running for further step() calls
        return results

    # -------------------------------------------------------------- audit
    def audit(self, raise_on_error=True):
        """Refcount invariant audit (serving/mem_telemetry.audit_pool):
        cross-check the pool's refcounts against THIS scheduler's
        holders — slot page tables, the prefix-cache trie, parked
        handoff chains — and the draft pool against the drafter's
        tables.  Raises :class:`~deepspeed_tpu.serving.mem_telemetry.
        AuditError` on a leak, double-free hazard, or orphan table
        entry.  Over a SHARED (disaggregated) pool only the structural
        + double-free directions run (``exact=False``): peer schedulers
        and router-held packets hold references this scheduler cannot
        see — the exact fleet-wide census is ``ClusterRouter.audit()``.
        Also asserts the page-state attribution is conservation-exact
        (the states sum to ``num_pages``)."""
        chains = [r._attach[0] for r in self._pending_attach]
        report = memtel.audit_pool(
            self.kv.pool, managers=[self.kv],
            caches=[self.prefix_cache] if self.prefix_cache is not None
            else [], chains=chains, exact=not self._pool_shared,
            label="kv_pool", raise_on_error=raise_on_error)
        reports = [report]
        # getattr like classify(): a duck-typed custom drafter without
        # the mem_stats hook must not turn a telemetry opt-in into an
        # AttributeError that kills a working serving loop
        stats = None if self._spec is None else \
            getattr(self._spec, "mem_stats", lambda: None)()
        if stats is not None and getattr(self._spec, "kv", None) \
                is not None:
            reports.append(memtel.audit_pool(
                self._spec.kv.pool, managers=[self._spec.kv],
                exact=True, label="draft_pool",
                raise_on_error=raise_on_error))
        counts = memtel.classify(self)
        total = sum(counts.get(k, 0) for k in
                    ("slot", "prefix_shared", "prefix_sole", "handoff",
                     "unattributed", "free"))
        if total != self.kv.pool.num_pages:
            msg = (f"page-state attribution not conservation-exact: "
                   f"{counts} sums to {total} != "
                   f"{self.kv.pool.num_pages}")
            if raise_on_error:
                raise memtel.AuditError(msg)
            reports.append({"label": "attribution", "errors": [msg],
                            "ok": False})
        if not self._pool_shared and counts["unattributed"]:
            msg = (f"{counts['unattributed']} allocated page(s) with no "
                   "known holder on a private pool (leak)")
            if raise_on_error:
                raise memtel.AuditError(msg)
            reports.append({"label": "attribution", "errors": [msg],
                            "ok": False})
        out = {"ok": all(r.get("ok", True) for r in reports),
               "reports": reports, "counts": counts}
        if self.tenancy is not None:
            # per-tenant split of the same census: every attributable
            # page charged to exactly one tenant (a page under two
            # tenants is a cross-tenant leak and fails the audit)
            treport = memtel.classify_tenants(
                self, raise_on_error=raise_on_error)
            reports.append(treport)
            out["ok"] = out["ok"] and treport["ok"]
            out["tenants"] = treport["tenants"]
        return out

    # ------------------------------------------------- comm ledger
    def comm_ledger(self, refresh=False):
        """Compute (and cache) the static HLO comm ledger of every
        serving signature this scheduler's engine has dispatched
        (``profiling/comm_ledger.py``), emit the ``serving/comm/*``
        gauges, and populate the ``comm_*`` health fields.

        The steady-state unit the gauges describe is the *largest
        captured decode_multi horizon* — the dispatch shape a warm
        server settles into; per-signature detail is the return value
        (``{label: ledger}``) and the CI artifact.  First call pays one
        analysis re-compile per signature (lower -> compile -> parse),
        so callers run it off the hot path: at drain/summary time, or
        the first health heartbeat (``ds_serve`` does the latter).
        Empty dict when ``comm_telemetry`` is off."""
        if not self.comm_telemetry or \
                not hasattr(self.engine, "comm_ledger"):
            return {}
        ledgers = self.engine.comm_ledger(refresh=refresh)
        best_h, decode_led = 0, None
        for label, led in ledgers.items():
            m = re.match(r"decode_multi\[h=(\d+)\]", label)
            if m:
                h = int(m.group(1))
                if h > best_h:
                    best_h, decode_led = h, led
        if decode_led is None and "decode" in ledgers:
            best_h, decode_led = 1, ledgers["decode"]
        if decode_led is not None:
            # a decode_multi dispatch serves ALL slots for `horizon`
            # steps, so the per-token unit divides by both — wire
            # bytes per emitted token at full slot occupancy (the
            # like-for-like scorecard unit; partial occupancy moves
            # the realized cost up, never down)
            self._comm_summary = {
                "horizon": best_h,
                "bytes_per_step": int(decode_led["wire_bytes"]),
                "bytes_per_token":
                    round(decode_led["wire_bytes"]
                          / max(best_h * self.num_slots, 1), 1),
                "collectives_per_step": int(decode_led["collectives"]),
                "per_axis": dict(decode_led["per_axis"]),
                "ici_bytes": int(decode_led["per_tier"]["ici"]),
                "dcn_bytes": int(decode_led["per_tier"]["dcn"]),
            }
            self.metrics.record_comm(self.step_idx, self._comm_summary)
        return ledgers

    def comm_health_fields(self):
        """The ``comm_*`` slice of :meth:`health` (the router's fleet
        aggregation reads this directly).  Byte figures are None until
        :meth:`comm_ledger` has analyzed a decode signature — health
        itself never compiles."""
        s = self._comm_summary
        wd = self.compile_watchdog
        return {
            "comm_telemetry": self.comm_telemetry,
            "comm_bytes_per_step":
                None if s is None else s["bytes_per_step"],
            "comm_bytes_per_token":
                None if s is None else s["bytes_per_token"],
            "comm_collectives_per_step":
                None if s is None else s["collectives_per_step"],
            "comm_axis_bytes": None if s is None else s["per_axis"],
            "comm_ici_bytes_per_step":
                None if s is None else s["ici_bytes"],
            "comm_dcn_bytes_per_step":
                None if s is None else s["dcn_bytes"],
            "compile_watchdog": wd is not None,
            "compiles": 0 if wd is None
            else int(sum(wd.counts.values())),
            "steady_recompiles": 0 if wd is None
            else wd.steady_recompiles,
        }

    # ------------------------------------------------------------- health
    def health(self):
        """Liveness/saturation snapshot for operators (exposed by
        ``bin/ds_serve``): current load, pool pressure, step latency,
        and terminal counts by kind."""
        m = self.metrics
        pc = self.prefix_cache
        uptime = max(1e-9, time.monotonic() - self._t_start)
        # page-state attribution: a fresh host sweep per snapshot (the
        # heartbeat cadence, not the hot loop), so health() reports the
        # split whether or not per-step telemetry is on.  Per-device
        # bytes derive from the existing pool_bytes_per_device figure.
        self._pull_routing()
        mem_counts = memtel.classify(self)
        bpp = None
        per_dev = self.mesh_info.get("kv_pool_bytes_per_device")
        if per_dev:
            bpp = per_dev // self.kv.pool.num_pages

        def _bytes(pages):
            return None if bpp is None else int(pages) * bpp
        return {
            "step": self.step_idx,
            "uptime_s": round(uptime, 3),
            "steps_per_s": round(self.step_idx / uptime, 3),
            "tracing": self.tracer.enabled,
            # the last steps over SLOW_STEP_S, each with its split by
            # phase (blocked on the device, in the host loop, or neither)
            "slow_steps": list(self._slow_step_log),
            "mesh": self.mesh_info.get("mesh_shape"),
            "mesh_devices": self.mesh_info.get("mesh_devices"),
            "serving_axes": self.mesh_info.get("serving_axes"),
            # the paged-attention path actually dispatched (kernel vs
            # reference, shard_map vs direct, and why): an accidental
            # reference fallback must show up on the operator surface,
            # not hide behind a silent slowdown
            "paged_attention": self.mesh_info.get("paged_attention"),
            # quantized serving memory: the pool dtype actually
            # allocated (int8/fp8 pools report their TRUE byte
            # footprint below — payload + scale leaves summed, never a
            # hand-computed figure) and the weight storage dtype
            "kv_dtype": self.kv_dtype_name,
            "weight_dtype": getattr(self.engine, "weight_dtype_name",
                                    None),
            "kv_pool_bytes_per_device":
                self.mesh_info.get("kv_pool_bytes_per_device"),
            "kv_pool_bytes_total":
                self.mesh_info.get("kv_pool_bytes_total"),
            # per-slot recurrent state beside the page pool (0 / None
            # for a model without any), and the routed layers' counters
            "state_pool_bytes_per_device":
                self.mesh_info.get("state_pool_bytes_per_device", 0),
            "state_pool_bytes_total":
                self.mesh_info.get("state_pool_bytes_total", 0),
            "state_resets": m.state_resets,
            "moe_assignments": m.moe_assignments,
            "moe_held_assignments": m.moe_held_assignments,
            "moe_held_load_max_over_mean":
                round(m.moe_held_load_max_over_mean(), 4),
            "moe_dense_experts_read_share":
                round(m.moe_dense_experts_read_share(), 4),
            "moe_walk_share": round(m.moe_walk_share(), 4),
            "prefix_cache": pc is not None,
            # why a prefix cache that was asked for is off (the rule
            # for a model with recurrent state), else None
            "prefix_cache_refused": self.prefix_cache_refused,
            "prefix_hit_rate": None if pc is None
            else round(pc.hit_rate(), 4),
            "tokens_reused": 0 if pc is None else pc.tokens_reused,
            "pages_shared": 0 if pc is None else pc.pages_shared,
            "cached_pages": 0 if pc is None else pc.cached_pages,
            "cow_copies": 0 if pc is None else pc.cow_copies,
            "running": sum(r is not None for r in self.slot_req),
            "waiting": len(self.waiting),
            "live_requests": len(self.requests),
            "queue_capacity": self.max_queue,
            "free_pages": self.kv.pool.free_pages,
            "page_utilization": round(self.kv.utilization(), 4),
            "ema_step_ms": None if self._ema_step_s is None
            else round(self._ema_step_s * 1e3, 3),
            "decode_horizon_steps": self.decode_horizon_steps,
            "horizon_buckets": list(self.horizon_buckets),
            # horizons the slot-bound rule chose below the configured
            # pick (_turnover_horizon), and their share of all horizons
            "horizon_turnover_picks": m.horizon_turnover_picks,
            "horizon_turnover_share": m.horizon_turnover_share(),
            "overlap": self.overlap,
            # sequence-parallel prefill: the resolved transport (or why
            # it degraded), the routing threshold, and the fairness cap
            # on up-front page reservations
            "seq_parallel_threshold": self.seq_parallel_threshold,
            "seq_parallel_axis": None if self.seq_plan is None
            else self.seq_plan.axis,
            "seq_parallel_impl": None if self.seq_plan is None
            else self.seq_plan.impl,
            "seq_parallel_degrade_reason": self._sp_degrade_reason,
            "sp_chunk_buckets": list(self.sp_chunk_buckets),
            # batched prefill: one [rows, prefill_chunk] dispatch per
            # boundary step; rows per dispatch near 1 means the traffic
            # bypasses the batching, pad share is the bucket padding
            "prefill_row_buckets": list(self.prefill_row_buckets),
            "prefill_dispatches": m.prefill_dispatches,
            "prefill_rows": m.prefill_rows,
            "prefill_padded_rows": m.prefill_padded_rows,
            "prefill_tokens": m.prefill_tokens,
            "prefill_rows_per_dispatch":
            round(m.prefill_rows_per_dispatch(), 3),
            "prefill_pad_share": round(m.prefill_pad_share(), 4),
            "prefill_dispatches_by_bucket":
            m.prefill_dispatches_by_bucket(),
            "prefill_reserve_cap": self.prefill_reserve_cap,
            "seq_prefill_routed": m.seq_prefill_routed,
            "seq_prefill_chunks": m.seq_prefill_chunks,
            "seq_prefill_degraded": m.seq_prefill_degraded,
            "seq_prefill_shed": m.seq_prefill_shed,
            # decoding-policy subsystem: the scheduler-wide default
            # policy label, and how much of the traffic actually used
            # per-request sampling / grammar constraints
            "decoding_policy": self.default_sampling.label(),
            "sampled_requests": m.sampled_requests,
            "grammar_requests": m.grammar_requests,
            "policy_dispatches": m.policy_dispatches,
            "grammar_violations": m.grammar_violations,
            "spec_decode": self.spec_mode,
            "spec_k": self.spec_k if self._spec is not None else None,
            "spec_acceptance_rate": round(m.spec_acceptance_rate(), 4),
            "spec_mean_accepted": round(m.spec_mean_accepted(), 3),
            "spec_draft_tokens": m.spec_proposed,
            "spec_accepted_tokens": m.spec_accepted,
            "spec_rollbacks": m.spec_rollbacks,
            "spec_degraded": m.spec_degraded,
            "mem_telemetry": self.mem.enabled,
            "mem_slot_pages": mem_counts["slot"],
            "mem_prefix_shared_pages": mem_counts["prefix_shared"],
            "mem_prefix_sole_pages": mem_counts["prefix_sole"],
            "mem_handoff_pages": mem_counts["handoff"],
            "mem_draft_pages": mem_counts.get("draft", 0),
            "mem_unattributed_pages": mem_counts["unattributed"],
            "mem_free_pages": mem_counts["free"],
            "mem_free_frac": round(
                self.kv.pool.free_pages / self.kv.pool.num_pages, 4),
            "mem_page_seconds": round(self.mem.page_seconds, 3)
            if self.mem.enabled else 0.0,
            "mem_pressure_events": m.mem_pressure_events,
            "mem_pressure_episodes": m.mem_pressure_episodes,
            "mem_slot_bytes_per_device": _bytes(mem_counts["slot"]),
            "mem_prefix_bytes_per_device": _bytes(
                mem_counts["prefix_shared"] + mem_counts["prefix_sole"]),
            "mem_handoff_bytes_per_device": _bytes(
                mem_counts["handoff"]),
            "mem_free_bytes_per_device": _bytes(mem_counts["free"]),
            # communication & compile observability (PR 12): the HLO
            # comm-ledger summary (None until comm_ledger() ran — a
            # health probe must never pay an analysis compile) and the
            # recompile-watchdog counters
            **self.comm_health_fields(),
            # serving autotuner (ROADMAP item 3): online-controller
            # presence + nudge count, and the searched-config
            # provenance (--tuned-config PATH; None = hand-set)
            "online_tuner": self.online is not None,
            "tune_nudges": m.tune_nudges,
            "tuned_from": self.tuned_from,
            "inflight_horizons": len(self._inflight),
            "draining": self.draining,
            "handoffs": m.handoffs,
            "pending_handoffs": len(self._pending_attach),
            # handoff transport (cross-pool chain transfers; all zero
            # on the shared-pool path, which moves page ids only)
            "handoff_bytes_out": m.handoff_bytes_out,
            "handoff_bytes_in": m.handoff_bytes_in,
            "handoff_chunks": m.handoff_chunks,
            "handoff_transport_ms": round(m.handoff_transport_ms, 3),
            "handoff_aborted": m.handoff_aborted,
            "completed": m.completed,
            "failed": m.failed,
            "shed": m.shed,
            "cancelled": m.cancelled,
            "preemptions": m.preemptions,
            "tokens_emitted": m.tokens_emitted,
            "last_error": self._last_error,
            "ha_epoch": self.ha_epoch,
            "ha_fenced": self.ha_fenced,
            # multi-tenant serving tier: per-tenant usage ledgers
            # (page-seconds billed, admissions, sheds) + live page
            # footprints, and the loaded adapter-store shape (the
            # rank bucket is a jit-signature input — operators watch
            # it to understand warmup recompiles)
            "tenancy": self.tenancy is not None,
            "tenants": None if self.tenancy is None
            else self.tenancy.usage_fields(),
            "tenant_pages": None if self.tenancy is None
            else {t: self._tenant_pages(t)
                  for t in sorted(self.tenancy.tenants)},
            "adapters": 0 if self.tenancy is None or
            self.tenancy.store is None else len(self.tenancy.store),
            "adapter_rank_bucket": 0 if self.tenancy is None or
            self.tenancy.store is None
            else self.tenancy.store.rank_bucket(),
            "quota_shed": m.quota_shed,
        }

    def _pull_routing(self):
        """The routed layers' counters ride the pools every dispatch
        returns; a snapshot or a summary reads them (it waits for the
        dispatch in flight) and the metrics keep the differences."""
        read = getattr(self.engine, "routing_counters", None)
        counters = None if read is None else read(self.pools)
        if counters is not None:
            self.metrics.record_routing(self.step_idx, counters)

    def _phase_summary(self):
        """What the phases of step() add up to: seconds by depth-one
        phase (they sum to ``step_wall_s``), the host's share of the
        steps' wall with BOTH device waits taken out (``device_wait_frac``
        books the first-token wait as host time), and the slow steps."""
        total = self.phases.total
        wall = total("step")
        out = {f"phase_{k}_s": round(total(k), 6)
               for k in STEP_PHASES + ("first_token_wait",)}
        out["step_wall_s"] = round(wall, 6)
        out["host_busy_frac"] = round(
            (wall - total(*BLOCKED_PHASES)) / wall, 4) if wall else 0.0
        out["first_token_wait_frac"] = round(
            total("first_token_wait") / wall, 4) if wall else 0.0
        out["slow_steps"] = self._slow_steps
        out["slow_step_max_s"] = round(self._slow_step_max_s, 4)
        out["slow_step_max_blocked_s"] = round(
            self._slow_step_max_blocked_s, 4)
        return out

    def summary(self):
        self._pull_routing()
        out = self.metrics.summary(getattr(self, "_wall_s", None))
        out.update(self._phase_summary())
        if self.mem.enabled:
            # per-request memory attribution aggregates: page-seconds
            # is the unit the autotuner's cost model bills capacity in
            out.update(self.mem.summary_fields())
        return out
