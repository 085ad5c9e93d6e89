"""Serving observability: TTFT / per-token latency / queue and pool
gauges, emitted as ``(tag, value, step)`` events through the existing
``monitor/`` path (MonitorMaster.write_events) so serving metrics land in
the same TensorBoard/WandB/CSV sinks as training metrics.

Latency samples are durations computed by the scheduler from
``time.monotonic()`` timestamps — never wall-clock, so an NTP step
cannot produce negative or wild TTFT/ITL values.  Terminal outcomes are
counted distinctly (completed / failed / shed / cancelled): an operator
must be able to tell "we errored" from "we refused load"."""

from collections import Counter, deque

import numpy as np

from deepspeed_tpu.monitor.monitor import clamp_min_step


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if values else 0.0


class ServingMetrics:
    """Aggregates per-request latency samples and per-step gauges."""

    def __init__(self, monitor=None):
        self.monitor = monitor        # MonitorMaster-compatible (or None)
        self.ttft_s = []              # submit -> first token, per request
        self.queue_wait_s = []        # submit -> first admission, per request
        self.tpot_s = []              # inter-token gaps, per token
        self.tbt_s = []               # horizon-boundary gaps, per request
        self.completed = 0
        self.failed = 0               # per-request error, contained
        self.shed = 0                 # deadline/capacity load shedding
        self.cancelled = 0
        self.preemptions = 0
        self.tokens_emitted = 0
        self.page_util = []           # pool utilization per step
        self.queue_depths = []
        self.horizons = []            # fused decode horizon per harvest
        self.horizon_turnover_picks = 0   # of them, chosen under the
        # configured pick by the slot-bound rule (_turnover_horizon)
        self.device_wait_s = 0.0      # step time blocked on the device
        self.host_s = 0.0             # step time doing host bookkeeping
        # prefix-cache aggregates (admission-time KV reuse)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefill_tokens_saved = 0  # == cached prefix tokens reused
        self.cache_evictions = 0       # cached pages drained under pressure
        # speculative decoding (draft/verify rounds)
        self.spec_dispatches = 0       # verify_multi rounds harvested
        self.spec_proposed = 0         # draft tokens scored (sum widths)
        self.spec_accepted = 0         # drafts the target's argmax matched
        self.spec_emitted = 0          # tokens a verify round produced
        self.spec_rollbacks = 0        # rounds that discarded written KV
        self.spec_rollback_tokens = 0  # KV positions rolled back
        self.spec_slot_rounds = 0      # (slot, round) pairs that proposed
        self.spec_degraded = 0         # drafter/verify faults contained
        self.spec_degrade_log = deque(maxlen=64)  # (step, rid, reason)
        self.handoffs = 0              # prefill->decode KV chains handed
        self.handoff_tokens = 0        # prefilled positions transferred
        # handoff transport (cross-pool transfers; all 0 on shared_pool)
        self.handoff_bytes_out = 0     # KV payload bytes exported
        self.handoff_bytes_in = 0      # KV payload bytes imported
        self.handoff_chunks = 0        # chunk dispatches either direction
        self.handoff_transport_ms = 0.0  # wall ms moving chains
        self.handoff_aborted = 0       # transfers torn down mid-chain
        # sequence-parallel prefill (long-context routing)
        self.prefill_dispatches = 0    # shared [rows, chunk] dispatches
        self.prefill_rows = 0          # slot chunks those carried
        self.prefill_padded_rows = 0   # rows dispatched incl. bucket pad
        self.prefill_by_bucket = Counter()  # row bucket -> dispatches
        self.prefill_tokens = 0        # prompt tokens those landed
        # decoding slots that took their next token as a one-token row
        # of a prefill dispatch (the scheduler's _plan_ride), the
        # dispatches that carried one, and the slot-bound steps: all,
        # and those whose decode pass was the dispatch and nothing else
        self.ride_rows = 0
        self.ride_dispatches = 0
        self.slot_bound_steps = 0
        self.horizon_none_steps = 0
        # prefill look-ahead (the scheduler's _launch_boundary): shared
        # dispatches launched while the one before had its sampled
        # tokens still on the device, boundaries that were pulled at
        # once or early by reason, and rows a dispatch computed for a
        # request the pull before it found finished
        self.lookahead_dispatches = 0
        self.lookahead_fallbacks = Counter()
        self.overrun_rows = 0
        self.horizons_after_boundary = 0
        self.horizons_before_pull = 0
        self.seq_prefill_routed = 0    # prompts routed onto the sp path
        self.seq_prefill_chunks = 0    # sp chunk dispatches
        self.seq_prefill_tokens = 0    # prompt tokens landed via sp chunks
        self.seq_prefill_degraded = 0  # long prompts kept on chunked path
        self.seq_prefill_shed = 0      # prompts shed on the reserve cap
        # decoding-policy subsystem (serving/sampling/)
        self.sampled_requests = 0      # intakes with a sampled policy
        self.grammar_requests = 0      # intakes carrying a grammar
        self.policy_dispatches = 0     # fused dispatches on the policy twins
        self.grammar_violations = 0    # grammar cursor rejected a token
        # memory telemetry (MemTelemetry drives these; all 0 when off)
        self.mem_pressure_events = 0   # capacity causal chains recorded
        self.mem_pressure_episodes = 0  # sustained episodes fired
        # multi-tenant serving (tenancy on; all 0 otherwise)
        self.quota_shed = 0            # requests shed on page quota
        # online autotuner (OnlineTuner drives these; all 0 when off)
        self.tune_nudges = 0           # knob nudges applied
        self.tune_log = deque(maxlen=64)   # (step, knob, value)
        # per-slot state beside the page pool (a recurrent layer's, a
        # window layer's ring), and routed layers (all 0 for a model
        # with neither)
        self.state_pool_bytes = 0      # per-slot state / rings allocated
        self.kv_pool_bytes = 0         # K/V pages allocated, beside it
        self.state_resets = 0          # prefill rows that began at 0
        self.prefix_cache_refused = 0  # a prefix cache asked for, refused
        self.moe_assignments = 0       # (token, choice) pairs routed
        self.moe_held_assignments = 0  # of those, on experts held here
        self.moe_load_ratio_q10 = 0    # sum of 1024 x busiest/mean, a call
        self.moe_calls = 0             # routed-layer calls
        self.moe_dense_calls = 0       # of those, in the small-call form
        self.moe_read_share_q10 = 0    # sum of 1024 x touched / held
        self.moe_walk_calls = 0        # small calls that walked
        self._routing_seen = None      # last raw device counters
        # what the decode steps NEEDED (the programs compute every slot
        # and every page of capacity): host-side sums at harvest
        self.decode_steps = 0          # steps of the harvested horizons
        self.decode_live_rows = 0      # sum over steps: slots that emit
        self.decode_kv_tokens = 0      # sum over those: the slot's length
        self.decode_live_pages = 0     # sum over those: the pages it spans
        self.decode_table_pages = 0    # sum over steps: slots x max_pages
        self.decode_window_tokens = 0  # decode_kv_tokens, each length
        #                                cut to the window ring (0: none)
        # what the prefill dispatches needed of the PAGED layers: over
        # the rows, the keys a chunk reads (its start + its columns) and
        # the (query, key) pairs it scores
        self.prefill_kv_tokens = 0
        self.prefill_kv_pairs = 0
        # and of the WINDOW layers (0: none): the same two with each
        # query's keys cut to the window, its own position included
        self.prefill_window_tokens = 0
        self.prefill_window_pairs = 0
        # and of the page table: over the dispatches, the (row, page)
        # entries the rows' chunks reach (what the paged prefill
        # kernel's work list holds) and padded rows x pages a slot
        self.prefill_live_pages = 0
        self.prefill_table_pages = 0
        # and of the kernel's grid: the steps it walks (key blocks of
        # several pages, one-page steps of a walked tail) and the pages
        # those steps compute (a masked tail block computes dead ones)
        self.prefill_key_blocks = 0
        self.prefill_block_pages = 0
        # what a token costs in pages and a slot in rings (gauges)
        self.kv_paged_bytes_per_token = 0
        self.kv_window_bytes_per_slot = 0
        # a latent page pool: what a token costs as published (layers x
        # the latent vector) and as the pool stores it, padding included
        self.kv_latent_bytes_per_token = 0
        self.kv_stored_bytes_per_token = 0
        self.mesh_info = {}            # serving topology (record_mesh)
        self._events = []

    # ---------------------------------------------------------- recording
    def _write(self, events):
        """The ONE funnel serving events take to the monitor sink.  The
        ``step >= 1`` invariant is enforced centrally here
        (``monitor.clamp_min_step`` — construction-time gauges
        legitimately predate step 1 and stamp to it silently;
        MonitorMaster additionally clamps-with-warning for emitters
        outside this funnel), replacing the old per-callsite
        hand-stamping."""
        if self.monitor is not None:
            self.monitor.write_events(clamp_min_step(events, warn=False))

    def record_mesh(self, mesh_info, step=0):
        """One-shot serving-topology gauges at scheduler construction:
        per-axis mesh sizes and the per-device KV-pool footprint (each
        device holds its kv-head shard of every page).  Scalar-only
        sinks get one gauge per mesh axis; the full map rides
        ``health()``.  Fires before the first live step — the central
        clamp in ``_write`` lands it at step 1."""
        self.mesh_info = mesh_info
        self.kv_pool_bytes = int(mesh_info.get("kv_pool_bytes_total") or 0)
        events = [(f"serving/mesh/{ax}", size, step)
                  for ax, size in
                  (mesh_info.get("mesh_shape") or {}).items()]
        if mesh_info.get("kv_pool_bytes_per_device") is not None:
            events.append(("serving/mesh/kv_pool_bytes_per_device",
                           mesh_info["kv_pool_bytes_per_device"], step))
        self._write(events)

    def record_step(self, step, *, queue_depth, running, waiting,
                    page_utilization, device_wait_s=0.0, host_s=0.0,
                    cached_pages=None):
        self.page_util.append(page_utilization)
        self.queue_depths.append(queue_depth)
        self.device_wait_s += device_wait_s
        self.host_s += host_s
        self._events = [
            ("serving/queue_depth", queue_depth, step),
            ("serving/running", running, step),
            ("serving/waiting", waiting, step),
            ("serving/page_utilization", page_utilization, step),
            ("serving/device_wait_ms", device_wait_s * 1e3, step),
            ("serving/host_ms", host_s * 1e3, step),
        ]
        if cached_pages is not None:
            self._events.append(
                ("serving/prefix_cache/cached_pages", cached_pages, step))
        self._write(self._events)

    def record_prefix(self, step, cached_tokens, prompt_tokens):
        """One admission-time prefix-cache lookup: ``cached_tokens`` of
        the ``prompt_tokens``-long prompt were served from cached pages
        (0 = miss).  Every cached token is a prefill token NOT
        computed."""
        self.prefix_lookups += 1
        if cached_tokens > 0:
            self.prefix_hits += 1
            self.prefill_tokens_saved += cached_tokens
        self._write([
                ("serving/prefix_cache/cached_prefix_tokens",
                 cached_tokens, step),
                ("serving/prefix_cache/hit_rate",
                 self.prefix_hits / self.prefix_lookups, step),
                ("serving/prefix_cache/prefill_tokens_saved",
                 self.prefill_tokens_saved, step),
            ])

    def record_prefill_dispatch(self, step, *, rows, padded_rows, tokens,
                                kv_tokens=0, kv_pairs=0, window_tokens=0,
                                window_pairs=0, riders=0,
                                live_pages=0, table_pages=0, key_blocks=0,
                                block_pages=0, lookahead=False):
        """One shared prefill dispatch carried the next chunk of
        ``rows`` prefilling slots (``tokens`` prompt tokens) in a
        ``padded_rows``-row bucket, and beside them the next token of
        ``riders`` decoding slots (one-token rows: in neither ``rows``
        nor ``tokens``); over all of them its rows read ``kv_tokens``
        keys of the paged layers and scored ``kv_pairs`` (query, key)
        pairs (in a window layer ``window_tokens`` keys, the window of
        a row's first query and its own columns, and ``window_pairs``
        pairs), on ``live_pages`` (row, page) entries of the
        ``table_pages`` = ``padded_rows`` x pages a slot that the
        dispatch's page table holds; the ``paged_prefill`` kernel walks
        them in ``key_blocks`` grid steps that compute ``block_pages``
        pages (``ops/attention/paged_prefill.count_key_blocks``).
        ``lookahead``: it was launched while the dispatch before it had
        its sampled tokens still on the device, or while the horizon
        before it was unharvested."""
        self.prefill_dispatches += 1
        self.lookahead_dispatches += bool(lookahead)
        self.ride_rows += int(riders)
        self.ride_dispatches += riders > 0
        self.prefill_kv_tokens += int(kv_tokens)
        self.prefill_kv_pairs += int(kv_pairs)
        self.prefill_window_tokens += int(window_tokens)
        self.prefill_window_pairs += int(window_pairs)
        self.prefill_live_pages += int(live_pages)
        self.prefill_table_pages += int(table_pages)
        self.prefill_key_blocks += int(key_blocks)
        self.prefill_block_pages += int(block_pages)
        self.prefill_rows += rows
        self.prefill_padded_rows += padded_rows
        self.prefill_by_bucket[padded_rows] += 1
        self.prefill_tokens += tokens
        self._write([("serving/prefill/rows", rows, step),
                     ("serving/prefill/padded_rows", padded_rows, step),
                     ("serving/prefill/tokens", tokens, step)])

    def record_state_pool(self, nbytes, step=0, *, paged_bytes_per_token=0,
                          window_bytes_per_slot=0):
        """One-shot gauges at scheduler construction: bytes of per-slot
        state (recurrent state, window rings) allocated beside the page
        pool; what a token costs in the paged layers and a slot in the
        window layers' rings."""
        self.state_pool_bytes = int(nbytes)
        self.kv_paged_bytes_per_token = int(paged_bytes_per_token)
        self.kv_window_bytes_per_slot = int(window_bytes_per_slot)
        self._write([("serving/state/pool_bytes", int(nbytes), step)])

    def record_latent_pool(self, published, stored):
        """One-shot gauges at scheduler construction over a latent page
        pool: bytes a token as published and as stored."""
        self.kv_latent_bytes_per_token = int(published)
        self.kv_stored_bytes_per_token = int(stored)

    def record_state_resets(self, step, rows):
        """``rows`` prefill rows of one dispatch began at position 0:
        their slots' state started from zeros (a ring: unseen)."""
        self.state_resets += int(rows)
        self._write([("serving/state/resets", int(rows), step)])

    def record_prefix_refused(self, step=0):
        """A prefix cache was asked for and refused: the model keeps
        recurrent state, which pages cannot share."""
        self.prefix_cache_refused += 1
        self._write([("serving/prefix_cache/refused", 1, step)])

    def record_routing(self, step, counters):
        """The routed layers' device counters as they stand (uint32 [7],
        moe/held_experts.routing_stats summed over layers and calls,
        wrapping at 2**32): the differences since the last reading are
        added up here."""
        now = [int(c) for c in counters]
        seen = self._routing_seen or [0] * len(now)
        self._routing_seen = now
        d_all, d_held, d_ratio, d_calls, d_dense, d_read, d_walk = (
            (a - b) % (1 << 32) for a, b in zip(now, seen))
        if not d_calls:
            return
        self.moe_assignments += d_all
        self.moe_held_assignments += d_held
        self.moe_load_ratio_q10 += d_ratio
        self.moe_calls += d_calls
        self.moe_dense_calls += d_dense
        self.moe_read_share_q10 += d_read
        self.moe_walk_calls += d_walk
        self._write([
            ("serving/moe/assignments", d_all, step),
            ("serving/moe/held_assignments", d_held, step),
            ("serving/moe/held_load_max_over_mean",
             d_ratio / 1024.0 / d_calls, step)])

    def moe_held_load_max_over_mean(self):
        """The busiest held expert's pairs over the mean held expert's,
        averaged over routed-layer calls."""
        return self.moe_load_ratio_q10 / 1024.0 / self.moe_calls \
            if self.moe_calls else 0.0

    def moe_dense_experts_read_share(self):
        """The share of the held experts with a pair -- whose weights
        the walk read -- in a small-call routed-layer call, averaged
        over those calls; in [0, 1]."""
        return self.moe_read_share_q10 / 1024.0 / self.moe_dense_calls \
            if self.moe_dense_calls else 0.0

    def moe_walk_share(self):
        """The share of the small calls that walked their touched
        experts (the others computed every held expert at once)."""
        return self.moe_walk_calls / self.moe_dense_calls \
            if self.moe_dense_calls else 0.0

    def ride_steps_share(self):
        """Share of the shared prefill dispatches that carried a
        decoding slot's next token beside the prompt rows."""
        return self.ride_dispatches / self.prefill_dispatches \
            if self.prefill_dispatches else 0.0

    def record_slot_bound_step(self, no_horizon):
        """A barrier step whose admission left requests waiting;
        ``no_horizon``: its decoding slots rode the prefill dispatch and
        no decode horizon followed."""
        self.slot_bound_steps += 1
        self.horizon_none_steps += bool(no_horizon)

    def record_lookahead_fallback(self, reason):
        """A step kept the barrier order at one of its two points.  A
        prefill boundary whose tokens were pulled before anything else
        was launched: at once (``policy``, ``spec``, ``drain``,
        ``other``) or early, from flight (``eviction``, ``drain``,
        ``other``).  Or a prefill dispatch launched only after the
        horizon in flight at the step's start had been harvested
        (``not_slot_bound``, ``no_prefill``, ``ride``, ``pages``,
        ``policy``, ``spec``, ``drain``)."""
        self.lookahead_fallbacks[reason] += 1

    def record_lookahead_pull(self, overrun_rows):
        """``overrun_rows`` rows were computed for a request that had
        finished or been closed by then and dropped: by the pull of a
        boundary that was in flight across a step boundary, or by the
        harvest of a horizon launched ahead of its boundary's pull."""
        self.overrun_rows += int(overrun_rows)

    def record_horizon_after_boundary(self, before_pull):
        """A decode horizon was launched in a step whose prefill
        dispatch sampled a row; ``before_pull``: ahead of the pull of
        that sample, off the device's copy of its tokens."""
        self.horizons_after_boundary += 1
        self.horizons_before_pull += bool(before_pull)

    def prefill_lookahead_share(self):
        """Share of the shared prefill dispatches launched before the
        dispatch ahead of them was back on the host: the previous
        one's sampled tokens not pulled, or the horizon in flight not
        harvested."""
        return self.lookahead_dispatches / self.prefill_dispatches \
            if self.prefill_dispatches else 0.0

    def horizon_lookahead_share(self):
        """Share of the horizons that followed a prefill boundary in
        its step and were launched before its pull."""
        return self.horizons_before_pull / self.horizons_after_boundary \
            if self.horizons_after_boundary else 0.0

    def horizon_none_share(self):
        """Share of the slot-bound steps that launched no horizon."""
        return self.horizon_none_steps / self.slot_bound_steps \
            if self.slot_bound_steps else 0.0

    def prefill_rows_per_dispatch(self):
        """Mean prefilling slots per shared prefill dispatch — how often
        the batching engages (near 1: the traffic bypasses it)."""
        return self.prefill_rows / self.prefill_dispatches \
            if self.prefill_dispatches else 0.0

    def prefill_pad_share(self):
        """Share of dispatched prefill rows that were bucket padding."""
        return 1.0 - self.prefill_rows / self.prefill_padded_rows \
            if self.prefill_padded_rows else 0.0

    def prefill_dispatches_by_bucket(self):
        """Shared prefill dispatches per row bucket they rode in, as
        ``{"16": n, "32": n, ...}`` (JSON keys; buckets never used are
        absent) — which programs of the bucket set the traffic runs."""
        return {str(b): n for b, n in sorted(self.prefill_by_bucket.items())}

    def record_seq_prefill_route(self, step, prompt_tokens, reserved_pages):
        """One admission routed onto the sequence-parallel prefill path:
        the full ``reserved_pages`` page chain is held up front so the
        wide chunks never stall mid-prompt on allocation."""
        self.seq_prefill_routed += 1
        self._write([
            ("serving/seq_prefill/routed", prompt_tokens, step),
            ("serving/seq_prefill/reserved_pages", reserved_pages, step),
        ])

    def record_seq_prefill_chunk(self, step, tokens):
        self.seq_prefill_chunks += 1
        self.seq_prefill_tokens += tokens
        self._write([("serving/seq_prefill/chunk_tokens", tokens, step)])

    def record_seq_prefill_degrade(self, step):
        """A prompt crossed the length threshold but stayed on the
        chunked path (no usable sequence axis, or the up-front page
        reservation self-preempted)."""
        self.seq_prefill_degraded += 1
        self._write([("serving/seq_prefill/degraded", 1, step)])

    def record_seq_prefill_shed(self, step, pages_needed):
        """A long prompt's up-front reservation exceeded the per-request
        cap (prefill_reserve_frac) and the request was shed with reason
        rather than allowed to starve concurrent short requests."""
        self.seq_prefill_shed += 1
        self._write([
            ("serving/seq_prefill/shed_reserve_cap", pages_needed, step)])

    def record_tenants(self, step, *, active, page_seconds, max_share):
        """Per-step tenancy gauges: tenants with live pages, the summed
        page-seconds ledger across all tenants, and the largest single
        tenant's share of the pool (the fairness headline — a weighted
        mix should keep it near its weight fraction).  Names are FIXED
        scalars (taxonomy-pinned); per-tenant detail rides
        ``health()['tenants']``, never dynamic gauge names."""
        self._write([
            ("serving/tenant/active", active, step),
            ("serving/tenant/page_seconds", page_seconds, step),
            ("serving/tenant/max_share", max_share, step),
        ])

    def record_quota_shed(self, step):
        """A request shed because its tenant's page quota could not
        cover it even after draining the tenant's own cached pages."""
        self.quota_shed += 1
        self._write([("serving/tenant/quota_shed", 1, step)])

    def record_cache_eviction(self, step, pages):
        """Cached pages drained back to the free list under pool
        pressure (reclaim, not failure)."""
        self.cache_evictions += pages
        self._write(
                [("serving/prefix_cache/evicted_pages", pages, step)])

    def record_tbt(self, step, gap_s):
        """Time-between-token-bursts at HORIZON granularity: the gap a
        streaming client sees between one request's consecutive token
        deliveries.  With fused horizons tokens arrive in bursts, so
        this — not the intra-burst tpot gap — is the client-visible
        latency cadence."""
        self.tbt_s.append(gap_s)
        self._write(
                [("serving/tbt_ms", gap_s * 1e3, step)])

    def record_horizon(self, step, horizon, tokens, device_wait_s,
                       live_rows=0, kv_tokens=0, live_pages=0,
                       table_pages=0, window_tokens=0, turnover=False):
        """One fused decode horizon was harvested: its step count, the
        tokens it delivered, and how long the host blocked waiting for
        the device (0 when the overlapped copy had already landed).
        ``live_rows`` sums, over its steps, the slots that emitted a
        token; ``kv_tokens`` sums, over those, the slot's length at
        that step (the keys its attention needed) and ``live_pages`` the
        pages that length spans (what the paged decode kernel walks);
        ``table_pages`` is one step's whole page table, slots x pages a
        slot; ``window_tokens`` is ``kv_tokens`` with each length cut to
        the window a ring holds (0 for a model without one);
        ``turnover`` says the scheduler was slot-bound and chose this
        horizon below its configured pick to turn slots over faster."""
        self.horizons.append(horizon)
        self.horizon_turnover_picks += bool(turnover)
        self.decode_steps += int(horizon)
        self.decode_live_rows += int(live_rows)
        self.decode_kv_tokens += int(kv_tokens)
        self.decode_live_pages += int(live_pages)
        self.decode_table_pages += int(horizon) * int(table_pages)
        self.decode_window_tokens += int(window_tokens)
        self._write([
                ("serving/horizon", horizon, step),
                ("serving/horizon_tokens", tokens, step),
                ("serving/horizon_wait_ms", device_wait_s * 1e3, step),
            ])

    def horizon_turnover_share(self):
        """Share of the harvested horizons that the slot-bound rule
        chose below the configured pick (0.0 before any horizon)."""
        return round(self.horizon_turnover_picks / len(self.horizons), 4) \
            if self.horizons else 0.0

    def record_spec(self, step, *, proposed, accepted, emitted, rollbacks,
                    rollback_tokens, k, slot_rounds=0):
        """One speculative draft/verify round was harvested: ``proposed``
        draft tokens were scored in one dispatch, ``accepted`` matched
        the target's argmax, ``emitted`` tokens came out (accepted
        prefixes + one bonus token per live slot), and
        ``rollback_tokens`` KV positions written for rejected drafts
        were rolled back across ``rollbacks`` slots."""
        self.spec_dispatches += 1
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        self.spec_emitted += emitted
        self.spec_rollbacks += rollbacks
        self.spec_rollback_tokens += rollback_tokens
        self.spec_slot_rounds += slot_rounds
        self._write([
                ("serving/spec/k", k, step),
                ("serving/spec/proposed", proposed, step),
                ("serving/spec/accepted", accepted, step),
                ("serving/spec/emitted", emitted, step),
                ("serving/spec/acceptance_rate",
                 accepted / proposed if proposed else 0.0, step),
                ("serving/spec/rollback_tokens", rollback_tokens, step),
            ])

    def record_spec_degrade(self, step, rid=None, reason=None):
        """A drafter exception or injected verify failure was contained:
        the request (or the round) degraded to normal decode.  The
        monitor sinks are scalar-only, so the which/why goes into
        ``spec_degrade_log`` (bounded) for operator inspection."""
        self.spec_degraded += 1
        self.spec_degrade_log.append((step, rid, reason))
        self._write([("serving/spec/degraded", 1, step)])

    def record_spec_wait(self, step, device_wait_s):
        """Host time blocked pulling a verify round's results."""
        self._write(
                [("serving/spec/wait_ms", device_wait_s * 1e3, step)])

    def spec_acceptance_rate(self):
        return self.spec_accepted / self.spec_proposed \
            if self.spec_proposed else 0.0

    def spec_mean_accepted(self):
        """Mean accepted draft tokens per proposing slot per round (the
        speedup driver: each slot-round costs ~one shared target
        forward and yields mean_accepted + 1 tokens)."""
        return self.spec_accepted / self.spec_slot_rounds \
            if self.spec_slot_rounds else 0.0

    def record_mem(self, step, counts, free_frac, page_seconds):
        """One memory-attribution sample (MemTelemetry.on_step): the
        page-state split of the pool (conservation-exact — the states
        sum to num_pages), the free fraction, and the cumulative
        page-seconds integral across all requests."""
        self._write([
                ("serving/mem/slot_pages", counts.get("slot", 0), step),
                ("serving/mem/prefix_shared_pages",
                 counts.get("prefix_shared", 0), step),
                ("serving/mem/prefix_sole_pages",
                 counts.get("prefix_sole", 0), step),
                ("serving/mem/handoff_pages",
                 counts.get("handoff", 0), step),
                ("serving/mem/draft_pages", counts.get("draft", 0), step),
                ("serving/mem/unattributed_pages",
                 counts.get("unattributed", 0), step),
                ("serving/mem/free_pages", counts.get("free", 0), step),
                ("serving/mem/free_frac", free_frac, step),
                ("serving/mem/page_seconds", page_seconds, step),
            ])

    def record_pressure(self, step, trigger):
        """One capacity-decision causal chain was recorded (the
        which/why — trigger, drained pages, victim — lives in the
        MemTelemetry pressure log; the monitor sinks are scalar-only)."""
        self.mem_pressure_events += 1
        self._write([("serving/mem/pressure", 1, step)])

    def record_pressure_episode(self, step):
        """Sustained pool pressure: the free fraction stayed under the
        episode threshold for the configured step window."""
        self.mem_pressure_episodes += 1
        self._write([("serving/mem/pressure_episode", 1, step)])

    # the per-knob gauge set is closed over the online tuner's three
    # safely-re-resolvable knobs (docs/autotuning.md knob table)
    _TUNE_KNOBS = ("decode_horizon", "spec_k", "prefix_cache_pages")

    def record_tune(self, step, knob, value):
        """One online-tuner nudge was applied: ``knob`` moved to
        ``value`` (the new live setting, not a delta).  The which/why
        detail (reason string) lives in the tuner's bounded nudge log
        and the ``tune_nudge`` tracer instant; monitor sinks get the
        counter plus the per-knob gauge."""
        if knob not in self._TUNE_KNOBS:
            raise ValueError(f"unknown tuned knob {knob!r}; the gauge "
                             f"set is closed over {self._TUNE_KNOBS}")
        self.tune_nudges += 1
        self.tune_log.append((step, knob, value))
        self._write([
                ("serving/tune/nudge", 1, step),
                (f"serving/tune/{knob}", value, step),
            ])

    # the serving/comm/axis/* gauge set is closed over MeshConfig's
    # known axes (like serving/mesh/*): scalar sinks get one gauge per
    # axis, joint-axis groups ("data+model") ride health()'s JSON dict
    _COMM_AXES = ("data", "model", "pipe", "expert", "sequence")

    def record_comm(self, step, summary):
        """The HLO comm-ledger summary of the steady-state decode
        dispatch (``ServingScheduler.comm_ledger``): per-device wire
        bytes per step/token, collective count, the per-mesh-axis split
        and the ICI/DCN tier attribution — static-analysis gauges, so
        they re-emit only when the ledger is (re)computed."""
        events = [
            ("serving/comm/bytes_per_step",
             summary["bytes_per_step"], step),
            ("serving/comm/bytes_per_token",
             summary["bytes_per_token"], step),
            ("serving/comm/collectives_per_step",
             summary["collectives_per_step"], step),
            ("serving/comm/ici_bytes_per_step",
             summary["ici_bytes"], step),
            ("serving/comm/dcn_bytes_per_step",
             summary["dcn_bytes"], step),
        ]
        for ax in self._COMM_AXES:
            if ax in summary["per_axis"]:
                events.append(
                    (f"serving/comm/axis/{ax}",
                     summary["per_axis"][ax], step))
        self._write(events)

    def record_recompile(self, step, cumulative):
        """The recompile watchdog detected steady-state jit signature
        churn (the compile-storm class); value = cumulative steady
        recompiles."""
        self._write([("serving/comm/recompile", cumulative, step)])

    def record_policy_request(self, step, *, sampled, grammar):
        """One intake (submit/attach) carried a non-default decoding
        policy: it samples/penalizes (``sampled``) and/or is grammar-
        constrained (``grammar``)."""
        events = []
        if sampled:
            self.sampled_requests += 1
            events.append(("serving/sampling/sampled_requests",
                           self.sampled_requests, step))
        if grammar:
            self.grammar_requests += 1
            events.append(("serving/sampling/grammar_requests",
                           self.grammar_requests, step))
        if events:
            self._write(events)

    def record_policy_dispatch(self, step, slots):
        """One fused dispatch took the policy twins (decode_multi_policy
        / verify_multi_policy) — per-slot traced sampling lanes instead
        of the legacy greedy statics — over ``slots`` running slots."""
        self.policy_dispatches += 1
        self._write([("serving/sampling/policy_dispatch", slots, step)])

    def record_grammar_violation(self, step, rid=None):
        """The host grammar cursor rejected a token the device emitted —
        the device mask makes this unreachable in a healthy loop, so a
        violation means corrupted constraint state; the request fails
        contained."""
        self.grammar_violations += 1
        self._write([("serving/sampling/grammar_violation", 1, step)])

    def record_handoff(self, step, tokens):
        """One prefill->decode KV handoff: ``tokens`` prefilled
        positions changed owners (zero-copy by page id on a shared
        pool; as a chunked chain transfer across pools — see
        :meth:`record_handoff_transport`)."""
        self.handoffs += 1
        self.handoff_tokens += tokens
        self._write([
                ("serving/handoff", 1, step),
                ("serving/handoff_tokens", tokens, step)])

    def record_handoff_transport(self, step, direction, nbytes, chunks,
                                 ms):
        """One completed chain transfer on THIS scheduler's side:
        ``direction`` is ``"out"`` (chain exported off this pool) or
        ``"in"`` (chain imported into it).  ``nbytes`` is exact KV
        payload bytes — ``pages * engine.kv_page_bytes(...)`` — the
        number the comm ledger's DCN tier aggregates (a cross-process
        handoff is host-staged DCN traffic by definition)."""
        if direction == "out":
            self.handoff_bytes_out += int(nbytes)
        else:
            self.handoff_bytes_in += int(nbytes)
        self.handoff_chunks += int(chunks)
        self.handoff_transport_ms += float(ms)
        self._write([
                ("serving/comm/handoff_bytes", int(nbytes), step),
                ("serving/handoff/chunks", int(chunks), step),
                ("serving/handoff/transfer_ms", float(ms), step)])

    def record_handoff_abort(self, step):
        """A chain transfer torn down mid-flight (fault or death on
        either side): partial pages were freed on both pools and the
        request requeued unified."""
        self.handoff_aborted += 1
        self._write([("serving/handoff/aborted", 1, step)])

    def record_queue_wait(self, wait_s):
        """Submit -> first admission of one request: the part of its
        TTFT spent in the queue (the rest is prefill steps)."""
        self.queue_wait_s.append(wait_s)

    def record_first_token(self, step, ttft_s):
        self.ttft_s.append(ttft_s)
        self.tokens_emitted += 1
        self._write(
                [("serving/ttft_ms", ttft_s * 1e3, step)])

    def record_token(self, step, gap_s):
        self.tpot_s.append(gap_s)
        self.tokens_emitted += 1
        self._write(
                [("serving/token_latency_ms", gap_s * 1e3, step)])

    def record_completion(self, step):
        self.completed += 1

    def record_terminal(self, step, state, rid, reason=None):
        """A request left the loop without finishing: ``state`` is
        ``failed`` (contained per-request error), ``shed`` (deadline or
        capacity refusal) or ``cancelled``."""
        if state == "failed":
            self.failed += 1
        elif state == "shed":
            self.shed += 1
        elif state == "cancelled":
            self.cancelled += 1
        self._write([(f"serving/{state}", 1, step)])

    def record_preemption(self, step):
        self.preemptions += 1

    # ----------------------------------------------------------- summary
    def summary(self, wall_s=None):
        out = {
            "completed": self.completed,
            "failed": self.failed,
            "shed": self.shed,
            "cancelled": self.cancelled,
            "tokens_emitted": self.tokens_emitted,
            "preemptions": self.preemptions,
            "ttft_ms_p50": round(_percentile(self.ttft_s, 50) * 1e3, 3),
            "ttft_ms_p90": round(_percentile(self.ttft_s, 90) * 1e3, 3),
            "ttft_ms_p99": round(_percentile(self.ttft_s, 99) * 1e3, 3),
            "queue_wait_ms_p50":
            round(_percentile(self.queue_wait_s, 50) * 1e3, 3),
            "queue_wait_ms_p90":
            round(_percentile(self.queue_wait_s, 90) * 1e3, 3),
            "tpot_ms_p50": round(_percentile(self.tpot_s, 50) * 1e3, 3),
            "tpot_ms_p90": round(_percentile(self.tpot_s, 90) * 1e3, 3),
            "tpot_ms_p99": round(_percentile(self.tpot_s, 99) * 1e3, 3),
            "tbt_ms_p50": round(_percentile(self.tbt_s, 50) * 1e3, 3),
            "tbt_ms_p90": round(_percentile(self.tbt_s, 90) * 1e3, 3),
            "tbt_ms_p99": round(_percentile(self.tbt_s, 99) * 1e3, 3),
            "horizon_mean": round(float(np.mean(self.horizons)), 3)
            if self.horizons else 0.0,
            "horizon_turnover_picks": self.horizon_turnover_picks,
            "horizon_turnover_share": self.horizon_turnover_share(),
            "device_wait_frac": round(
                self.device_wait_s / (self.device_wait_s + self.host_s), 4)
            if (self.device_wait_s + self.host_s) > 0 else 0.0,
            "page_util_mean": round(float(np.mean(self.page_util)), 4)
            if self.page_util else 0.0,
            "page_util_peak": round(float(np.max(self.page_util)), 4)
            if self.page_util else 0.0,
            "queue_depth_peak": int(np.max(self.queue_depths))
            if self.queue_depths else 0,
            "prefix_hit_rate": round(
                self.prefix_hits / self.prefix_lookups, 4)
            if self.prefix_lookups else 0.0,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "cache_evictions": self.cache_evictions,
            "spec_dispatches": self.spec_dispatches,
            "spec_draft_tokens": self.spec_proposed,
            "spec_accepted_tokens": self.spec_accepted,
            "spec_acceptance_rate": round(self.spec_acceptance_rate(), 4),
            "spec_mean_accepted": round(self.spec_mean_accepted(), 3),
            "spec_rollbacks": self.spec_rollbacks,
            "spec_rollback_tokens": self.spec_rollback_tokens,
            "spec_degraded": self.spec_degraded,
            "handoffs": self.handoffs,
            "handoff_tokens": self.handoff_tokens,
            "handoff_bytes_out": self.handoff_bytes_out,
            "handoff_bytes_in": self.handoff_bytes_in,
            "handoff_chunks": self.handoff_chunks,
            "handoff_transport_ms": round(self.handoff_transport_ms, 3),
            "handoff_aborted": self.handoff_aborted,
            "prefill_dispatches": self.prefill_dispatches,
            "prefill_rows": self.prefill_rows,
            "prefill_padded_rows": self.prefill_padded_rows,
            "prefill_tokens": self.prefill_tokens,
            "prefill_rows_per_dispatch":
            round(self.prefill_rows_per_dispatch(), 3),
            "prefill_pad_share": round(self.prefill_pad_share(), 4),
            "ride_rows": self.ride_rows,
            "ride_steps_share": round(self.ride_steps_share(), 4),
            "horizon_none_share": round(self.horizon_none_share(), 4),
            "prefill_lookahead_share":
            round(self.prefill_lookahead_share(), 4),
            "prefill_lookahead_fallbacks":
            dict(sorted(self.lookahead_fallbacks.items())),
            "prefill_overrun_rows": self.overrun_rows,
            "horizon_lookahead_share":
            round(self.horizon_lookahead_share(), 4),
            "prefill_dispatches_by_bucket":
            self.prefill_dispatches_by_bucket(),
            "seq_prefill_routed": self.seq_prefill_routed,
            "seq_prefill_chunks": self.seq_prefill_chunks,
            "seq_prefill_tokens": self.seq_prefill_tokens,
            "seq_prefill_degraded": self.seq_prefill_degraded,
            "seq_prefill_shed": self.seq_prefill_shed,
            "sampled_requests": self.sampled_requests,
            "grammar_requests": self.grammar_requests,
            "policy_dispatches": self.policy_dispatches,
            "grammar_violations": self.grammar_violations,
            "tune_nudges": self.tune_nudges,
            "state_pool_bytes": self.state_pool_bytes,
            "kv_pool_bytes": self.kv_pool_bytes,
            "decode_steps": self.decode_steps,
            "decode_live_rows": self.decode_live_rows,
            "decode_kv_tokens": self.decode_kv_tokens,
            "decode_window_tokens": self.decode_window_tokens,
            "prefill_kv_tokens": self.prefill_kv_tokens,
            "prefill_kv_pairs": self.prefill_kv_pairs,
            "prefill_window_tokens": self.prefill_window_tokens,
            "prefill_window_pairs": self.prefill_window_pairs,
            "kv_paged_bytes_per_token": self.kv_paged_bytes_per_token,
            "kv_window_bytes_per_slot": self.kv_window_bytes_per_slot,
            "kv_latent_bytes_per_token": self.kv_latent_bytes_per_token,
            "kv_stored_bytes_per_token": self.kv_stored_bytes_per_token,
            "decode_live_page_share":
            round(self.decode_live_pages / self.decode_table_pages, 4)
            if self.decode_table_pages else None,
            "prefill_live_page_share":
            round(self.prefill_live_pages / self.prefill_table_pages, 4)
            if self.prefill_table_pages else None,
            "prefill_key_block_fill_share":
            round(self.prefill_live_pages / self.prefill_block_pages, 4)
            if self.prefill_block_pages else None,
            "prefill_pages_per_step":
            round(self.prefill_live_pages / self.prefill_key_blocks, 4)
            if self.prefill_key_blocks else None,
            "state_resets": self.state_resets,
            "prefix_cache_refused": self.prefix_cache_refused,
            "moe_assignments": self.moe_assignments,
            "moe_held_assignments": self.moe_held_assignments,
            "moe_calls": self.moe_calls,
            "moe_dense_calls": self.moe_dense_calls,
            "moe_held_load_max_over_mean":
            round(self.moe_held_load_max_over_mean(), 4),
            "moe_dense_experts_read_share":
            round(self.moe_dense_experts_read_share(), 4),
            "moe_walk_share": round(self.moe_walk_share(), 4),
        }
        if wall_s:
            out["tokens_per_sec"] = round(self.tokens_emitted / wall_s, 2)
        return out


class ClusterMetrics:
    """Router-tier counters: what the fleet did with requests, kept
    separate from each replica's own :class:`ServingMetrics` (an
    operator must see "one replica died and its work replayed" even
    when every per-replica summary looks clean).  Events ride the same
    ``write_events`` monitor contract under ``cluster/``."""

    def __init__(self, monitor=None):
        self.monitor = monitor
        self.submitted = 0            # journal admissions (deduped rids)
        self.duplicate_rids = 0       # idempotent re-submissions absorbed
        self.routed = 0               # request->replica assignments
        self.finished = 0
        self.failed = 0
        self.shed = 0
        self.cancelled = 0
        self.replays = 0              # requests replayed off a dead replica
        self.replayed_tokens = 0      # emitted tokens folded into replays
        self.failovers = 0            # replica deaths detected
        self.retries = 0              # backpressure resubmission attempts
        self.heartbeat_misses = 0
        self.drains = 0               # replica drains completed
        self.restarts = 0
        self.handoffs = 0             # prefill->decode packets delivered
        self.degraded_routes = 0      # routed unified for lack of a
                                      # healthy prefill worker
        # handoff transport aggregates (cross-pool chain transfers)
        self.handoff_transfers = 0    # completed chain transfers
        self.handoff_bytes = 0        # KV payload bytes moved
        self.handoff_chunks = 0       # chunk dispatches
        self.handoff_transfer_ms = 0.0  # wall ms source-send -> adopted
        self.handoff_aborts = 0       # transfers torn down mid-chain
        self.handoff_paths = {"shared_pool": 0, "device_put": 0,
                              "wire": 0}

    def record_handoff_transfer(self, step, path, nbytes, chunks, ms):
        """One chain transfer completed end to end through the router:
        ``path`` is the three-way transport dispatch
        (shared_pool | device_put | wire)."""
        self.handoff_transfers += 1
        self.handoff_bytes += int(nbytes)
        self.handoff_chunks += int(chunks)
        self.handoff_transfer_ms += float(ms)
        self.handoff_paths[path] = self.handoff_paths.get(path, 0) + 1
        self.event(step, "handoff_bytes", int(nbytes))

    def record_handoff_abort(self, step):
        """A chain transfer torn down mid-flight: partial pages freed
        on both pools, request requeued unified."""
        self.handoff_aborts += 1
        self.event(step, "handoff_abort")

    def event(self, step, tag, value=1):
        if self.monitor is not None:
            # same central step>=1 enforcement as ServingMetrics._write
            # (replacing the old inline max(1, step) workaround)
            self.monitor.write_events(clamp_min_step(
                [(f"cluster/{tag}", value, step)], warn=False))

    def record_terminal(self, step, state):
        if state == "finished":
            self.finished += 1
        elif state == "failed":
            self.failed += 1
        elif state == "shed":
            self.shed += 1
        elif state == "cancelled":
            self.cancelled += 1
        self.event(step, state)

    def summary(self):
        return {
            "submitted": self.submitted,
            "duplicate_rids": self.duplicate_rids,
            "routed": self.routed,
            "finished": self.finished,
            "failed": self.failed,
            "shed": self.shed,
            "cancelled": self.cancelled,
            "replays": self.replays,
            "replayed_tokens": self.replayed_tokens,
            "failovers": self.failovers,
            "retries": self.retries,
            "heartbeat_misses": self.heartbeat_misses,
            "drains": self.drains,
            "restarts": self.restarts,
            "handoffs": self.handoffs,
            "degraded_routes": self.degraded_routes,
            "handoff_transfers": self.handoff_transfers,
            "handoff_bytes": self.handoff_bytes,
            "handoff_chunks": self.handoff_chunks,
            "handoff_transfer_ms": round(self.handoff_transfer_ms, 3),
            "handoff_mb_per_s": round(
                self.handoff_bytes / 1e6
                / (self.handoff_transfer_ms / 1e3), 3)
            if self.handoff_transfer_ms > 0 else 0.0,
            "handoff_aborts": self.handoff_aborts,
            "handoff_paths": dict(self.handoff_paths),
        }


class HaMetrics:
    """Router-HA observability (cluster/ha.RouterSupervisor): takeover
    counts and fencing gauges, separate from :class:`ClusterMetrics`
    because they outlive any single router — a takeover retires the
    primary's metrics object but the supervisor's survive.  Events ride
    ``write_events`` under ``router/``."""

    def __init__(self, monitor=None):
        self.monitor = monitor
        self.failovers = 0         # standby takeovers (router deaths)
        self.epoch = 0             # current lease epoch
        self.fenced_writes = 0     # WAL appends rejected from old epochs
        self.wal_records = 0       # WAL records accepted (lifetime)

    def gauge(self, step, tag, value):
        if self.monitor is not None:
            self.monitor.write_events(clamp_min_step(
                [(f"router/{tag}", value, step)], warn=False))

    def record_takeover(self, step, epoch, fenced_writes, wal_records):
        self.failovers += 1
        self.record_gauges(step, epoch, fenced_writes, wal_records)

    def record_gauges(self, step, epoch, fenced_writes, wal_records):
        self.epoch = int(epoch)
        self.fenced_writes = int(fenced_writes)
        self.wal_records = int(wal_records)
        self.gauge(step, "failovers", self.failovers)
        self.gauge(step, "epoch", self.epoch)
        self.gauge(step, "fenced_writes", self.fenced_writes)
        self.gauge(step, "wal_records", self.wal_records)
