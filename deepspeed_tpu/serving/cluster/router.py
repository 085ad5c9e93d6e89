"""Front-end router: prefix-aware load balancing with zero-lost-request
failover over a replica fleet.

The router is pure host logic pumped cooperatively (``step()`` /
``run()``), exactly like the scheduler under it.  One pump iteration:

1. **heartbeats** — poll every replica's ``heartbeat()``; a raise (or
   ``heartbeat_misses`` consecutive misses for process replicas) marks
   the replica dead and *replays* its unfinished journal entries:
   emitted tokens fold into the prompt (the preemption-recompute
   trick), so a survivor continues the stream token-exact without
   re-emitting a single token.
2. **handoff dispatch** — finished-prompt KV chains from prefill
   workers attach to decode workers in the same group; a failed or
   faulted handoff (``cluster.handoff``) frees the pages and requeues
   the request for unified serving — contained, never lost.
3. **routing** — queued entries pick a replica: prefill workers first
   when the tier is disaggregated and one is healthy (else unified,
   counted as a degraded route); among candidates the *prefix-aware*
   policy scores each replica by how many prompt tokens its radix
   cache already holds (``PrefixCache.prefix_len``) and ties break by
   load then round-robin. ``QueueFull``/backpressure costs a bounded
   retry with exponential backoff + jitter; the retry budget exhausted
   sheds the request distinctly.
4. **pump replicas** — step each live replica once; a raise is a
   replica death (see 1), never a router death.
5. **collect** — replica-side terminal states propagate to the
   journal: finished/cancelled/failed/deadline-shed finalize; a
   capacity shed requeues under the same bounded retry budget.

Admission is **at-most-once** (client idempotency rids dedupe in the
journal), replay is **at-least-once** (a request may run partially on
several replicas), and client output is **exactly-once** (the journal
is the only token path and drops post-terminal stragglers).
"""

import json
import time
from collections import deque

import numpy as np

from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving.cluster import journal as jn
from deepspeed_tpu.serving.cluster import transport as tp
from deepspeed_tpu.serving.cluster.journal import RequestJournal
from deepspeed_tpu.serving.cluster.replica import (DEAD, DRAINING, UP,
                                                   LocalReplica,
                                                   ReplicaKilled,
                                                   StaleEpoch)
from deepspeed_tpu.serving.metrics import ClusterMetrics
from deepspeed_tpu.serving.page_manager import PagePool, PagePoolExhausted
from deepspeed_tpu.serving.scheduler import ServingScheduler, _PoolsRef


class DisaggGroup:
    """A prefill/decode worker group and its transport path.

    ``transport`` is the three-way dispatch rule
    (:func:`transport.choose_transport`): ``shared_pool`` groups share
    ONE physical page pool + device-pools ref (handoff = page-id
    ownership transfer, zero copies); ``device_put`` groups give every
    worker its own pool in one process (chains move chunk-wise through
    ``export_page_chain`` -> ``jax.device_put`` ->
    ``import_page_chain``); ``wire`` groups are separate processes
    (chains move as length-prefixed frames over KV sidecar fds,
    relayed by the router).  ``pool``/``pools_ref`` are None except on
    the shared path."""

    def __init__(self, name, pool, pools_ref, transport="shared_pool"):
        self.name = name
        self.pool = pool
        self.pools_ref = pools_ref
        self.transport = transport


class _Packet:
    """A finished-prompt KV chain in flight between workers.

    ``prompt`` is the EXACT token sequence whose KV the pages hold (the
    prompt the prefill worker served) — the decode-side request must be
    keyed on it, not on the journal's current folded prompt, because
    the boundary token was already journal-emitted by the time the
    packet dispatches and folding it again would double-count it.

    Cross-pool packets also carry the transfer ``manifest`` (chunk
    count / exact bytes / digest / epoch), the source replica
    (``src_rep`` — whose pool the pages still live in until the
    transfer completes), and, on the wire path, the worker-side rid
    the source's sidecar frames are keyed by (``wire_rid``; ``pages``
    is empty and ``pool`` None — the payload never exists as router-
    side pages)."""

    __slots__ = ("entry", "group", "prompt", "pages", "length",
                 "first_tok", "pool", "manifest", "src_rep", "wire_rid")

    def __init__(self, entry, group, prompt, pages, length, first_tok,
                 pool, manifest=None, src_rep=None, wire_rid=None):
        self.entry = entry
        self.group = group
        self.prompt = prompt
        self.pages = pages
        self.length = length
        self.first_tok = first_tok
        self.pool = pool
        self.manifest = manifest
        self.src_rep = src_rep
        self.wire_rid = wire_rid


class _Transfer:
    """One in-flight cross-pool chain transfer (``device_put`` path):
    destination pages are allocated up front, then the chain moves one
    chunk per router pump — export-gather from the (live, still
    serving) source pool, ``device_put`` to the destination sharding,
    scatter-import — so the transfer overlaps both sides' ongoing
    decode horizons.  The ``cluster.handoff`` fault point fires per
    chunk, and death of either side mid-transfer aborts: partial pages
    freed on BOTH pools, request requeued unified."""

    __slots__ = ("pkt", "dst_rep", "dst_pages", "dst_pool", "chunks",
                 "seq", "t0", "nbytes", "page_bytes", "flow")

    def __init__(self, pkt, dst_rep, dst_pages, t0):
        self.pkt = pkt
        self.dst_rep = dst_rep
        self.dst_pages = dst_pages
        # captured now: a replica death drops its scheduler, but the
        # pool object is stable — partial pages stay freeable
        self.dst_pool = dst_rep.sched.kv.pool
        self.chunks = list(tp.iter_chunks(pkt.pages))
        self.seq = 0
        self.t0 = t0
        self.nbytes = 0
        src_sched = pkt.src_rep.sched
        self.page_bytes = src_sched.engine.kv_page_bytes(
            src_sched.kv.page_size, src_sched.kv_dtype_name)
        self.flow = f"handoff:{pkt.entry.rid}:{id(self)}"

    def done(self):
        return self.seq >= len(self.chunks)

    def advance_chunk(self):
        """Move ONE chunk; the caller owns fault/death policy."""
        import jax
        src_sched = self.pkt.src_rep.sched
        dst_sched = self.dst_rep.sched
        chunk = self.chunks[self.seq]
        src_chunk = chunk
        payload, _ = tp.export_chunk(src_sched.engine, src_sched.pools,
                                     src_chunk)
        # same-process fast path: both pools live on one mesh, so the
        # device_put to the destination's pool NamedSharding is a
        # resharding-free placement (on separate hosts this is the DCN
        # hop)
        pool_sh = dst_sched.engine._serving_shardings().pool
        payload = jax.device_put(payload, pool_sh)
        dst_chunk = self.dst_pages[self.seq * tp.CHUNK_PAGES:
                                   self.seq * tp.CHUNK_PAGES + len(chunk)]
        tp.import_chunk(dst_sched.engine, dst_sched._pools_ref, payload,
                        dst_chunk, dst_sched.kv.pool.num_pages)
        self.nbytes += len(chunk) * self.page_bytes
        self.seq += 1


class _WireRelay:
    """One in-flight wire transfer (``wire`` path, separate processes):
    the prefill worker's exported frames, buffered host-side by the
    source ``ProcessReplica``, streaming into the decode worker's KV
    sidecar fd a few frames per router pump.  The decode worker
    scatters each chunk on arrival and only attaches the request once
    the manifest verifies (chunk count, exact bytes, running digest)."""

    __slots__ = ("pkt", "dst_rep", "handle", "frames", "seq", "t0",
                 "flow")

    def __init__(self, pkt, dst_rep, handle, frames, t0):
        self.pkt = pkt
        self.dst_rep = dst_rep
        self.handle = handle
        self.frames = frames
        self.seq = 0
        self.t0 = t0
        self.flow = f"handoff:{pkt.entry.rid}:{id(self)}"


class ClusterRouter:
    """Load-balance requests across engine replicas; lose none."""

    def __init__(self, replicas, *, routing="prefix", retry_max=3,
                 retry_backoff_s=0.02, heartbeat_misses=3, monitor=None,
                 seed=0, term_grace_s=10.0, tracer=None,
                 flight_recorder=None, journal=None, wal=None,
                 epoch=None, lease=None, transfer_chunks_per_step=2):
        if routing not in ("prefix", "round_robin"):
            raise ValueError(f"unknown routing policy {routing!r}")
        self.replicas = list(replicas)
        self.routing = routing
        self.retry_max = int(retry_max)
        self.retry_backoff_s = float(retry_backoff_s)
        self.heartbeat_misses = int(heartbeat_misses)
        self.term_grace_s = float(term_grace_s)
        # Router HA (cluster/ha.py): `epoch` tags every replica-facing
        # call and every WAL append; `lease` is the shared authority a
        # RouterSupervisor moves between primaries.  Both None = the
        # legacy single-router mode, fencing entirely off.
        self.epoch = epoch
        self.lease = lease
        self.fenced_dispatches = 0   # replica-side StaleEpoch rejections
        self.fenced_tokens = 0       # sink-side stale-epoch token drops
        self.stale_sink_tokens = 0   # ownership-fence drops (flapping)
        if journal is not None:
            self.journal = journal
        else:
            self.journal = RequestJournal(wal=wal,
                                          epoch=0 if epoch is None
                                          else int(epoch))
        self.metrics = ClusterMetrics(monitor)
        self.step_idx = 0
        self._rr = 0
        self._rng = np.random.default_rng(seed)
        self._by_handle = {}     # id(replica handle) -> journal entry
        self._packets = deque()
        # in-flight cross-pool chain transfers, advanced
        # `transfer_chunks_per_step` chunks per pump so a transfer
        # overlaps the whole fleet's serving instead of stalling it
        self._transfers = []
        self.transfer_chunks_per_step = max(1,
                                            int(transfer_chunks_per_step))
        self._has_prefill = any(r.role == "prefill" for r in self.replicas)
        # fleet tracing: the router records routing/failover/handoff
        # spans under its own process label and hands every replica a
        # tracer of its own (the replica keeps it across die/restart);
        # dump_trace() merges the lot into ONE Chrome-trace JSON — one
        # process per replica, the rid linking a request's spans across
        # them.  flight_recorder (serving/trace.FlightRecorder) dumps
        # every source's recent-span window on replica death, correlated
        # with the journal entries that were in flight.
        self.tracer = tracer
        self.flight = flight_recorder
        if tracer is not None:
            from deepspeed_tpu.serving.trace import SpanTracer
            for rep in self.replicas:
                if hasattr(rep, "enable_trace") and \
                        getattr(rep, "tracer", None) is None:
                    rep.enable_trace(SpanTracer(process=str(rep.id)))
        if self.flight is not None:
            if tracer is not None:
                self.flight.register("router", tracer)
            for rep in self.replicas:
                if getattr(rep, "tracer", None) is not None:
                    self.flight.register(str(rep.id), rep.tracer)
                elif hasattr(rep, "trace_events"):
                    self.flight.register(
                        str(rep.id),
                        (lambda r: (lambda: list(r.trace_events)))(rep))
                if hasattr(rep, "attach_mem_flight"):
                    # replicas running memory telemetry dump their
                    # sustained-pressure episodes into the FLEET
                    # recorder (journal-correlatable rids ride along)
                    rep.attach_mem_flight(self.flight)
                if hasattr(rep, "attach_comm_flight"):
                    # and the recompile watchdog's steady-state churn
                    # dumps land in the same fleet recorder
                    rep.attach_comm_flight(self.flight)
        for rep in self.replicas:
            if rep.role == "prefill" and hasattr(rep, "set_handoff_sink"):
                if getattr(rep.group, "transport",
                           "shared_pool") == "wire":
                    rep.set_handoff_sink(
                        self._make_wire_handoff_sink(rep))
                else:
                    rep.set_handoff_sink(self._make_handoff_sink(rep))

    # ------------------------------------------------------------ intake
    def submit(self, prompt, max_new_tokens=32, eos_token_id=None,
               on_token=None, deadline_s=None, rid=None, sampling=None,
               seed=None, grammar=None, tenant=None, adapter=None):
        """Journal a request (idempotent on ``rid``) for routing at the
        next pump.  Returns the journal entry — its ``state`` /
        ``emitted`` are the client-visible truth across any number of
        replica deaths.  ``sampling``/``seed``/``grammar`` are wire
        dicts journaled verbatim: a failover resubmission replays the
        identical decoding policy (position-keyed PRNG + grammar-cursor
        replay make the continuation stream-exact, not just
        distribution-exact).  ``tenant``/``adapter`` are journaled the
        same way: a failover lands on the survivor under the same
        tenant ledger/quota/namespace and adapter weights."""
        entry, created = self.journal.admit(
            prompt, max_new_tokens, eos_token_id=eos_token_id,
            on_token=on_token, deadline_s=deadline_s, rid=rid,
            sampling=sampling, seed=seed, grammar=grammar,
            tenant=tenant, adapter=adapter)
        if created:
            self.metrics.submitted += 1
        else:
            self.metrics.duplicate_rids += 1
        return entry

    def cancel(self, rid):
        """Cancel a journaled request.  Idempotent: cancelling a
        terminal (or unknown) rid is a no-op returning False."""
        entry = self.journal.entries.get(rid)
        if entry is None or entry.state in jn.TERMINAL:
            return False
        self.journal.mark_cancel(entry)
        if entry.state == jn.QUEUED:
            self._finalize(entry, jn.CANCELLED, "cancelled in queue")
        elif entry.state == jn.ROUTED and entry.handle is not None:
            entry.handle.cancel()
        # HANDOFF packets are cancelled at dispatch (pages freed there)
        return True

    # ------------------------------------------------------------- pump
    def step(self):
        """One router pump; returns True while any journaled work is
        live.  The ``cluster.router_kill`` fault point fires first — an
        armed raise here IS the router's death, propagating to the
        RouterSupervisor (or the caller) exactly as a process crash
        would: nothing after the raise runs, the WAL holds everything
        acknowledged so far."""
        self.step_idx += 1
        faults.fire("cluster.router_kill", step=self.step_idx)
        if self.lease is not None:
            # a renewal that fails (expired, or a newer epoch holds the
            # lease) means this router is deposed; keep pumping — every
            # write is fenced — but the supervisor will notice
            self.lease.renew(self.epoch)
        now = time.monotonic()
        self._check_replicas()
        self._dispatch_handoffs(now)
        self._advance_transfers(now)
        self._route(now)
        for rep in self.replicas:
            if rep.state == DEAD:
                continue
            try:
                rep.step(self.step_idx, epoch=self.epoch)
            except StaleEpoch:
                # WE are the zombie, not the replica: never a failover
                self.fenced_dispatches += 1
            except ReplicaKilled:
                self._on_death(rep)
            except Exception:   # an uncontained replica error is a death
                self._on_death(rep)
        self._collect(now)
        return self.journal.has_live() or bool(self._packets) \
            or bool(self._transfers)

    def run(self, max_steps=100000):
        """Pump until every journaled request is terminal; returns
        ``{rid: emitted tokens}`` for the FINISHED ones."""
        for _ in range(max_steps):
            if not self.step():
                break
            if not any(rep.state != DEAD and rep.has_work()
                       for rep in self.replicas) and not self._packets \
                    and not self._transfers:
                # nothing on any device: backoff gates are the only
                # clock left — don't spin the host
                time.sleep(0.002)
        return {e.rid: list(e.emitted)
                for e in self.journal.entries.values()
                if e.state == jn.FINISHED}

    # ------------------------------------------------------- heartbeats
    def _check_replicas(self):
        for rep in self.replicas:
            if rep.state == DEAD:
                if not getattr(rep, "_death_handled", False):
                    self._on_death(rep)
                continue
            try:
                rep.heartbeat(epoch=self.epoch)
                rep.missed_beats = 0
            except StaleEpoch:
                # a deposed router's heartbeat is not a replica problem:
                # counting it as a miss would let a zombie KILL a healthy
                # replica the new primary is serving through
                self.fenced_dispatches += 1
            except Exception:
                rep.missed_beats += 1
                self.metrics.heartbeat_misses += 1
                self.metrics.event(self.step_idx, "heartbeat_miss")
                if rep.state == DEAD or \
                        rep.missed_beats >= self.heartbeat_misses:
                    self._on_death(rep)

    def _on_death(self, rep):
        if getattr(rep, "_death_handled", False):
            return
        rep._death_handled = True
        rep.die(getattr(rep, "death_reason", None) or
                "missed heartbeats")
        self.metrics.failovers += 1
        self.metrics.event(self.step_idx, "failover")
        # abort in-flight chain transfers touching the dead replica
        # BEFORE replaying its stranded entries: device_put transfers
        # free partial pages on both pools and requeue unified (pool
        # objects outlive their scheduler — same contract the shared-
        # pool path relies on); wire relays into a dead decode worker
        # just stop (the entry is ROUTED there, so the stranded scan
        # below owns the token-exact requeue)
        for t in list(self._transfers):
            if isinstance(t, _WireRelay):
                # source death is harmless here — the frames are
                # already host-buffered; only the destination matters
                if t.dst_rep is rep:
                    self._transfers.remove(t)
                    self.metrics.record_handoff_abort(self.step_idx)
            elif t.pkt.src_rep is rep or t.dst_rep is rep:
                side = "source" if t.pkt.src_rep is rep \
                    else "destination"
                self._abort_transfer(
                    t, reason=f"{side} died mid-transfer")
        # incarnation-matched: entries routed to a LATER incarnation of
        # this id (revived replica, flap race) are NOT stranded — a
        # stale death signal must never re-adopt live work
        stranded = [e for e in self.journal.live()
                    if e.state == jn.ROUTED and e.replica == rep.id and
                    e.replica_inc == getattr(rep, "incarnation", 0)]
        if self.tracer is not None:
            self.tracer.instant(
                "replica_death", cat="failover", process=str(rep.id),
                args={"reason": getattr(rep, "death_reason", None),
                      "stranded": len(stranded)})
        if self.flight is not None:
            # the post-mortem bundle: the recent-span window from every
            # source, correlated with the journal entries that were in
            # flight on the dead replica (their snapshots carry the
            # replica chain the replay will extend)
            self.flight.dump(
                f"replica_death:{rep.id}",
                journal_entry=[e.snapshot() for e in stranded],
                extra={"death_reason": getattr(rep, "death_reason",
                                               None)})
        for entry in stranded:
            self._replay(entry, dead_replica=rep.id)

    def _replay(self, entry, dead_replica=None):
        """Zero-lost failover: requeue a dead replica's entry with its
        delivered tokens folded into the prompt.  If the emitted stream
        already satisfies the request, finalize instead (a death racing
        completion must not re-serve a finished stream)."""
        if entry.handle is not None:
            self._by_handle.pop(id(entry.handle), None)
            entry.handle = None
        entry.replica = None
        if entry.finished_by_emitted():
            self._finalize(entry, jn.FINISHED)
            return
        if entry.cancel_requested:
            # cancel raced the failover: the client asked out before the
            # death — resurrecting the request onto a survivor would
            # serve work nobody wants; terminal idempotently instead
            self._finalize(entry, jn.CANCELLED,
                           "cancelled during failover replay")
            return
        entry.replays += 1
        entry.next_try = 0.0
        self.journal.requeue(entry)
        self.metrics.replays += 1
        self.metrics.replayed_tokens += len(entry.emitted)
        self.metrics.event(self.step_idx, "replay")
        if self.tracer is not None:
            # open the explicit dead-replica -> survivor flow link; the
            # matching "f" event lands when _route places the replay
            entry.trace_flow = f"replay:{entry.rid}:{entry.replays}"
            self.tracer.flow(
                "s", entry.trace_flow, "failover_replay",
                rid=entry.rid,
                process=None if dead_replica is None
                else str(dead_replica),
                args={"replays": entry.replays,
                      "tokens_folded": len(entry.emitted)})

    # ---------------------------------------------------------- routing
    def _up(self, role=None):
        out = [r for r in self.replicas if r.state == UP]
        if role is not None:
            out = [r for r in out if r.role == role]
        return out

    def _candidates(self):
        """(candidate replicas, handoff?) under the degrade policy:
        prefill workers take fresh admissions only while a decode
        worker in the same group is up; otherwise everything routes
        unified (decode/unified replicas — or, last resort, a prefill
        worker serving unified)."""
        decode_up = {id(r.group) for r in self._up("decode")}
        prefill = [r for r in self._up("prefill")
                   if id(r.group) in decode_up]
        if prefill:
            return prefill, True
        unified = [r for r in self._up() if r.role != "prefill"]
        if unified:
            return unified, False
        return self._up(), False    # prefill workers serving unified

    def _pick(self, candidates, prompt):
        if self.routing == "prefix":
            scores = [r.prefix_match_len(prompt) for r in candidates]
            best = max(scores)
            pool = [r for r, s in zip(candidates, scores) if s == best]
        else:
            pool = candidates
        min_load = min(r.load() for r in pool)
        pool = [r for r in pool if r.load() == min_load]
        rep = pool[self._rr % len(pool)]
        self._rr += 1
        return rep

    def _backoff(self, entry, now, reason):
        entry.attempts += 1
        self.metrics.retries += 1
        self.metrics.event(self.step_idx, "retry")
        if entry.attempts > self.retry_max:
            self._finalize(entry, jn.SHED,
                           f"cluster capacity: {self.retry_max} "
                           f"admission retries exhausted ({reason})")
            return
        self.journal.requeue(entry)
        # exponential backoff with jitter: synchronized retry bursts
        # are how one full replica becomes every replica's problem
        delay = self.retry_backoff_s * (2 ** (entry.attempts - 1))
        entry.next_try = now + delay * (1.0 + self._rng.random())

    def _route(self, now):
        for entry in self.journal.live():
            if entry.state != jn.QUEUED or entry.next_try > now:
                continue
            if entry.cancel_requested:
                self._finalize(entry, jn.CANCELLED, "cancelled in queue")
                continue
            if entry.deadline_abs is not None and now > entry.deadline_abs:
                self._finalize(entry, jn.SHED, "deadline expired in "
                               "router queue")
                continue
            if entry.finished_by_emitted():
                self._finalize(entry, jn.FINISHED)
                continue
            candidates, handoff = self._candidates()
            if not candidates:
                continue   # whole fleet down/draining: wait for restart
            if self._has_prefill and not handoff:
                self.metrics.degraded_routes += 1
            prompt = entry.serve_prompt()
            rep = self._pick(candidates, prompt)
            deadline_s = None if entry.deadline_abs is None \
                else max(0.001, entry.deadline_abs - now)
            try:
                handle = rep.submit(
                    prompt, entry.remaining_new,
                    eos_token_id=entry.eos_token_id,
                    deadline_s=deadline_s,
                    on_token=self._make_token_sink(entry, rep),
                    handoff=handoff,
                    trace_ctx=None if self.tracer is None else
                    {"trace_id": entry.rid, "attempt": entry.replays},
                    # the folded prompt carries len(emitted) already-
                    # served positions: sample_offset re-anchors the
                    # position-keyed PRNG and tells the scheduler which
                    # prompt suffix to replay through the grammar cursor
                    sampling=entry.sampling, seed=entry.seed,
                    grammar=entry.grammar,
                    tenant=entry.tenant, adapter=entry.adapter,
                    sample_offset=len(entry.emitted), epoch=self.epoch)
            except StaleEpoch:
                # this router is deposed: the replica refused the
                # dispatch.  Leave the entry alone — the NEW primary's
                # journal owns it now; ours is a fenced shadow.
                self.fenced_dispatches += 1
                return
            except ReplicaKilled:
                continue    # heartbeat pass will handle the body
            except ValueError as e:
                # validation error (oversize prompt, config mismatch):
                # permanent — retrying elsewhere burns the backoff
                # budget to convert a client error into a misleading
                # "cluster capacity" shed. Fail it with the message.
                self._finalize(entry, jn.FAILED,
                               f"{type(e).__name__}: {e}")
                continue
            except Exception as e:   # QueueFull et al: backpressure
                self._backoff(entry, now, f"{type(e).__name__}")
                continue
            self.journal.dispatch(entry, rep.id,
                                  getattr(rep, "incarnation", 0))
            entry.handle = handle
            self._by_handle[id(handle)] = entry
            self.metrics.routed += 1
            if self.tracer is not None:
                if entry.trace_flow is not None:
                    # close the failover link on the survivor's track
                    self.tracer.flow("f", entry.trace_flow,
                                     "failover_replay", rid=entry.rid,
                                     process=str(rep.id))
                    entry.trace_flow = None
                self.tracer.instant(
                    "route", cat="routing", rid=entry.rid,
                    process=str(rep.id),
                    args={"replica": str(rep.id),
                          "attempt": entry.attempts,
                          "replays": entry.replays,
                          "handoff": handoff})

    def _make_token_sink(self, entry, rep):
        """Token path with two fences in front of the journal:

        * **ownership** — the sink is minted for (replica, incarnation)
          at dispatch time; once the entry is replayed elsewhere (or
          the replica restarts) the pair no longer matches and a late
          token from the old stream is dropped — a flapping replica
          cannot double-emit;
        * **epoch** — under HA, a sink minted by a deposed router drops
          tokens once the lease moved on (fast path; the WAL append
          inside ``journal.token`` is the authority and would fence it
          regardless).
        """
        journal, lease, epoch = self.journal, self.lease, self.epoch
        owner = (rep.id, getattr(rep, "incarnation", 0))

        def sink(_req, tok):
            if lease is not None and lease.current_epoch != epoch:
                self.fenced_tokens += 1
                return
            if (entry.replica, entry.replica_inc) != owner:
                self.stale_sink_tokens += 1
                return
            journal.token(entry, tok)
        return sink

    # ---------------------------------------------------------- handoff
    def _make_handoff_sink(self, rep):
        def sink(req, pages, length, first_tok):
            entry = self._by_handle.pop(id(req), None)
            if entry is None:   # not a routed request (defensive)
                rep.sched.kv.pool.free(pages)
                return
            entry.handle = None
            manifest = None
            if getattr(rep.group, "transport",
                       "shared_pool") == "device_put":
                # cross-pool packet: the manifest travels into the WAL
                # so a takeover knows exactly what was in flight.  The
                # digest is empty — this path never host-stages the
                # payload (only the wire path hashes bytes).
                sched = rep.sched
                manifest = tp.make_manifest(
                    len(pages),
                    len(pages) * sched.engine.kv_page_bytes(
                        sched.kv.page_size, sched.kv_dtype_name),
                    "", 0 if self.epoch is None else self.epoch)
            self.journal.handoff(entry, rep.group.name,
                                 list(req.orig_prompt), pages, length,
                                 first_tok, manifest=manifest,
                                 src=rep.id)
            self._packets.append(
                _Packet(entry, rep.group, list(req.orig_prompt), pages,
                        length, first_tok, rep.sched.kv.pool,
                        manifest=manifest, src_rep=rep))
        return sink

    def _make_wire_handoff_sink(self, rep):
        """Handoff sink for a prefill ``ProcessReplica``: the worker
        already exported the chain onto its KV sidecar fd (and freed
        its local pages) by the time the ``handoff`` event arrives —
        the router holds the frames and relays them to a decode
        worker's sidecar.  ``pages`` is empty by construction: the
        payload never exists as router-side pool pages."""
        def sink(handle, prompt, length, first_tok, manifest):
            entry = self._by_handle.pop(id(handle), None)
            if entry is None:   # not a routed request (defensive)
                rep.drop_wire_frames(handle.rid)
                return
            entry.handle = None
            self.journal.handoff(entry, rep.group.name, list(prompt),
                                 [], length, first_tok,
                                 manifest=manifest, src=rep.id)
            self._packets.append(
                _Packet(entry, rep.group, list(prompt), [], length,
                        first_tok, None, manifest=manifest, src_rep=rep,
                        wire_rid=handle.rid))
        return sink

    def _attach_packet(self, pkt, rep, now, pages):
        """Dispatch the decode-side attach for a packet whose chain
        (or chain transfer) is complete: ``pages`` are destination-pool
        page ids (the packet's own ids on the shared path, the freshly
        imported ids after a device_put transfer).  Returns the handle
        or raises (StaleEpoch propagates; the caller owns cleanup)."""
        entry = pkt.entry
        handle = rep.attach(
            pkt.prompt, pages, pkt.length,
            pkt.first_tok, max_new_tokens=entry.remaining_new + 1,
            eos_token_id=entry.eos_token_id,
            deadline_s=None if entry.deadline_abs is None
            else max(0.001, entry.deadline_abs - now),
            on_token=self._make_token_sink(entry, rep),
            trace_ctx=None if self.tracer is None else
            {"trace_id": entry.rid, "attempt": entry.replays},
            # the boundary token (already journal-emitted) rides
            # in out_tokens on the decode side, so the offset
            # excludes it: next position = offset + len(out) =
            # len(emitted) — the stream stays position-exact
            # across the handoff
            sampling=entry.sampling, seed=entry.seed,
            grammar=entry.grammar,
            tenant=entry.tenant, adapter=entry.adapter,
            sample_offset=max(0, len(entry.emitted) - 1),
            epoch=self.epoch)
        self.journal.dispatch(entry, rep.id,
                              getattr(rep, "incarnation", 0))
        entry.handle = handle
        self._by_handle[id(handle)] = entry
        self.metrics.handoffs += 1
        self.metrics.event(self.step_idx, "handoff")
        return handle

    def _dispatch_handoffs(self, now):
        """Attach pending KV packets to decode workers, per the
        group's transport path.  Every failure mode — injected
        ``cluster.handoff`` fault, no live decode worker, attach
        refusal, source death before the chain was relayable — frees
        the pages (on whichever pools hold them) and requeues the
        request for unified serving: a handoff can be retried or
        degraded, never lost."""
        if self.lease is not None and \
                self.lease.current_epoch != self.epoch:
            # deposed: the packets (and their POOL PAGES) belong to the
            # new primary's re-driven copies — freeing or attaching them
            # here would corrupt shared state the fence exists to protect
            self.fenced_dispatches += len(self._packets)
            self._packets.clear()
            self._transfers.clear()
            return
        for _ in range(len(self._packets)):
            pkt = self._packets.popleft()
            entry = pkt.entry
            transport = getattr(pkt.group, "transport", "shared_pool")
            if entry.cancel_requested:
                self._free_packet_source(pkt)
                self._finalize(entry, jn.CANCELLED,
                               "cancelled during handoff")
                continue
            if transport == "shared_pool":
                # zero-copy path: page ids change owners, the fault
                # point fires once per packet (there are no chunks)
                try:
                    faults.fire("cluster.handoff", step=self.step_idx,
                                rid=entry.rid)
                except Exception as e:
                    pkt.pool.free(pkt.pages)
                    self._requeue_unified(
                        entry, f"handoff fault: {type(e).__name__}")
                    continue
            rep = self._pick_decode_target(pkt)
            if rep is None:
                if self._up("decode"):
                    self._packets.append(pkt)   # backpressure: retry
                    continue
                self._free_packet_source(pkt)
                self._requeue_unified(entry, "no live decode worker")
                continue
            if transport == "shared_pool":
                try:
                    self._attach_packet(pkt, rep, now, pkt.pages)
                except StaleEpoch:
                    self.fenced_dispatches += 1
                    return         # deposed: pages belong to the heir
                except Exception:
                    pkt.pool.free(pkt.pages)
                    self._requeue_unified(entry, "attach failed")
                continue
            if transport == "wire":
                self._begin_wire_transfer(pkt, rep, now)
                continue
            # device_put: allocate the destination chain up front and
            # start the chunked transfer; the attach dispatches when
            # the last chunk lands (_advance_transfers)
            try:
                dst_pages = rep.sched.kv.pool.allocate(len(pkt.pages))
            except PagePoolExhausted:
                self._packets.append(pkt)       # backpressure: retry
                continue
            t = _Transfer(pkt, rep, dst_pages, now)
            self._transfers.append(t)
            if self.tracer is not None:
                # the s/f flow pair: arrow from the source process's
                # track to the destination's, one per transfer
                self.tracer.flow(
                    "s", t.flow, "handoff_transfer", rid=entry.rid,
                    process=str(pkt.src_rep.id),
                    args={"pages": len(pkt.pages),
                          "chunks": len(t.chunks),
                          "bytes": pkt.manifest["bytes"]
                          if pkt.manifest else None})

    def _pick_decode_target(self, pkt):
        """Least-loaded live decode worker in the packet's group with
        attach headroom (the soft admission gate: never park more
        chains at a worker than it has slots — parked chains hold pool
        pages)."""
        targets = [r for r in self._up("decode") if r.group is pkt.group
                   and r.attach_backlog() < r.attach_slots()]
        return min(targets, key=lambda r: r.load()) if targets else None

    def _free_packet_source(self, pkt):
        """Free whatever source-side pages a packet still holds.  Wire
        packets hold none (the worker freed its pages at export; the
        router only buffers host frames, dropped here)."""
        if pkt.pool is not None and pkt.pages:
            pkt.pool.free(pkt.pages)
        if pkt.wire_rid is not None and pkt.src_rep is not None:
            pkt.src_rep.drop_wire_frames(pkt.wire_rid)

    # -------------------------------------------------- chain transfers
    def _begin_wire_transfer(self, pkt, rep, now):
        """Start relaying a wire packet: dispatch the attach op to the
        decode worker (it allocates pages and scatters frames as they
        arrive), then stream the buffered frames over the pumps."""
        entry = pkt.entry
        if not pkt.src_rep.wire_frames_ready(pkt.wire_rid,
                                             pkt.manifest["chunks"]):
            if pkt.src_rep.state == DEAD:
                # source SIGKILLed mid-export: the chain can never
                # complete — drop the partial frames, requeue unified
                # (token-exact: emitted tokens fold into the prompt)
                pkt.src_rep.drop_wire_frames(pkt.wire_rid)
                self.metrics.record_handoff_abort(self.step_idx)
                self._requeue_unified(
                    entry, "prefill worker died mid-transfer")
                return
            self._packets.append(pkt)       # frames still arriving
            return
        frames = pkt.src_rep.take_wire_frames(pkt.wire_rid)
        try:
            handle = rep.begin_wire_attach(
                pkt.prompt, pkt.length, pkt.first_tok,
                manifest=pkt.manifest,
                max_new_tokens=entry.remaining_new + 1,
                eos_token_id=entry.eos_token_id,
                deadline_s=None if entry.deadline_abs is None
                else max(0.001, entry.deadline_abs - now),
                on_token=self._make_token_sink(entry, rep),
                trace_ctx=None if self.tracer is None else
                {"trace_id": entry.rid, "attempt": entry.replays},
                sampling=entry.sampling, seed=entry.seed,
                grammar=entry.grammar,
                tenant=entry.tenant, adapter=entry.adapter,
                sample_offset=max(0, len(entry.emitted) - 1),
                epoch=self.epoch)
        except StaleEpoch:
            self.fenced_dispatches += 1
            return
        except Exception:
            self.metrics.record_handoff_abort(self.step_idx)
            self._requeue_unified(entry, "wire attach refused")
            return
        self.journal.dispatch(entry, rep.id,
                              getattr(rep, "incarnation", 0))
        entry.handle = handle
        self._by_handle[id(handle)] = entry
        self.metrics.handoffs += 1
        self.metrics.event(self.step_idx, "handoff")
        relay = _WireRelay(pkt, rep, handle, frames, now)
        self._transfers.append(relay)
        if self.tracer is not None:
            self.tracer.flow(
                "s", relay.flow, "handoff_transfer", rid=entry.rid,
                process=str(pkt.src_rep.id),
                args={"chunks": pkt.manifest["chunks"],
                      "bytes": pkt.manifest["bytes"]})

    def _advance_transfers(self, now):
        """Move every in-flight chain transfer forward by up to
        ``transfer_chunks_per_step`` chunks.  The per-chunk
        ``cluster.handoff`` fault fires before each chunk moves;
        faults and deaths abort the transfer with partial pages freed
        on both sides and the request requeued unified."""
        for t in list(self._transfers):
            if isinstance(t, _WireRelay):
                self._advance_wire_relay(t)
                continue
            pkt = t.pkt
            entry = pkt.entry
            if entry.cancel_requested:
                self._abort_transfer(t, requeue=False)
                self._finalize(entry, jn.CANCELLED,
                               "cancelled during handoff transfer")
                continue
            if pkt.src_rep.state == DEAD or t.dst_rep.state == DEAD:
                side = "source" if pkt.src_rep.state == DEAD \
                    else "destination"
                self._abort_transfer(
                    t, reason=f"{side} died mid-transfer")
                continue
            aborted = False
            for _ in range(self.transfer_chunks_per_step):
                if t.done():
                    break
                try:
                    faults.fire("cluster.handoff", step=self.step_idx,
                                rid=entry.rid, chunk=t.seq)
                except Exception as e:
                    self._abort_transfer(
                        t, reason=f"handoff fault at chunk {t.seq}: "
                                  f"{type(e).__name__}")
                    aborted = True
                    break
                try:
                    t.advance_chunk()
                except Exception as e:
                    self._abort_transfer(
                        t, reason=f"transfer failed at chunk {t.seq}: "
                                  f"{type(e).__name__}")
                    aborted = True
                    break
            if aborted or not t.done():
                continue
            # chain complete: source pages release, destination adopts
            self._transfers.remove(t)
            if pkt.pool is not None:
                pkt.pool.free(pkt.pages)
            ms = (time.monotonic() - t.t0) * 1e3
            try:
                self._attach_packet(pkt, t.dst_rep, now, t.dst_pages)
            except StaleEpoch:
                self.fenced_dispatches += 1
                return
            except Exception:
                t.dst_pool.free(t.dst_pages)
                self.metrics.record_handoff_abort(self.step_idx)
                self._requeue_unified(entry, "attach failed after "
                                             "transfer")
                continue
            self._record_transfer(t, pkt, ms, "device_put")

    def _advance_wire_relay(self, relay):
        """Stream the next frames of a wire transfer into the decode
        worker's KV sidecar.  The worker scatters each chunk on
        arrival; its death mid-relay is a normal replica death (the
        entry is ROUTED there — the failover pass replays it unified,
        token-exact), so the relay just stops."""
        pkt = relay.pkt
        entry = pkt.entry
        if relay.dst_rep.state == DEAD or entry.handle is None:
            # destination died (failover owns the requeue) or the
            # entry moved on: stop relaying, count the abort
            self._transfers.remove(relay)
            self.metrics.record_handoff_abort(self.step_idx)
            return
        for _ in range(self.transfer_chunks_per_step):
            if relay.seq >= len(relay.frames):
                break
            try:
                faults.fire("cluster.handoff", step=self.step_idx,
                            rid=entry.rid, chunk=relay.seq)
            except Exception as e:
                # mid-relay fault: tear down the decode side (it frees
                # its partial pages) and requeue unified.  The entry is
                # ROUTED to the decode worker — pull it back first.
                self._transfers.remove(relay)
                relay.dst_rep.abort_wire_attach(relay.handle.rid)
                self._by_handle.pop(id(relay.handle), None)
                entry.handle = None
                entry.replica = None
                self.metrics.record_handoff_abort(self.step_idx)
                self._requeue_unified(
                    entry, f"handoff fault at chunk {relay.seq}: "
                           f"{type(e).__name__}")
                return
            try:
                relay.dst_rep.send_wire_chunk(relay.handle.rid,
                                              relay.frames[relay.seq])
            except Exception:
                # broken sidecar = dying worker: stop; the heartbeat
                # pass declares the death and replays the entry
                self._transfers.remove(relay)
                self.metrics.record_handoff_abort(self.step_idx)
                return
            relay.seq += 1
        if relay.seq >= len(relay.frames):
            self._transfers.remove(relay)
            ms = (time.monotonic() - relay.t0) * 1e3
            self._record_transfer(relay, pkt, ms, "wire")

    def _record_transfer(self, t, pkt, ms, path):
        nbytes = pkt.manifest["bytes"] if pkt.manifest else t.nbytes
        chunks = pkt.manifest["chunks"] if pkt.manifest \
            else len(t.chunks)
        self.metrics.record_handoff_transfer(self.step_idx, path,
                                             nbytes, chunks, ms)
        if self.tracer is not None:
            self.tracer.flow(
                "f", t.flow, "handoff_transfer", rid=pkt.entry.rid,
                process=str(t.dst_rep.id),
                args={"bytes": nbytes, "chunks": chunks,
                      "ms": round(ms, 3), "path": path})

    def _abort_transfer(self, t, reason=None, requeue=True):
        """Tear down a device_put transfer mid-chain: free the source
        pages (the source pool outlives its scheduler — same contract
        as the shared-pool path) and the destination's pre-allocated
        chain, requeue unified.  Token-exact either way: the journal
        folds emitted tokens into the replayed prompt."""
        if t in self._transfers:
            self._transfers.remove(t)
        pkt = t.pkt
        if pkt.pool is not None:
            pkt.pool.free(pkt.pages)
        t.dst_pool.free(t.dst_pages)
        self.metrics.record_handoff_abort(self.step_idx)
        if requeue:
            self._requeue_unified(pkt.entry,
                                  reason or "transfer aborted")

    def _requeue_unified(self, entry, reason):
        if entry.finished_by_emitted():
            self._finalize(entry, jn.FINISHED)
            return
        entry.next_try = 0.0
        # `reason` rides entry.error as a transient note (cleared on
        # finish) and lands in the WAL requeue record
        self.journal.requeue(entry, error=reason)
        self.metrics.event(self.step_idx, "handoff_degrade")

    # ---------------------------------------------------------- collect
    def _collect(self, now):
        for entry in list(self.journal.live()):
            if entry.state != jn.ROUTED or entry.handle is None:
                continue
            st = entry.handle.state
            if st in ("waiting", "prefill", "running"):
                continue
            if st == "handoff":
                continue   # the sink already owns this transition
            err = entry.handle.error
            self._by_handle.pop(id(entry.handle), None)
            entry.handle = None
            entry.replica = None
            if st == "finished":
                self._finalize(entry, jn.FINISHED)
            elif st == "cancelled":
                self._finalize(entry, jn.CANCELLED, err)
            elif st == "failed":
                self._finalize(entry, jn.FAILED, err)
            elif st == "shed":
                if err is not None and "deadline" in err:
                    self._finalize(entry, jn.SHED, err)
                else:
                    # capacity shed (pool dead-end, drain): another
                    # replica may well serve it — bounded retry
                    if entry.finished_by_emitted():
                        self._finalize(entry, jn.FINISHED)
                    else:
                        self._backoff(entry, now, f"replica shed: {err}")

    def _finalize(self, entry, state, error=None):
        if entry.handle is not None:
            self._by_handle.pop(id(entry.handle), None)
        if state == jn.FINISHED:
            entry.error = None   # transient retry notes don't survive
        self.journal.finalize(entry, state, error)
        self.metrics.record_terminal(self.step_idx, state)
        if self.tracer is not None:
            # the cluster-level per-request span: submit -> terminal,
            # spanning every replica that ever held the work
            self.tracer.complete(
                "cluster_request", entry.t_submit, time.monotonic(),
                cat="request", rid=entry.rid,
                args={"state": state, "replays": entry.replays,
                      "replicas": [str(r) for r in
                                   entry.replica_history],
                      "tokens": len(entry.emitted)})

    # ------------------------------------------------- drain + restart
    def drain_replica(self, rep, max_steps=100000):
        """Rolling-restart phase 1: stop routing to ``rep`` (drain
        mode), pump the whole tier until its in-flight work finishes.
        The fleet keeps serving throughout."""
        rep.begin_drain()
        for _ in range(max_steps):
            if rep.state == DEAD or rep.drained():
                break
            self.step()
        self.metrics.drains += 1
        self.metrics.event(self.step_idx, "drain")
        if self.tracer is not None:
            self.tracer.instant("drain_complete", cat="lifecycle",
                                process=str(rep.id))

    def rolling_restart(self, term_grace_s=None):
        """Restart every live replica in sequence: drain, restart
        (process replicas get SIGTERM with the grace budget, then
        SIGKILL), resume routing.  Zero requests fail by construction —
        drained replicas finish their work before going down."""
        grace = self.term_grace_s if term_grace_s is None \
            else float(term_grace_s)
        for rep in list(self.replicas):
            if rep.state == DEAD:
                continue
            self.drain_replica(rep)
            if rep.state == DEAD:
                continue   # died mid-drain: failover already replayed
            rep.restart(term_grace_s=grace)
            rep._death_handled = False
            self.metrics.restarts += 1
            self.metrics.event(self.step_idx, "restart")
            if self.tracer is not None:
                self.tracer.instant("restart", cat="lifecycle",
                                    process=str(rep.id))

    def restart_replica(self, rep, term_grace_s=None):
        """Post-death recovery: bring a dead replica back with a fresh
        scheduler/process and rejoin it to the routing pool.  Calling
        this on a replica that is NOT dead (operator restart, flap
        recovery) first replays its in-flight entries — the fresh
        scheduler won't know them, and stranding them in ROUTED would
        hang the journal forever."""
        if rep.state != DEAD:
            inc = getattr(rep, "incarnation", 0)
            for entry in [e for e in self.journal.live()
                          if e.state == jn.ROUTED and
                          e.replica == rep.id and e.replica_inc == inc]:
                self._replay(entry, dead_replica=rep.id)
        rep.restart(term_grace_s=self.term_grace_s if term_grace_s is None
                    else term_grace_s)
        rep._death_handled = False
        self.metrics.restarts += 1

    def drain_all(self, grace_s=None, shed_queued=True):
        """Shutdown drain (the ds_serve SIGTERM path, cluster flavor):
        shed what is still queued at the router, drain every replica
        within the grace budget, shed the remainder distinctly."""
        deadline = None if grace_s is None \
            else time.monotonic() + float(grace_s)
        if shed_queued:
            for entry in self.journal.live():
                if entry.state == jn.QUEUED:
                    self._finalize(entry, jn.SHED,
                                   "shutdown drain: still queued")
        for rep in self.replicas:
            if rep.state != DEAD:
                rep.begin_drain()
        while self.journal.has_live() or self._packets or self._transfers:
            if deadline is not None and time.monotonic() > deadline:
                break
            if not self.step():
                break
        for pkt in list(self._packets):
            self._free_packet_source(pkt)
            self._finalize(pkt.entry, jn.SHED,
                           "shutdown drain: grace budget exhausted")
        self._packets.clear()
        for t in list(self._transfers):
            if isinstance(t, _WireRelay):
                self._transfers.remove(t)
                self.metrics.record_handoff_abort(self.step_idx)
                # entry is ROUTED at the decode worker — the live-entry
                # sweep below sheds it
            else:
                self._abort_transfer(t, requeue=False)
                self._finalize(t.pkt.entry, jn.SHED,
                               "shutdown drain: grace budget exhausted")
        for entry in list(self.journal.live()):
            self._finalize(entry, jn.SHED,
                           "shutdown drain: grace budget exhausted")

    # ------------------------------------------------------------ trace
    def fleet_trace(self):
        """The merged fleet Chrome-trace JSON object: the router's own
        routing/failover spans plus every replica's — live schedulers,
        DEAD replicas (their tracer outlives the dropped scheduler), and
        worker processes (spans flushed over the JSONL protocol; what a
        SIGKILLed worker flushed before dying survives here)."""
        from deepspeed_tpu.serving.trace import merge_chrome
        lists = []
        if self.tracer is not None:
            lists.append(self.tracer.serialized())
        for rep in self.replicas:
            if getattr(rep, "tracer", None) is not None:
                lists.append(rep.tracer.serialized())
            if getattr(rep, "trace_events", None):
                lists.append(list(rep.trace_events))
        return merge_chrome(lists)

    def dump_trace(self, path):
        """Write :meth:`fleet_trace` as a Chrome-trace/Perfetto JSON
        file (open at https://ui.perfetto.dev).  Returns the path."""
        import os as _os
        d = _os.path.dirname(_os.path.abspath(path))
        _os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.fleet_trace(), f)
            f.write("\n")
        return path

    # ------------------------------------------------------------- audit
    def audit(self, raise_on_error=True):
        """Fleet-wide refcount invariant audit.  Unlike a scheduler's
        own ``audit()`` — which over a SHARED disaggregated pool can
        only check structure (its peers hold references it cannot
        see) — the router sees every sharer: it groups live schedulers
        by physical pool, adds its own in-flight handoff packets (the
        pages a chain holds between detach and adopt), and runs the
        EXACT census on each pool.  This is the machine check for the
        bug class PR-7's review caught by hand: a replica die/restart
        over a shared pool that leaks (or double-frees) pages."""
        from deepspeed_tpu.serving.mem_telemetry import audit_pool
        pools = {}

        def entry(pool):
            return pools.setdefault(
                id(pool), {"pool": pool, "managers": [], "caches": [],
                           "chains": []})

        for rep in self.replicas:
            sched = getattr(rep, "sched", None)
            if sched is None:
                continue          # DEAD local replica / process replica
            ent = entry(sched.kv.pool)
            ent["managers"].append(sched.kv)
            if sched.prefix_cache is not None:
                ent["caches"].append(sched.prefix_cache)
            ent["chains"].extend(r._attach[0]
                                 for r in sched._pending_attach)
            if sched._spec is not None and \
                    getattr(sched._spec, "kv", None) is not None:
                dent = entry(sched._spec.kv.pool)
                dent["managers"].append(sched._spec.kv)
        for pkt in self._packets:
            if pkt.pool is not None:     # wire packets hold no pages
                entry(pkt.pool)["chains"].append(pkt.pages)
        for t in self._transfers:
            # mid-transfer chains hold pages on BOTH pools: the source
            # chain until the last chunk lands, the pre-allocated
            # destination chain from dispatch onward
            if isinstance(t, _WireRelay):
                continue                 # both sides worker-internal
            entry(t.pkt.pool)["chains"].append(t.pkt.pages)
            entry(t.dst_pool)["chains"].append(t.dst_pages)
        reports = []
        for i, ent in enumerate(pools.values()):
            pool = ent.pop("pool")
            reports.append(audit_pool(pool, exact=True,
                                      label=f"fleet_pool{i}",
                                      raise_on_error=raise_on_error,
                                      **ent))
        return {"ok": all(r["ok"] for r in reports), "reports": reports}

    # ------------------------------------------------------- comm ledger
    def comm_ledger(self, refresh=False):
        """Fleet comm-ledger pass: run every live local replica's
        ``ServingScheduler.comm_ledger()`` (populating its ``comm_*``
        health fields and gauges) and return ``{replica_id: {label:
        ledger}}`` — the per-signature JSON artifact CI uploads.
        Process replicas contribute through their heartbeat health
        instead (their worker computes the ledger in-process)."""
        out = {}
        for rep in self.replicas:
            sched = getattr(rep, "sched", None)
            if sched is None or not getattr(sched, "comm_telemetry",
                                            False):
                continue
            out[rep.id] = sched.comm_ledger(refresh=refresh)
        return out

    # ------------------------------------------------------------ health
    def health(self):
        """Fleet snapshot: per-replica state + aggregate counters the
        CI failover job asserts on (and uploads)."""
        hits = lookups = reused = 0
        for rep in self.replicas:
            h, lo, tr = rep.prefix_stats()
            hits += h
            lookups += lo
            reused += tr
        # fleet memory aggregation.  Free pages are a POOL property, so
        # group by physical pool (a disaggregated group's sharers would
        # otherwise multiply-count the one pool they share); process
        # replicas have no local pool object and contribute their last
        # heartbeat figure (they never share a pool cross-process).
        # Pressure counters are per-scheduler detections and sum as-is.
        mem_free = mem_episodes = mem_events = 0
        comm_bytes = steady_recompiles = 0
        comm_known = False
        seen_pools = set()
        seen_watchdogs = set()
        for rep in self.replicas:
            lh = rep.last_health or {}
            mem_episodes += lh.get("mem_pressure_episodes") or 0
            mem_events += lh.get("mem_pressure_events") or 0
            # comm/compile aggregation: local replicas read live, dead/
            # process replicas contribute their last heartbeat figure
            # (the per-scheduler ledger is static analysis — it does
            # not go stale the way load figures do)
            sched_live = getattr(rep, "sched", None) \
                if rep.state != DEAD else None
            ch = sched_live.comm_health_fields() if sched_live is not None \
                and hasattr(sched_live, "comm_health_fields") else lh
            if ch.get("comm_bytes_per_step") is not None:
                comm_known = True
                comm_bytes += ch["comm_bytes_per_step"]
            # local replicas share the ENGINE-lifetime watchdog, so
            # recompile counts are deduped by watchdog identity (like
            # free pages by pool); process replicas are separate
            # processes and sum as-is
            wd = None if sched_live is None else \
                getattr(sched_live, "compile_watchdog", None)
            if wd is not None:
                if id(wd) not in seen_watchdogs:
                    seen_watchdogs.add(id(wd))
                    steady_recompiles += wd.steady_recompiles
            elif getattr(rep, "sched", None) is None:
                # true process replicas only: a DEAD local replica's
                # heartbeat snapshots the shared engine watchdog a
                # live sibling already contributed through
                steady_recompiles += ch.get("steady_recompiles") or 0
            if rep.state == DEAD:
                continue   # stale heartbeat, no live pool to report
            sched = getattr(rep, "sched", None)
            if sched is not None:
                if id(sched.kv.pool) not in seen_pools:
                    seen_pools.add(id(sched.kv.pool))
                    mem_free += sched.kv.pool.free_pages
            else:
                mem_free += lh.get("mem_free_pages") or 0
        return {
            "step": self.step_idx,
            "routing": self.routing,
            "replicas": {
                rep.id: {
                    "state": rep.state, "role": rep.role,
                    "group": None if rep.group is None else rep.group.name,
                    "restarts": rep.restarts,
                    "missed_beats": rep.missed_beats,
                    "death_reason": getattr(rep, "death_reason", None),
                    "load": rep.load() if rep.state != DEAD else None,
                } for rep in self.replicas},
            "prefill_workers_up": len(self._up("prefill")),
            "decode_workers_up": len(self._up("decode")),
            "unified_up": len([r for r in self._up()
                               if r.role == "unified"]),
            "disaggregated": self._has_prefill,
            "degraded": self._has_prefill and
            not self._candidates()[1],
            "queued": sum(1 for e in self.journal.live()
                          if e.state == jn.QUEUED),
            "live_requests": len(self.journal.live()),
            "packets_pending": len(self._packets),
            "transfers_inflight": len(self._transfers),
            "aggregate_prefix_hit_rate":
                round(hits / lookups, 4) if lookups else 0.0,
            "aggregate_tokens_reused": reused,
            "aggregate_mem_free_pages": mem_free,
            "aggregate_mem_pressure_events": mem_events,
            "aggregate_mem_pressure_episodes": mem_episodes,
            "aggregate_comm_bytes_per_step":
                comm_bytes if comm_known else None,
            "aggregate_steady_recompiles": steady_recompiles,
            "epoch": self.epoch,
            "fenced_dispatches": self.fenced_dispatches,
            "fenced_tokens": self.fenced_tokens,
            "stale_sink_tokens": self.stale_sink_tokens,
            "wal_records": self.journal.wal_records,
            "wal_position": None if self.journal.wal is None
            else self.journal.wal.position(),
            **self.metrics.summary(),
        }


# ----------------------------------------------------------- builders

def make_local_fleet(engine, n, *, id_prefix="replica", **sched_kw):
    """N unified in-process replicas over one engine (separate pools
    and schedulers, shared compiled primitives)."""
    def factory():
        return ServingScheduler(engine, **sched_kw)
    return [LocalReplica(f"{id_prefix}{i}", factory) for i in range(n)]


def make_disaggregated_group(engine, *, name="g0", num_prefill=1,
                             num_decode=1, num_pages=64, page_size=16,
                             kv_dtype=None, transport="shared_pool",
                             **sched_kw):
    """A prefill/decode worker group under the three-path transport
    dispatch rule (:func:`transport.choose_transport`):

    * ``transport="shared_pool"`` — separate schedulers (separate slot
      tables) over ONE shared page pool and ONE device-pools ref; a
      finished prompt's KV chain transfers by page id, zero copies.
      This is the fast path when prefill and decode share devices.
    * ``transport="device_put"`` — every worker gets its OWN pool and
      device-pools ref (same process, separate HBM budgets); chains
      move chunk-wise through ``engine.export_page_chain`` ->
      ``jax.device_put`` to the destination pool's NamedSharding ->
      ``engine.import_page_chain``, overlapped with both sides' decode.
    * for separate OS processes use
      :func:`make_process_disaggregated_group` (``transport="wire"``):
      chains move as length-prefixed binary frames over dedicated KV
      sidecar fds, relayed by the router — never on the JSONL control
      wire.

    ``kv_dtype`` overrides the engine's pool dtype (int8/fp8 quantized
    pages handoff like any others on every path — their scale pools
    ride the same page ids, and the chunk payloads carry the scale
    leaves so transferred pages land with their own scales)."""
    # a prefill/decode pair hands page chains over; a model that keeps
    # recurrent state per slot has nothing to hand its state over with
    getattr(engine, "refuse_slot_state", lambda feature: None)(
        "handoff")
    if transport not in ("shared_pool", "device_put"):
        raise ValueError(f"unknown in-process transport {transport!r}")
    reps = []
    if transport == "shared_pool":
        pool = PagePool(num_pages, page_size)
        pools_ref = _PoolsRef(engine.init_paged_cache(
            num_pages, page_size, kv_dtype=kv_dtype))
        group = DisaggGroup(name, pool, pools_ref)

        def factory():
            return ServingScheduler(engine, num_pages=num_pages,
                                    page_size=page_size,
                                    shared_pool=pool,
                                    pools_ref=pools_ref, **sched_kw)
        for i in range(num_prefill):
            reps.append(LocalReplica(f"{name}-prefill{i}", factory,
                                     role="prefill", group=group))
        for i in range(num_decode):
            reps.append(LocalReplica(f"{name}-decode{i}", factory,
                                     role="decode", group=group))
        return reps
    group = DisaggGroup(name, None, None, transport="device_put")
    roles = [("prefill", i) for i in range(num_prefill)] + \
            [("decode", i) for i in range(num_decode)]
    for role, i in roles:
        # per-replica pool + pools ref created OUTSIDE the factory
        # closure: a die/restart builds a fresh scheduler over the SAME
        # physical pool (mirroring how a real worker's HBM allocation
        # survives its serving loop), so in-flight transfer pages stay
        # freeable and the fleet audit's census holds across restarts
        pool = PagePool(num_pages, page_size)
        pools_ref = _PoolsRef(engine.init_paged_cache(
            num_pages, page_size, kv_dtype=kv_dtype))

        def factory(pool=pool, pools_ref=pools_ref):
            return ServingScheduler(engine, num_pages=num_pages,
                                    page_size=page_size,
                                    shared_pool=pool,
                                    pools_ref=pools_ref, **sched_kw)
        reps.append(LocalReplica(f"{name}-{role}{i}", factory,
                                 role=role, group=group))
    return reps


def make_process_disaggregated_group(*, name="w0", num_prefill=1,
                                     num_decode=1, model="gpt2-tiny",
                                     **proc_kw):
    """A prefill/decode worker group over SEPARATE OS processes
    (``transport="wire"``): each worker owns a private page pool in its
    own process; finished-prompt chains leave the prefill worker as
    length-prefixed binary frames on its KV sidecar fd, the router
    relays them (with the decode-side rid rewritten) into the decode
    worker's sidecar, and the decode worker scatters each chunk on
    arrival — attach happens only after the manifest verifies (chunk
    count, exact bytes, running digest).  ``proc_kw`` passes through to
    :class:`ProcessReplica` (num_pages, page_size, kv_dtype, ...)."""
    from deepspeed_tpu.serving.cluster.replica import ProcessReplica
    group = DisaggGroup(name, None, None, transport="wire")
    reps = []
    for i in range(num_prefill):
        reps.append(ProcessReplica(f"{name}-prefill{i}", model=model,
                                   role="prefill", group=group,
                                   **proc_kw))
    for i in range(num_decode):
        reps.append(ProcessReplica(f"{name}-decode{i}", model=model,
                                   role="decode", group=group,
                                   **proc_kw))
    return reps
