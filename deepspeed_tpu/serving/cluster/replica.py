"""Engine replicas: the units the cluster router load-balances over.

Two backings share one interface (submit/attach/step/heartbeat/drain/
restart/prefix_match_len):

* :class:`LocalReplica` — a ``ServingScheduler`` in this process,
  stepped cooperatively by the router's pump.  Crashes are simulated
  through the ``cluster.replica_kill`` fault point: an armed injection
  raising at the replica's step entry drops the whole scheduler —
  in-flight requests, queue, prefix cache — exactly like a process
  death, and the shared page pool is made whole again (a real node
  death takes its HBM with it; the in-process model must not leak the
  pool it shares with survivors).
* :class:`ProcessReplica` — a child process running
  ``deepspeed_tpu.serving.cluster.worker`` over a JSONL stdin/stdout
  protocol.  Death is real (SIGKILL), detection is missed heartbeats
  or a reaped pid, and restart honors the elastic agent's
  SIGTERM-then-SIGKILL ``term_grace_s`` contract
  (``DS_PREEMPTION_GRACE_S`` rides the worker env so its drain sizes
  itself against the real budget).

A replica NEVER owns client-visible request state: the router's
journal does.  Replica handles expose ``.state``/``.error``/
``.cancel()`` and stream tokens through the router-supplied callback;
everything else is private.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from collections import deque

from deepspeed_tpu.resilience import faults

UP, DRAINING, DEAD = "up", "draining", "dead"


class ReplicaKilled(RuntimeError):
    """A replica crashed, was killed, or stopped heartbeating."""


class StaleEpoch(RuntimeError):
    """A replica-facing call carried an epoch older than the replica's
    fence — the CALLER is a deposed (zombie) router, not the replica.
    Raised instead of doing the work; the zombie must stop dispatching.
    Deliberately NOT a :class:`ReplicaKilled`: the replica is fine."""


def _fence_check(rep, epoch):
    """Shared epoch gate: ``None`` means the caller is not running
    under HA (legacy single-router path — no fencing).  A newer epoch
    advances the fence (the first dispatch from a new primary fences
    everything older); a stale one raises."""
    if epoch is None:
        return
    epoch = int(epoch)
    if epoch < rep.fence_epoch:
        rep.fenced_calls += 1
        sched = getattr(rep, "sched", None)
        if sched is not None:
            sched.ha_fenced += 1
        raise StaleEpoch(
            f"{rep.id}: epoch {epoch} < fence {rep.fence_epoch}")
    if epoch > rep.fence_epoch:
        rep.fence_epoch = epoch
        sched = getattr(rep, "sched", None)
        if sched is not None:
            sched.ha_epoch = epoch


class LocalReplica:
    """An in-process ServingScheduler behind the replica interface."""

    def __init__(self, replica_id, scheduler_factory, role="unified",
                 group=None):
        self.id = replica_id
        self.role = role                 # unified | prefill | decode
        self.group = group               # DisaggGroup for role workers
        self._factory = scheduler_factory
        self.sched = scheduler_factory()
        self.state = UP
        self.death_reason = None
        self.missed_beats = 0
        self.restarts = 0
        self.incarnation = 0       # bumped per restart: entries + token
                                   # sinks record (replica, incarnation)
                                   # so a flapping/revived replica can't
                                   # be double-adopted or double-emit
        self.fence_epoch = 0       # highest router epoch seen (HA)
        self.fenced_calls = 0      # stale-epoch calls rejected
        self.last_health = None
        self._handoff_sink = None
        # per-replica span tracer (serving/trace.py), owned by the
        # REPLICA not the scheduler: a crash drops the scheduler but the
        # dead replica's spans must survive into the merged fleet trace
        # and the flight-recorder dump
        self.tracer = None

    def enable_trace(self, tracer):
        """Router wiring: attach this replica's tracer (survives die/
        restart — fresh schedulers are re-pointed at it)."""
        self.tracer = tracer
        if self.sched is not None:
            self.sched.tracer = tracer
            if self.sched.mem.enabled:
                # memory telemetry rides the replica's tracer too (the
                # pool counter track lands in the fleet trace)
                self.sched.mem.bind(self.sched.metrics, tracer)

    def attach_mem_flight(self, flight):
        """Router wiring: a scheduler built with memory telemetry gets
        the fleet FlightRecorder, so a sustained-pressure episode on
        this replica dumps fleet-correlatable forensics.  Survives
        die/restart (fresh schedulers are re-wired)."""
        self._mem_flight = flight
        if self.sched is not None and self.sched.mem.enabled:
            self.sched.mem.flight = flight

    def attach_comm_flight(self, flight):
        """Router wiring, the compile twin of :meth:`attach_mem_flight`:
        a scheduler running the recompile watchdog dumps steady-state
        signature churn into the FLEET recorder.  The watchdog is
        engine-lifetime (schedulers reuse it), so the wiring survives
        die/restart; re-wired anyway for custom per-scheduler
        instances."""
        self._comm_flight = flight
        wd = None if self.sched is None else self.sched.compile_watchdog
        if wd is not None:
            wd.flight_recorder = flight

    # ------------------------------------------------------------ intake
    def submit(self, prompt, max_new_tokens, eos_token_id=None,
               deadline_s=None, on_token=None, handoff=False,
               trace_ctx=None, sampling=None, seed=None, grammar=None,
               sample_offset=0, tenant=None, adapter=None, epoch=None):
        _fence_check(self, epoch)
        if self.state != UP:
            raise ReplicaKilled(f"{self.id} is {self.state}")
        req = self.sched.submit(prompt, max_new_tokens,
                                eos_token_id=eos_token_id,
                                on_token=on_token, deadline_s=deadline_s,
                                handoff=handoff, trace_ctx=trace_ctx,
                                sampling=sampling, seed=seed,
                                grammar=grammar,
                                sample_offset=sample_offset,
                                tenant=tenant, adapter=adapter)
        req._fence_epoch = epoch
        return req

    def attach(self, prompt, pages, length, first_tok, *, max_new_tokens,
               eos_token_id=None, deadline_s=None, on_token=None,
               trace_ctx=None, sampling=None, seed=None, grammar=None,
               sample_offset=0, tenant=None, adapter=None, epoch=None):
        _fence_check(self, epoch)
        if self.state != UP:
            raise ReplicaKilled(f"{self.id} is {self.state}")
        req = self.sched.attach_handoff(
            prompt, pages, length, first_tok,
            max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
            on_token=on_token, deadline_s=deadline_s,
            trace_ctx=trace_ctx, sampling=sampling, seed=seed,
            grammar=grammar, sample_offset=sample_offset,
            tenant=tenant, adapter=adapter)
        req._fence_epoch = epoch
        return req

    def fence(self, epoch):
        """Takeover hygiene: raise the fence so stale-epoch calls are
        rejected, and cancel any in-flight request dispatched under an
        older epoch (its tokens belong to a deposed router's sinks,
        which drop them — cancelling reclaims the slots/pages)."""
        epoch = int(epoch)
        self.fence_epoch = max(self.fence_epoch, epoch)
        if self.sched is None:
            return
        self.sched.ha_epoch = self.fence_epoch
        for req in list(self.sched.requests.values()):
            tag = getattr(req, "_fence_epoch", None)
            if tag is None or tag < epoch:
                req.cancel()
                self.sched.ha_fenced += 1

    def set_handoff_sink(self, cb):
        """Router wiring for prefill workers: where finished-prompt KV
        chains go.  Survives :meth:`restart` (the fresh scheduler is
        rewired)."""
        self._handoff_sink = cb
        if self.sched is not None:
            self.sched.on_handoff = cb

    def prefix_match_len(self, tokens):
        """Prefix-aware routing score: how many leading tokens of the
        prompt this replica's radix cache could serve right now."""
        if self.state != UP or self.sched is None or \
                self.sched.prefix_cache is None or len(tokens) < 2:
            return 0
        return self.sched.prefix_cache.prefix_len(tokens,
                                                  limit=len(tokens) - 1)

    def prefix_stats(self):
        pc = None if self.sched is None else self.sched.prefix_cache
        if pc is None:
            return (0, 0, 0)
        return (pc.hits, pc.lookups, pc.tokens_reused)

    def load(self):
        """Routing tie-break: live work items on this replica."""
        if self.sched is None:
            return 0
        s = self.sched
        return (len(s.waiting) + len(s._pending_attach) +
                sum(r is not None for r in s.slot_req))

    def attach_backlog(self):
        """Chains parked at this replica awaiting a slot.  The router's
        soft admission gate (``attach_backlog() < attach_slots()``)
        never parks more chains than the replica has slots — parked
        chains hold pool pages."""
        return 0 if self.sched is None else \
            len(self.sched._pending_attach)

    def attach_slots(self):
        return 0 if self.sched is None else self.sched.num_slots

    # -------------------------------------------------------------- pump
    def has_work(self):
        if self.sched is None:
            return False
        s = self.sched
        return bool(s.waiting) or bool(s._inflight) or \
            bool(s._pf_flight) or bool(s._pending_attach) or \
            any(r is not None for r in s.slot_req)

    def step(self, step_idx, epoch=None):
        """One scheduler iteration.  The ``cluster.replica_kill`` fault
        point fires first — an armed raise here IS the crash: the
        scheduler is dropped wholesale and :class:`ReplicaKilled`
        surfaces to the router, which replays this replica's journal
        entries onto survivors.  An uncontained scheduler exception
        (shared-dispatch failure, per PR-2's containment policy the
        only kind that can escape) is treated identically: one replica
        dies, never the tier."""
        if self.state == DEAD:
            return False
        _fence_check(self, epoch)
        try:
            faults.fire("cluster.replica_kill", step=step_idx,
                        replica=self.id)
        except Exception as e:
            self.die(f"injected kill: {type(e).__name__}: {e}")
            raise ReplicaKilled(self.death_reason) from e
        if not self.has_work():
            return False
        try:
            return self.sched.step()
        except Exception as e:
            self.die(f"uncontained scheduler error: "
                     f"{type(e).__name__}: {e}")
            raise ReplicaKilled(self.death_reason) from e

    def heartbeat(self, epoch=None):
        """Health snapshot, or :class:`ReplicaKilled` — the router's
        death-detection signal."""
        _fence_check(self, epoch)
        if self.state == DEAD:
            raise ReplicaKilled(f"{self.id} dead: {self.death_reason}")
        self.last_health = self.sched.health()
        return self.last_health

    # ----------------------------------------------------- lifecycle
    @staticmethod
    def _reclaim(sched):
        """Return every pool page a discarded scheduler holds — live
        slots, parked handoff chains, AND its refcounted prefix
        cache.  Mandatory when the pool is shared (a disaggregated
        group's pool outlives its workers in-process, unlike the
        per-node HBM it models): pages an abandoned scheduler still
        references would never recycle and the group would march to
        exhaustion one restart at a time."""
        if sched is None:
            return
        try:
            sched._inflight.clear()
            sched._pf_flight.clear()
            for slot in range(sched.num_slots):
                if sched.kv.slot_page_count(slot):
                    sched.kv.release_slot(slot)
            while sched._pending_attach:
                req = sched._pending_attach.popleft()
                sched.kv.pool.free(req._attach[0])
            if sched.prefix_cache is not None:
                sched.prefix_cache.evict(sched.kv.pool.num_pages)
        except Exception:
            pass   # reclaim is best-effort; the router replays anyway

    def die(self, reason):
        """Crash semantics: all scheduler state is lost; its pool
        pages are reclaimed (see :meth:`_reclaim`).  The tracer is NOT
        scheduler state — the spans recorded up to the crash are
        exactly what the flight recorder exists to keep."""
        if self.state == DEAD:
            return
        self.state = DEAD
        self.death_reason = reason
        if self.tracer is not None:
            self.tracer.instant("replica_death", cat="failover",
                                args={"reason": str(reason)})
        sched, self.sched = self.sched, None
        self._reclaim(sched)

    def begin_drain(self):
        """Rolling-restart entry: refuse new work, keep serving what is
        already accepted (the router stops routing here too)."""
        if self.state == UP:
            self.state = DRAINING
            self.sched.begin_drain(shed_waiting=False)

    def drained(self):
        return not self.has_work()

    def restart(self, term_grace_s=None):
        """Fresh scheduler from the factory (post-drain rolling restart
        or post-death recovery).  ``term_grace_s`` is a no-op here —
        in-process there is nothing to SIGTERM — and honored by
        :class:`ProcessReplica`.  The outgoing scheduler's pages
        (notably its prefix cache — a drained replica holds nothing
        else) are reclaimed first, or a shared pool would leak them on
        every rolling restart."""
        self._reclaim(self.sched)
        self.sched = self._factory()
        if self._handoff_sink is not None:
            self.sched.on_handoff = self._handoff_sink
        if self.tracer is not None:
            self.sched.tracer = self.tracer
            if self.sched.mem.enabled:
                self.sched.mem.bind(self.sched.metrics, self.tracer)
        if getattr(self, "_mem_flight", None) is not None and \
                self.sched.mem.enabled:
            self.sched.mem.flight = self._mem_flight
        if getattr(self, "_comm_flight", None) is not None and \
                self.sched.compile_watchdog is not None:
            self.sched.compile_watchdog.flight_recorder = \
                self._comm_flight
        if self.fence_epoch:
            self.sched.ha_epoch = self.fence_epoch
        self.state = UP
        self.death_reason = None
        self.missed_beats = 0
        self.restarts += 1
        self.incarnation += 1


class _RemoteHandle:
    """Router-visible handle for a request living in a worker process:
    mirrors the scheduler Request surface the router consumes
    (``state`` / ``error`` / ``cancel()``)."""

    __slots__ = ("rid", "state", "error", "on_token", "_replica")

    def __init__(self, rid, on_token, replica):
        self.rid = rid
        self.state = "running"
        self.error = None
        self.on_token = on_token
        self._replica = replica

    def cancel(self):
        # a broken pipe means the worker is dying: swallow it — cancel
        # must stay idempotent/no-raise for callers (router.cancel),
        # and the heartbeat pass will declare the death and replay
        try:
            self._replica._send({"op": "cancel", "rid": self.rid})
        except Exception:
            pass


class ProcessReplica:
    """A worker process behind the replica interface (JSONL protocol —
    see ``cluster/worker.py``).

    Role workers carry real cross-process KV transport: a ``prefill``
    worker gets a dedicated binary KV sidecar fd (``--kv-fd-out``) its
    exported page-chain frames ride OUT on (length-prefixed, never the
    JSONL control wire; a reader thread buffers them here per worker
    rid), and a ``decode`` worker gets one (``--kv-fd-in``) the router
    relays those frames INTO — the worker scatters each chunk on
    arrival and attaches the request once the manifest verifies.
    Prefix routing for process replicas runs on shipped
    ``PrefixCache.fingerprint()`` digests (heartbeat cadence + the
    ``fingerprint`` op), matched router-side by
    :class:`~deepspeed_tpu.serving.prefix_cache.FingerprintMatcher` —
    the wire twin of ``prefix_len`` scoring."""

    def __init__(self, replica_id, *, model="gpt2-tiny", num_slots=3,
                 num_pages=32, page_size=16, max_pages_per_slot=8,
                 prefill_chunk=8, prefix_cache=False, term_grace_s=5.0,
                 hb_timeout_s=60.0, env=None, trace=False,
                 mem_telemetry=False, comm_telemetry=False,
                 kv_dtype=None, role="unified", group=None,
                 tenants=None, lora=None):
        self.id = replica_id
        self.role = role                 # unified | prefill | decode
        self.group = group               # DisaggGroup for role workers
        self.state = UP
        self.death_reason = None
        self.missed_beats = 0
        self.restarts = 0
        self.incarnation = 0
        self.fence_epoch = 0
        self.fenced_calls = 0
        self.last_health = None
        self.term_grace_s = float(term_grace_s)
        self.hb_timeout_s = float(hb_timeout_s)
        self._cfg = dict(model=model, num_slots=num_slots,
                         num_pages=num_pages, page_size=page_size,
                         max_pages_per_slot=max_pages_per_slot,
                         prefill_chunk=prefill_chunk,
                         prefix_cache=prefix_cache, trace=bool(trace),
                         mem_telemetry=bool(mem_telemetry),
                         comm_telemetry=bool(comm_telemetry),
                         kv_dtype=kv_dtype, tenants=tenants, lora=lora)
        self._env = dict(env or {})
        self._handles = {}
        self._next_rid = 0
        self._handoff_sink = None
        self._fp = None              # FingerprintMatcher, once shipped
        # worker-side spans, flushed over the JSONL protocol with each
        # heartbeat (already epoch-µs-serialized by the worker).  Kept
        # on the REPLICA so a SIGKILLed worker's last flushed window
        # survives into the merged fleet trace / flight record — spans
        # between the last flush and the kill die with the process,
        # exactly like the requests the journal replays.
        self.trace_events = deque(maxlen=8192)
        self._spawn()

    def enable_trace(self, tracer=None):
        """Turn on worker-side span tracing (now, and across restarts).
        The optional ``tracer`` argument is accepted for interface
        parity with LocalReplica and ignored — a process replica's
        spans are recorded in the worker and shipped back serialized."""
        if self._cfg["trace"]:
            return
        self._cfg["trace"] = True
        try:
            self._send({"op": "trace", "label": str(self.id)})
        except Exception:
            pass   # dying worker: the restart respawns with --trace

    # --------------------------------------------------------- process
    def _spawn(self):
        cfg = self._cfg
        cmd = [sys.executable, "-m", "deepspeed_tpu.serving.cluster.worker",
               "--model", cfg["model"],
               "--num-slots", str(cfg["num_slots"]),
               "--num-pages", str(cfg["num_pages"]),
               "--page-size", str(cfg["page_size"]),
               "--max-pages-per-slot", str(cfg["max_pages_per_slot"]),
               "--prefill-chunk", str(cfg["prefill_chunk"])]
        if cfg.get("kv_dtype"):
            # quantized (or explicitly float) paged-KV pools survive a
            # worker restart: the dtype is part of the replica config
            cmd += ["--kv-dtype", str(cfg["kv_dtype"])]
        if cfg["prefix_cache"]:
            cmd.append("--prefix-cache")
        if cfg["mem_telemetry"]:
            cmd.append("--mem-telemetry")
        if cfg.get("comm_telemetry"):
            cmd.append("--comm-telemetry")
        if cfg.get("tenants"):
            # tenancy survives restarts: the respawned worker rebuilds
            # the identical registry (same adapter ids/namespaces)
            cmd += ["--tenants", str(cfg["tenants"])]
        if cfg.get("lora"):
            cmd += ["--lora", str(cfg["lora"])]
        if cfg["trace"]:
            cmd += ["--trace", "--trace-label", str(self.id)]
        # KV sidecar plumbing for role workers: a dedicated binary fd
        # pair per direction, separate from the JSONL control pipes —
        # page-chain payloads never ride (or block) the control wire
        self._wire_frames = {}       # worker rid -> [(header, raw)...]
        self._wire_lock = threading.Lock()
        self._wire_pending = set()   # wire-attach rids not yet adopted
        self._kv_w = None            # decode: parent -> worker frames
        self._kv_r = None            # prefill: worker -> parent frames
        pass_fds, child_fds = (), []
        if self.role == "prefill":
            r_fd, w_fd = os.pipe()
            cmd += ["--role", "prefill", "--kv-fd-out", str(w_fd)]
            pass_fds, child_fds = (w_fd,), [w_fd]
            self._kv_r = os.fdopen(r_fd, "rb")
        elif self.role == "decode":
            r_fd, w_fd = os.pipe()
            cmd += ["--role", "decode", "--kv-fd-in", str(r_fd)]
            pass_fds, child_fds = (r_fd,), [r_fd]
            self._kv_w = os.fdopen(w_fd, "wb")
        try:
            # forward PRNG semantics: seeded init only yields the SAME
            # params in the child when threefry partitioning matches
            import jax
            if jax.config.jax_threefry_partitionable:
                cmd.append("--threefry-partitionable")
        except Exception:
            pass
        # the child inherits the parent's platform selection: a worker
        # on a chip machine serves from the chip (and needs one of its
        # own — a parent that has touched JAX holds the one it took)
        env = os.environ.copy()
        # the child must import THIS deepspeed_tpu however the parent
        # got it (site-packages, cwd, or an explicit sys.path entry —
        # the env of a driver script run from anywhere): the package's
        # import root rides PYTHONPATH, it is not inherited through -m
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        env["PYTHONPATH"] = pkg_root + (
            os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH") else "")
        # the elastic-agent grace contract: the worker's SIGTERM drain
        # sizes itself against the budget the supervisor will enforce
        env["DS_PREEMPTION_GRACE_S"] = str(self.term_grace_s)
        env.update(self._env)
        self._proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env=env, pass_fds=pass_fds)   # stderr: inherited
        for fd in child_fds:
            os.close(fd)    # the child owns its end now
        self._events = deque()
        self._events_lock = threading.Lock()
        self._reader = threading.Thread(target=self._read_loop,
                                        daemon=True)
        self._reader.start()
        if self._kv_r is not None:
            self._kv_reader = threading.Thread(target=self._kv_read_loop,
                                               daemon=True)
            self._kv_reader.start()
        self._last_hb = time.monotonic()
        self._ready = False

    def _kv_read_loop(self):
        """Prefill sidecar reader: buffer exported chain frames per
        worker rid until the router relays (or drops) them.  Frames
        are decoded once here — the relay rewrites only the rid."""
        from deepspeed_tpu.serving.cluster import transport as tp
        stream = self._kv_r
        try:
            while True:
                frame = tp.read_frame(stream)
                if frame is None:
                    return           # EOF: worker died or sidecar closed
                header, raw = frame
                with self._wire_lock:
                    self._wire_frames.setdefault(
                        header["rid"], []).append((header, raw))
        except Exception:
            pass

    def _read_loop(self):
        proc = self._proc
        try:
            for line in proc.stdout:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                with self._events_lock:
                    self._events.append(ev)
        except Exception:
            pass

    def _send(self, op):
        try:
            self._proc.stdin.write(json.dumps(op) + "\n")
            self._proc.stdin.flush()
        except Exception as e:
            raise ReplicaKilled(f"{self.id} pipe broken: {e}") from e

    def wait_ready(self, timeout_s=300.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            self._pump_events()
            if self._ready:
                return True
            if self._proc.poll() is not None:
                raise ReplicaKilled(
                    f"{self.id} exited rc={self._proc.returncode} "
                    "before ready")
            time.sleep(0.05)
        raise TimeoutError(f"{self.id} not ready in {timeout_s}s")

    def _pump_events(self):
        while True:
            with self._events_lock:
                if not self._events:
                    return
                ev = self._events.popleft()
            kind = ev.get("ev")
            if kind == "ready":
                self._ready = True
                self._last_hb = time.monotonic()
            elif kind == "hb":
                self._last_hb = time.monotonic()
                self.last_health = ev.get("health")
                if ev.get("fp") is not None:
                    self._absorb_fp(ev["fp"])
            elif kind == "fp":
                self._absorb_fp(ev)
            elif kind == "handoff":
                # prefill worker finished a handoff prompt: its frames
                # are on (or arriving over) the KV sidecar; hand the
                # metadata to the router's wire sink
                rid = ev.get("rid")
                h = self._handles.pop(rid, None)
                if h is None or self._handoff_sink is None:
                    self.drop_wire_frames(rid)
                elif h.state in ("waiting", "prefill", "running"):
                    h.state = "handoff"
                    self._handoff_sink(
                        h, [int(t) for t in ev["prompt"]],
                        int(ev["length"]), int(ev["first_tok"]),
                        ev["manifest"])
            elif kind == "attached":
                # decode worker verified the manifest and adopted the
                # chain: the wire attach left the pending (backlog) set
                self._wire_pending.discard(ev.get("rid"))
            elif kind == "tok":
                h = self._handles.get(ev.get("rid"))
                if h is not None and h.on_token is not None:
                    h.on_token(h, int(ev["t"]))
            elif kind == "done":
                rid = ev.get("rid")
                self._wire_pending.discard(rid)
                h = self._handles.pop(rid, None)
                if h is not None:
                    h.state = ev.get("status", "finished")
                    h.error = ev.get("error")
            elif kind == "spans":
                self.trace_events.extend(ev.get("spans") or [])

    def _absorb_fp(self, fp):
        from deepspeed_tpu.serving.prefix_cache import FingerprintMatcher
        if self._fp is None:
            self._fp = FingerprintMatcher()
        self._fp.update(fp)

    # ------------------------------------------------------------ intake
    def submit(self, prompt, max_new_tokens, eos_token_id=None,
               deadline_s=None, on_token=None, handoff=False,
               trace_ctx=None, sampling=None, seed=None, grammar=None,
               sample_offset=0, tenant=None, adapter=None, epoch=None):
        if handoff and self.role != "prefill":
            raise ValueError(
                "handoff submits require a prefill-role worker "
                "(its KV sidecar is the chain's way out)")
        _fence_check(self, epoch)
        if self.state != UP:
            raise ReplicaKilled(f"{self.id} is {self.state}")
        rid = f"w{self._next_rid}"
        self._next_rid += 1
        handle = _RemoteHandle(rid, on_token, self)
        self._handles[rid] = handle
        op = {"op": "submit", "rid": rid,
              "prompt": [int(t) for t in prompt],
              "max_new_tokens": int(max_new_tokens),
              "eos_token_id": eos_token_id,
              "deadline_s": deadline_s}
        if handoff:
            op["handoff"] = True
        # decoding-policy wire fields are omitted when default so old
        # workers keep accepting the protocol
        if sampling:
            op["sampling"] = dict(sampling)
        if seed:
            op["seed"] = int(seed)
        if grammar:
            op["grammar"] = dict(grammar)
        if sample_offset:
            op["sample_offset"] = int(sample_offset)
        # tenancy fields are omitted when absent for the same
        # wire-compat reason
        if tenant is not None:
            op["tenant"] = str(tenant)
        if adapter is not None:
            op["adapter"] = str(adapter)
        if epoch is not None:
            # the epoch rides the wire too: even if a zombie router
            # slips past the in-process fence (it cannot here, but a
            # network transport could reorder), the WORKER rejects the
            # stale dispatch — defense in depth at the protocol layer
            op["epoch"] = int(epoch)
        if trace_ctx is not None:
            # the trace id crosses the process boundary with the
            # request, so worker-side spans carry the journal rid
            op["trace"] = trace_ctx
        self._send(op)
        return handle

    def set_handoff_sink(self, cb):
        """Router wiring for prefill workers: where finished-prompt
        handoff metadata goes (the frames ride the KV sidecar)."""
        self._handoff_sink = cb

    def prefix_match_len(self, tokens):
        """Prefix-aware routing score from the worker's last shipped
        fingerprint: the wire twin of ``prefix_len`` (page-granular by
        construction — a digest set can't represent the in-process
        copy-on-write partial, and routing doesn't need it)."""
        if self.state != UP or self._fp is None or len(tokens) < 2:
            return 0
        return self._fp.match_len(tokens, limit=len(tokens) - 1)

    def prefix_stats(self):
        if self._fp is None:
            return (0, 0, 0)
        return (self._fp.hits, self._fp.lookups, self._fp.tokens_reused)

    def request_fingerprint(self):
        """Ask the worker for a fresh prefix fingerprint now (it also
        rides every heartbeat); the reply lands via ``_pump_events``."""
        try:
            self._send({"op": "fingerprint"})
        except Exception:
            pass   # dying worker: heartbeats will declare the death

    def load(self):
        return len(self._handles)

    def attach_backlog(self):
        """Wire attaches dispatched but not yet adopted worker-side —
        each holds a freshly allocated destination chain, so the
        router's admission gate bounds them by slot count exactly like
        an in-process replica's parked chains."""
        return len(self._wire_pending)

    def attach_slots(self):
        return int(self._cfg["num_slots"])

    # ------------------------------------------------------ KV sidecar
    def wire_frames_ready(self, rid, total):
        """True once every frame of a chain export is host-buffered."""
        with self._wire_lock:
            return len(self._wire_frames.get(rid, ())) >= int(total)

    def take_wire_frames(self, rid):
        with self._wire_lock:
            return self._wire_frames.pop(rid, [])

    def drop_wire_frames(self, rid):
        with self._wire_lock:
            self._wire_frames.pop(rid, None)

    def begin_wire_attach(self, prompt, length, first_tok, *, manifest,
                          max_new_tokens, eos_token_id=None,
                          deadline_s=None, on_token=None, trace_ctx=None,
                          sampling=None, seed=None, grammar=None,
                          sample_offset=0, tenant=None, adapter=None,
                          epoch=None):
        """Dispatch the decode side of a cross-process handoff: the
        worker allocates the destination chain, scatters relayed
        frames as they land, and adopts the request once the manifest
        verifies (chunk count, exact bytes, running digest).  Frames
        follow via :meth:`send_wire_chunk`."""
        _fence_check(self, epoch)
        if self.state != UP:
            raise ReplicaKilled(f"{self.id} is {self.state}")
        if self._kv_w is None:
            raise ReplicaKilled(f"{self.id} has no KV sidecar "
                                "(not a decode-role worker)")
        rid = f"w{self._next_rid}"
        self._next_rid += 1
        handle = _RemoteHandle(rid, on_token, self)
        self._handles[rid] = handle
        self._wire_pending.add(rid)
        op = {"op": "attach", "rid": rid,
              "prompt": [int(t) for t in prompt],
              "length": int(length), "first_tok": int(first_tok),
              "manifest": dict(manifest),
              "max_new_tokens": int(max_new_tokens),
              "eos_token_id": eos_token_id,
              "deadline_s": deadline_s}
        if sampling:
            op["sampling"] = dict(sampling)
        if seed:
            op["seed"] = int(seed)
        if grammar:
            op["grammar"] = dict(grammar)
        if sample_offset:
            op["sample_offset"] = int(sample_offset)
        if tenant is not None:
            op["tenant"] = str(tenant)
        if adapter is not None:
            op["adapter"] = str(adapter)
        if epoch is not None:
            op["epoch"] = int(epoch)
        if trace_ctx is not None:
            op["trace"] = trace_ctx
        try:
            self._send(op)
        except ReplicaKilled:
            self._wire_pending.discard(rid)
            self._handles.pop(rid, None)
            raise
        return handle

    def send_wire_chunk(self, rid, frame):
        """Relay one buffered frame into the decode worker's sidecar,
        rewriting the source worker's rid to the decode-side one."""
        from deepspeed_tpu.serving.cluster import transport as tp
        header, raw = frame
        hdr = dict(header)
        hdr["rid"] = rid
        hb = json.dumps(hdr, separators=(",", ":")).encode()
        buf = tp._MAGIC + tp._HDR.pack(len(hb), len(raw)) + hb + raw
        try:
            self._kv_w.write(buf)
            self._kv_w.flush()
        except Exception as e:
            raise ReplicaKilled(
                f"{self.id} KV sidecar broken: {e}") from e

    def abort_wire_attach(self, rid):
        """Tear down a dispatched wire attach (mid-transfer fault):
        the worker frees the partial destination chain.  No-raise —
        a dead worker's pages died with its pool."""
        self._wire_pending.discard(rid)
        self._handles.pop(rid, None)
        try:
            self._send({"op": "attach_abort", "rid": rid})
        except Exception:
            pass

    # -------------------------------------------------------------- pump
    def has_work(self):
        """Always False: the actual work runs in the child process, so
        the router's pump has nothing to drive here and may idle-sleep
        between event polls instead of busy-spinning CPU away from the
        worker."""
        return False

    def fence(self, epoch):
        """Raise the local fence AND ship it to the worker, which
        cancels in-flight requests dispatched under older epochs."""
        epoch = int(epoch)
        self.fence_epoch = max(self.fence_epoch, epoch)
        try:
            self._send({"op": "fence", "epoch": self.fence_epoch})
        except Exception:
            pass   # dying worker: heartbeats will declare the death

    def step(self, step_idx, epoch=None):
        if self.state == DEAD:
            return False
        _fence_check(self, epoch)
        try:
            faults.fire("cluster.replica_kill", step=step_idx,
                        replica=self.id)
        except Exception as e:
            self.kill()
            self.die(f"injected kill: {type(e).__name__}: {e}")
            raise ReplicaKilled(self.death_reason) from e
        self._pump_events()
        return bool(self._handles)

    def heartbeat(self, epoch=None):
        _fence_check(self, epoch)
        if self.state == DEAD:
            raise ReplicaKilled(f"{self.id} dead: {self.death_reason}")
        self._pump_events()
        if self._proc.poll() is not None:
            raise ReplicaKilled(
                f"{self.id} exited rc={self._proc.returncode}")
        if time.monotonic() - self._last_hb > self.hb_timeout_s:
            raise ReplicaKilled(
                f"{self.id} silent for > {self.hb_timeout_s}s")
        return self.last_health

    # ----------------------------------------------------- lifecycle
    def kill(self):
        """The real thing: SIGKILL, no goodbye."""
        try:
            if self._proc.poll() is None:
                os.kill(self._proc.pid, signal.SIGKILL)
        except OSError:
            pass

    def _close_kv(self):
        """Close this incarnation's sidecar ends (buffered frames for
        unfinished exports die with them — the journal replays)."""
        for stream in (self._kv_w, self._kv_r):
            if stream is not None:
                try:
                    stream.close()
                except Exception:
                    pass
        self._kv_w = self._kv_r = None
        with self._wire_lock:
            self._wire_frames.clear()
        self._wire_pending.clear()

    def die(self, reason):
        if self.state == DEAD:
            return
        self.state = DEAD
        self.death_reason = reason
        self.kill()
        self._handles.clear()
        self._close_kv()

    def begin_drain(self):
        if self.state != UP:
            return
        self.state = DRAINING
        try:
            self._send({"op": "drain"})
        except Exception:
            # dead pipe: the drain is moot — heartbeats will declare
            # the death; drain_all/rolling_restart must keep going for
            # the surviving replicas instead of aborting mid-shutdown
            pass

    def drained(self):
        self._pump_events()
        return not self._handles

    def restart(self, term_grace_s=None):
        """Elastic-agent restart contract: SIGTERM first (the worker
        drains within ``DS_PREEMPTION_GRACE_S``), SIGKILL only after
        the grace budget, then respawn."""
        grace = self.term_grace_s if term_grace_s is None \
            else float(term_grace_s)
        if self._proc.poll() is None:
            self._proc.terminate()
            deadline = time.monotonic() + grace
            while self._proc.poll() is None and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            if self._proc.poll() is None:
                self._proc.kill()
        try:
            self._proc.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            pass
        self._handles.clear()
        self._close_kv()
        self._spawn()
        self.wait_ready()
        if self.fence_epoch:
            self.fence(self.fence_epoch)
        self.state = UP
        self.death_reason = None
        self.missed_beats = 0
        self.restarts += 1
        self.incarnation += 1
