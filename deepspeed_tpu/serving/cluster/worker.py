"""Process-backed engine replica: one ServingScheduler in its own
process, driven over a JSONL stdin/stdout protocol.

stdin ops (one JSON object per line):
  {"op": "submit", "rid": ..., "prompt": [...], "max_new_tokens": N,
   "eos_token_id": E?, "deadline_s": D?,
   "sampling": {...}?, "seed": S?, "grammar": {...}?,
   "sample_offset": O?,           # decoding policy; omitted = greedy
   "handoff": true?,              # prefill role: export the chain at
                                  # prompt end instead of decoding
   "trace": {"trace_id": ...}?}   # cluster trace ctx rides the wire
  {"op": "attach", "rid": ..., "prompt": [...], "length": L,
   "first_tok": T, "manifest": {...}, ...}   # decode role: adopt a
                                  # relayed chain once its sidecar
                                  # frames verify against the manifest
  {"op": "attach_abort", "rid": ...}  # mid-transfer fault: free the
                                      # partial destination chain
  {"op": "cancel", "rid": ...}
  {"op": "fingerprint"}      # reply {"ev": "fp", ...} now (prefix
                             # digests also ride every heartbeat)
  {"op": "drain"}            # stop admitting, finish in-flight
  {"op": "trace"}            # enable span tracing at runtime
  {"op": "fence", "epoch": N}  # router-HA fence: reject ops carrying a
                               # lower epoch, cancel in-flight requests
                               # dispatched under one (their tokens
                               # belong to a deposed router)

KV page-chain payloads NEVER ride this JSONL wire: role workers get a
dedicated binary sidecar fd (``--kv-fd-out`` on prefill: exported
frames out; ``--kv-fd-in`` on decode: relayed frames in), carrying
length-prefixed ``transport.encode_frame`` frames.  Only the manifest
and the attach metadata travel on the control wire.

Ops may carry "epoch": N (router-HA).  A submit whose epoch is below
the worker's fence is REJECTED on the wire with a "fenced" done event
— the in-process check in ProcessReplica is the fast path, this is the
authority a reordering transport cannot bypass.

stdout events (one JSON object per line, flushed immediately — a token
the router never read is a token the router will replay, so buffering
here would manufacture duplicate work on a crash):
  {"ev": "ready"}                          # engine built, serving
  {"ev": "hb", "health": {...}}            # periodic health heartbeat
  {"ev": "tok", "rid": ..., "t": ...}      # one generated token
  {"ev": "done", "rid": ..., "status": ..., "tokens": [...],
   "error": ...?}
  {"ev": "handoff", "rid": ..., "prompt": [...], "length": L,
   "first_tok": T, "manifest": {...}}       # prefill role: the chain's
                                            # frames are on the sidecar
  {"ev": "attached", "rid": ...}            # decode role: manifest
                                            # verified, chain adopted
  {"ev": "fp", "page_size": P, "digests": [...], ...}  # prefix cache
                                            # fingerprint (also rides
                                            # heartbeats as hb["fp"])
  {"ev": "spans", "spans": [...]}          # --trace: serialized span
                                           # batch, flushed with each
                                           # heartbeat (epoch-µs ts, so
                                           # the router merges them onto
                                           # the fleet timeline)

SIGTERM is the elastic-agent preemption notice: the worker drains
in-flight requests within ``DS_PREEMPTION_GRACE_S`` (shedding the
still-queued remainder distinctly) and exits 0.  SIGKILL — the failure
the cluster tier exists to survive — is exactly what it looks like.
"""

import argparse
import json
import os
import queue
import signal
import sys
import threading
import time


def _emit(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _build_engine(model_name, dtype="float32"):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2, gpt2_small, gpt2_tiny
    from deepspeed_tpu.models.llama import Llama, llama_tiny

    models = {
        "gpt2-tiny": lambda: GPT2(gpt2_tiny()),
        "gpt2-small": lambda: GPT2(gpt2_small()),
        "llama-tiny": lambda: Llama(llama_tiny()),
    }
    engine = deepspeed_tpu.init_inference(
        models[model_name](), dtype=dtype, kv_cache_dtype=dtype,
        mesh={"data": 1, "model": 1})
    # seeded init: every worker of the same model config holds the SAME
    # params, so a failover replay onto a different worker continues
    # the greedy stream token-exact
    engine.init_params(seed=0)
    return engine


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="gpt2-tiny")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--num-slots", type=int, default=3)
    p.add_argument("--num-pages", type=int, default=32)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--max-pages-per-slot", type=int, default=8)
    p.add_argument("--prefill-chunk", type=int, default=8)
    p.add_argument("--kv-dtype", default=None,
                   help="paged-KV pool dtype (float32/bfloat16/int8/"
                        "fp8); int8/fp8 pools store quantized pages + "
                        "per-row f32 scale pools.  Default: the engine "
                        "dtype")
    p.add_argument("--prefix-cache", action="store_true")
    p.add_argument("--mem-telemetry", action="store_true",
                   help="page-state attribution + per-request "
                        "page-seconds + pressure forensics; the mem_* "
                        "health fields ride the heartbeat to the router")
    p.add_argument("--comm-telemetry", action="store_true",
                   help="HLO comm-ledger capture + recompile watchdog; "
                        "the comm_* health fields ride the heartbeat "
                        "to the router (the in-process ledger analysis "
                        "runs once, after warmup)")
    p.add_argument("--trace", action="store_true",
                   help="record serving spans and flush them over the "
                        "protocol with each heartbeat")
    p.add_argument("--trace-label", default=None,
                   help="process label for this worker's spans in the "
                        "merged fleet trace (the replica id)")
    p.add_argument("--role", default="unified",
                   choices=["unified", "prefill", "decode"],
                   help="disaggregated-tier role; prefill/decode "
                        "workers move KV chains over the sidecar fds")
    p.add_argument("--kv-fd-out", type=int, default=None,
                   help="prefill role: fd exported page-chain frames "
                        "are written to (binary, length-prefixed)")
    p.add_argument("--kv-fd-in", type=int, default=None,
                   help="decode role: fd relayed page-chain frames "
                        "arrive on (binary, length-prefixed)")
    p.add_argument("--tenants", default=None,
                   help="tenants.json path (TenantConfig.from_dict "
                        "schema) — turns the multi-tenant tier on; "
                        "submits then REQUIRE a tenant field")
    p.add_argument("--lora", default=None,
                   help="adapter roster 'name=path.npz,...' (or "
                        "name=random:<rank>[:<seed>] for synthetic "
                        "factors); requires --tenants")
    p.add_argument("--hb-interval-s", type=float, default=0.2)
    p.add_argument("--threefry-partitionable", action="store_true",
                   help="mirror the parent's jax_threefry_partitionable "
                        "setting: PRNG semantics feed init_params, and "
                        "a failover replay is only token-exact across "
                        "processes when every worker holds bitwise-"
                        "identical params")
    args = p.parse_args(argv)

    if args.threefry_partitionable:
        import jax
        jax.config.update("jax_threefry_partitionable", True)

    from deepspeed_tpu.serving.cluster import transport as tp
    from deepspeed_tpu.serving.scheduler import (TERMINAL,
                                                 ServingScheduler)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    engine = _build_engine(args.model, args.dtype)
    tenancy = None
    if args.tenants is not None or args.lora is not None:
        # same builder ds_serve uses: every worker of the fleet derives
        # the IDENTICAL registry (adapter ids, namespaces, weights)
        # from the same CLI strings, so failover replays land under
        # the same tenant/adapter on any survivor
        from deepspeed_tpu.serving.tenancy import build_tenancy
        tenancy = build_tenancy(engine.module.cfg, tenants=args.tenants,
                                lora=args.lora)
    sched = ServingScheduler(
        engine, num_slots=args.num_slots, num_pages=args.num_pages,
        page_size=args.page_size,
        max_pages_per_slot=args.max_pages_per_slot,
        prefill_chunk=args.prefill_chunk, prefix_cache=args.prefix_cache,
        kv_dtype=args.kv_dtype,
        mem_telemetry=args.mem_telemetry,
        comm_telemetry=args.comm_telemetry, tenancy=tenancy)

    fence = {"epoch": 0}   # highest router epoch seen on the wire

    # ---- KV sidecar: the binary fd pair page-chain payloads ride.
    # Prefill exports whole chains out; decode scatters relayed frames
    # in, chunk by chunk, overlapped with its own decode horizon.
    kv_out = None
    if args.role == "prefill" and args.kv_fd_out is not None:
        kv_out = os.fdopen(args.kv_fd_out, "wb")

        def on_handoff(req, pages, length, first_tok):
            """Export the finished prompt's chain: host-stage + frame
            every chunk onto the sidecar, then free the local pages —
            the source's HBM is reclaimed the moment the bytes leave
            (a destination death later still requeues unified token-
            exact off the journal, never off these pages)."""
            t0 = time.monotonic()
            frames, manifest = tp.export_chain_frames(
                engine, sched.pools, pages, req._wire_rid,
                epoch=fence["epoch"])
            for fr in frames:
                kv_out.write(fr)
            kv_out.flush()
            sched.kv.pool.free(pages)
            sched.metrics.record_handoff_transport(
                sched.step_idx, "out", manifest["bytes"],
                manifest["chunks"], (time.monotonic() - t0) * 1e3)
            _emit({"ev": "handoff", "rid": req._wire_rid,
                   "prompt": [int(t) for t in req.orig_prompt],
                   "length": int(length), "first_tok": int(first_tok),
                   "manifest": manifest})

        sched.on_handoff = on_handoff

    kv_frames = queue.Queue()
    if args.role == "decode" and args.kv_fd_in is not None:
        def _kv_reader():
            stream = os.fdopen(args.kv_fd_in, "rb")
            try:
                while True:
                    fr = tp.read_frame(stream)
                    if fr is None:
                        return          # router hung up the sidecar
                    kv_frames.put(fr)
            except Exception:
                pass

        threading.Thread(target=_kv_reader, daemon=True).start()

    # decode-side in-flight imports: wire rid -> {"imp": ChunkImporter,
    # "op": the attach op (metadata for the eventual attach_handoff),
    # "t0": arrival time}.  Frames racing ahead of their attach op on
    # the other pipe park in orphans until the op lands.
    imports = {}
    orphans = {}

    tracer = {"t": None}

    def enable_trace(label=None):
        if tracer["t"] is None:
            from deepspeed_tpu.serving.trace import SpanTracer
            tracer["t"] = SpanTracer(
                process=label or args.trace_label or
                f"worker-{os.getpid()}")
            sched.tracer = tracer["t"]
            if sched.mem.enabled:
                # the pool counter track rides the worker's span flushes
                sched.mem.bind(sched.metrics, tracer["t"])

    if args.trace:
        enable_trace()

    def flush_spans():
        t = tracer["t"]
        if t is not None and t.events:
            _emit({"ev": "spans", "spans": t.serialized(drain=True)})

    term = {"flag": False}
    signal.signal(signal.SIGTERM, lambda *a: term.update(flag=True))

    live = {}          # wire rid -> scheduler Request
    eof = False
    last_hb = 0.0
    _emit({"ev": "ready"})

    def on_token(req, tok):
        _emit({"ev": "tok", "rid": req._wire_rid, "t": int(tok)})

    def report(req):
        row = {"ev": "done", "rid": req._wire_rid, "status": req.state,
               "tokens": [int(t) for t in req.out_tokens]}
        if req.error is not None:
            row["error"] = req.error
        _emit(row)

    def shed(rid, error):
        _emit({"ev": "done", "rid": rid, "status": "shed",
               "tokens": [], "error": error})

    def finish_import(rid):
        """Last chunk landed: verify against the manifest, adopt the
        chain.  A verification miss (truncated relay, corrupt frame)
        frees the pages and sheds distinctly — the router requeues
        unified off the journal, never off a half-imported chain."""
        st = imports.pop(rid)
        orphans.pop(rid, None)
        imp, op = st["imp"], st["op"]
        if not imp.verify():
            imp.abort()
            shed(rid, "KV transfer verification failed: "
                      f"{imp.nbytes}B/{imp.seq} chunks vs manifest "
                      f"{imp.manifest['bytes']}B/"
                      f"{imp.manifest['chunks']}")
            return
        try:
            req = sched.attach_handoff(
                op["prompt"], imp.pages, op["length"], op["first_tok"],
                max_new_tokens=op.get("max_new_tokens", 32),
                eos_token_id=op.get("eos_token_id"),
                on_token=on_token, deadline_s=op.get("deadline_s"),
                trace_ctx=op.get("trace"),
                sampling=op.get("sampling"), seed=op.get("seed"),
                grammar=op.get("grammar"),
                sample_offset=op.get("sample_offset", 0),
                tenant=op.get("tenant"), adapter=op.get("adapter"))
        except Exception as e:
            sched.kv.pool.free(imp.pages)
            shed(rid, f"{type(e).__name__}: {e}")
            return
        req._wire_rid = rid
        req._fence_epoch = st["epoch"]
        live[rid] = req
        sched.metrics.record_handoff_transport(
            sched.step_idx, "in", imp.nbytes, imp.seq,
            (time.monotonic() - st["t0"]) * 1e3)
        _emit({"ev": "attached", "rid": rid})

    def feed_frame(st, rid, header, raw):
        imp = st["imp"]
        try:
            imp.feed(header, raw)
        except Exception as e:
            imports.pop(rid, None)
            orphans.pop(rid, None)
            imp.abort()
            shed(rid, f"{type(e).__name__}: {e}")
            return
        if imp.done:
            finish_import(rid)

    def pump_kv():
        """Scatter every sidecar frame that has landed.  Frames that
        raced ahead of their attach op (separate pipes, no cross-fd
        ordering) park in ``orphans`` until the op arrives."""
        while True:
            try:
                header, raw = kv_frames.get_nowait()
            except queue.Empty:
                return
            rid = header["rid"]
            st = imports.get(rid)
            if st is None:
                orphans.setdefault(rid, []).append((header, raw))
                continue
            feed_frame(st, rid, header, raw)

    # stdin rides a reader thread: select()-then-readline() on a
    # BUFFERED stream drops the tail of a multi-line burst (readline
    # pulls the whole kernel buffer into Python's, so select sees an
    # empty fd while ops sit unread) — a blocking reader thread has no
    # such window
    ops = queue.Queue()

    def _stdin_reader():
        for line in sys.stdin:
            ops.put(line)
        ops.put(None)           # EOF sentinel

    threading.Thread(target=_stdin_reader, daemon=True).start()

    def pump_stdin():
        nonlocal eof
        while not eof:
            try:
                line = ops.get_nowait()
            except queue.Empty:
                return
            if line is None:    # router hung up: drain and leave
                eof = True
                term["flag"] = True
                return
            line = line.strip()
            if not line:
                continue
            op = json.loads(line)
            kind = op.get("op")
            op_epoch = op.get("epoch")
            if op_epoch is not None and op_epoch > fence["epoch"]:
                fence["epoch"] = int(op_epoch)
                sched.ha_epoch = fence["epoch"]
            if kind == "submit":
                if op_epoch is not None and op_epoch < fence["epoch"]:
                    # stale-epoch dispatch: a deposed router's late op.
                    # Reject on the wire — never admitted, never echoed
                    sched.ha_fenced += 1
                    _emit({"ev": "done", "rid": op["rid"],
                           "status": "fenced", "tokens": [],
                           "error": f"epoch {op_epoch} < fence "
                                    f"{fence['epoch']}"})
                    continue
                try:
                    req = sched.submit(
                        op["prompt"], op.get("max_new_tokens", 32),
                        eos_token_id=op.get("eos_token_id"),
                        deadline_s=op.get("deadline_s"),
                        on_token=on_token,
                        handoff=bool(op.get("handoff")),
                        trace_ctx=op.get("trace"),
                        sampling=op.get("sampling"),
                        seed=op.get("seed"),
                        grammar=op.get("grammar"),
                        sample_offset=op.get("sample_offset", 0),
                        tenant=op.get("tenant"),
                        adapter=op.get("adapter"))
                except Exception as e:
                    shed(op["rid"], f"{type(e).__name__}: {e}")
                    continue
                req._wire_rid = op["rid"]
                req._fence_epoch = op_epoch
                if req.state in TERMINAL:   # max_new_tokens=0 parity
                    report(req)
                else:
                    live[op["rid"]] = req
            elif kind == "attach":
                if op_epoch is not None and op_epoch < fence["epoch"]:
                    sched.ha_fenced += 1
                    _emit({"ev": "done", "rid": op["rid"],
                           "status": "fenced", "tokens": [],
                           "error": f"epoch {op_epoch} < fence "
                                    f"{fence['epoch']}"})
                    continue
                try:
                    # allocates the whole destination chain up front;
                    # PagePoolExhausted sheds before any bytes scatter
                    imp = tp.ChunkImporter(engine, sched,
                                           op["manifest"])
                except Exception as e:
                    shed(op["rid"], f"{type(e).__name__}: {e}")
                    continue
                st = {"imp": imp, "op": op, "t0": time.monotonic(),
                      "epoch": op_epoch}
                imports[op["rid"]] = st
                for header, raw in orphans.pop(op["rid"], []):
                    feed_frame(st, op["rid"], header, raw)
                    if op["rid"] not in imports:
                        break     # fed to completion (or shed)
            elif kind == "attach_abort":
                rid = op.get("rid")
                orphans.pop(rid, None)
                st = imports.pop(rid, None)
                if st is not None:
                    st["imp"].abort()
            elif kind == "fingerprint":
                if sched.prefix_cache is not None:
                    _emit({"ev": "fp",
                           **sched.prefix_cache.fingerprint()})
            elif kind == "cancel":
                req = live.get(op.get("rid"))
                if req is not None:
                    req.cancel()
            elif kind == "fence":
                # cancel everything dispatched under an older epoch:
                # those tokens would be dropped by the new router's
                # journal anyway, so reclaim the slots/pages now
                for req in list(live.values()):
                    tag = getattr(req, "_fence_epoch", None)
                    if tag is None or tag < fence["epoch"]:
                        req.cancel()
                        sched.ha_fenced += 1
            elif kind == "drain":
                sched.begin_drain(shed_waiting=False)
            elif kind == "trace":
                enable_trace(op.get("label"))

    while True:
        pump_stdin()
        pump_kv()
        if term["flag"]:
            break
        work = sched.step() if (sched.requests or sched._inflight or
                                sched._pending_attach) else False
        for rid in [r for r, req in live.items()
                    if req.state in TERMINAL]:
            report(live.pop(rid))
        now = time.monotonic()
        if now - last_hb >= args.hb_interval_s:
            if sched.comm_telemetry and sched._comm_summary is None \
                    and sched.step_idx >= 2 and not sched.requests:
                # one-time static analysis (an XLA re-compile per
                # signature), gated on an IDLE heartbeat so no live
                # request's latency pays it; the comm_* fields ride
                # every subsequent heartbeat to the router
                sched.comm_ledger()
            flush_spans()
            hb = {"ev": "hb", "health": sched.health()}
            if sched.prefix_cache is not None:
                # the prefix fingerprint rides every heartbeat: the
                # router scores this worker for a prompt exactly like
                # an in-process replica, from digests instead of the
                # trie it cannot see
                hb["fp"] = sched.prefix_cache.fingerprint()
            _emit(hb)
            last_hb = now
        if not work:
            time.sleep(0.01)

    # SIGTERM drain: finish in-flight within the supervisor's grace
    # budget, shed the rest distinctly, report every outcome
    grace = float(os.environ.get("DS_PREEMPTION_GRACE_S", 10.0))
    sched.drain(grace_s=grace, shed_waiting=True)
    for rid in list(live):
        report(live.pop(rid))
    flush_spans()
    _emit({"ev": "hb", "health": sched.health()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
