"""Kind "serve": one engine, one ``ServingScheduler``, one traffic mix.

The engine is built the way ``bin/ds_serve:build_engine`` builds it
(``deepspeed_tpu.init_inference`` with ``paged_kernel`` at the CLI's
default), the scheduler exactly as ``serve_single`` builds it from the
CLI's parsed arguments — so every default of the program (prefill
chunk, decode horizon, overlap, prefix cache, page size) is measured,
not bypassed.  A cell sets only the sizes its model and traffic force.
The load is offered by this one thread between scheduler steps.
"""

import gc
import importlib
import importlib.machinery
import importlib.util
import os
import threading
import time

import numpy as np

import loadgen

# a served token must score within EPS_ULPS bf16 ulps (2**-8 relative)
# of the float32 reference's maximum at the observed logit scale.
# Random-init logits are nearly flat, so a batched bf16 paged kernel and
# a float32 forward flip near-ties; chip_smoke.py measured a worst
# margin of 1.5 ulps at GPT-2-small width (PR 22), while a token from
# another request's stream, or a forward in a lower precision than
# bf16, misses by many times this bound.
EPS_ULPS = 8
CHECK_SAMPLE = 4

# The routed rule.  Where a configuration declares a router
# (``reference.routed``), a faultless bf16 program and the float32
# reference choose different experts at near-ties, and one swapped
# expert moves a logit by many times eps: on the chip a faultless toy
# stack (calibrate_routed.py; 560 draws of 4-12 routed layers holding
# 8-64 of 128 or 256 experts, top 6, 8 or 10, a causal mixer before each)
# read 0-21.4% of 1,024 served positions over eps and a worst margin of
# up to 24.0 eps, where the same stack with its routing forced equal to
# the reference's read 0% and 0.75 eps at most.  So such a configuration
# is held to three things over the served positions of its sample:
# (a) the SHARE of margins over eps is at most ``routed_share_max``;
# (b) no margin is over ROUTED_WORST_MAX x eps; (c) all are finite.
# PERF.md section 6, PR 31, holds the calibration's tables.
#
# (a) The faultless share grows with what a swap can reach: by
# regression over the draws as layers^0.8 x held^1.2 (held: the experts
# this chip holds), and neither the router's width nor the experts a
# token takes adds to the fit.  The limit is the envelope of that: the
# largest share / (layers x held) of all 560 draws (0.000331: 21.2% at
# 10 x 64) with a quarter of room.  Fitted on the first 320 draws it was
# 0.00037, and none of 240 fresh draws failed it.  It is an envelope
# over three families of widths, so it sits 3.5 times over the median
# draw: with experts of 1856 and 1024 (hidden 2688, 3072) fp8
# activations failed it in every draw, with experts of 768 (hidden
# 2048), which add least to the stream, in 81%; PERF.md names what it
# cannot see.
ROUTED_SHARE_PER_LAYER_EXPERT = 0.00041
# no limit is extrapolated past the calibration's largest stack (12
# layers x 64 held: its faultless draws read up to 21.4%)
ROUTED_SHARE_CAP = 0.30
# (b) the largest faultless margin of all draws is 24.0 eps (16 or under
# in 97% of them), a quarter of room over it; a served stream of another
# request's tokens reads 31-51 eps in every draw.  The cap catches a
# foreign token too rare to move the share, where it lands over it.
ROUTED_WORST_MAX = 30.0
# the limits are the largest of readings over 1,024 positions each; a
# sample half that size read up to 1.6 times its draw's share
ROUTED_MIN_POSITIONS = 1024


def routed_share_max(routed):
    """The largest share of served positions over eps that a
    configuration with this router may read."""
    return min(ROUTED_SHARE_CAP, ROUTED_SHARE_PER_LAYER_EXPERT
               * routed["layers"] * routed["held"])


def load_object(path):
    """``"package.module:name"`` -> the object."""
    mod, name = path.split(":")
    return getattr(importlib.import_module(mod), name)


def load_ds_serve(root):
    path = os.path.join(root, "bin", "ds_serve")
    loader = importlib.machinery.SourceFileLoader("ds_serve_cli", path)
    spec = importlib.util.spec_from_loader("ds_serve_cli", loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


def program_field(config, name):
    """The published value behind a field of the program's config."""
    return config[config["program"]["fields"][name]]


def build_module(config, **dtypes):
    """The flax module of a configuration file: ``program.config`` is
    the program's config class, ``program.fields`` maps its fields to
    the published keys; ``dtypes`` are the class's dtype fields."""
    prog = config["program"]
    fields = {dst: config[src] for dst, src in prog["fields"].items()}
    fields.update(prog.get("extra", {}))
    return load_object(prog["module"])(
        load_object(prog["config"])(**dtypes, **fields))


def numeric_items(mapping, prefix=""):
    """The items of a counters map that are plain numbers."""
    return {prefix + k: v for k, v in mapping.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def seed_key(seed):
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def build_engine(config, seed, cli):
    """``init_inference`` as ``ds_serve`` calls it; the weights come
    from one jitted ``module.init`` on the device, in the served type."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    dt = jnp.dtype(cli.dtype)
    module = build_module(config, dtype=dt, param_dtype=dt)
    engine = deepspeed_tpu.init_inference(
        module, dtype=cli.dtype, kv_cache_dtype=cli.kv_dtype,
        tensor_parallel={"tp_size": cli.tp}, paged_kernel=cli.paged_kernel)
    ids = jnp.zeros((1, 8), jnp.int32)
    variables = jax.jit(lambda key: module.init(key, ids))(seed_key(seed))
    # set_params casts into a second copy without donating the first:
    # wait for the copy and drop ours before the KV pool is allocated,
    # or the two overlap in some runs and the peak moves by 1.6 GB
    engine.set_params(variables["params"])
    jax.block_until_ready(engine.params)
    del variables
    gc.collect()
    return engine


def build_scheduler(engine, cli, max_queue):
    """``serve_single``'s constructor call, minus tracing, tenancy and
    speculation (all off at the CLI's defaults)."""
    from deepspeed_tpu.serving import ServingScheduler
    return ServingScheduler(
        engine, num_slots=cli.num_slots, num_pages=cli.num_pages,
        page_size=cli.page_size, max_pages_per_slot=cli.max_pages_per_slot,
        prefill_chunk=cli.prefill_chunk, do_sample=cli.do_sample,
        seq_parallel_threshold=cli.seq_parallel_threshold,
        prefill_reserve_frac=cli.prefill_reserve_frac,
        temperature=cli.temperature, top_k=cli.top_k, top_p=cli.top_p,
        decode_horizon_steps=cli.decode_horizon,
        overlap=not cli.no_overlap, prefix_cache=cli.prefix_cache,
        prefix_cache_pages=cli.prefix_cache_pages,
        mem_telemetry=cli.mem_telemetry, audit_every=cli.audit_every,
        max_queue=max_queue)


def cli_args(root, config, mix):
    serve = dict(config["serve"], **mix["serve"])
    argv = ["--dtype", config["dtype"], "--kv-dtype", config["kv_dtype"],
            "--num-slots", str(serve["num_slots"]),
            "--num-pages", str(serve["num_pages"]),
            "--max-pages-per-slot", str(serve["max_pages_per_slot"])]
    if "page_size" in serve:     # CPU rehearsals only: the chip's default
        argv += ["--page-size", str(serve["page_size"])]   # is the CLI's
    if "paged_kernel" in serve:
        argv += ["--paged-kernel", serve["paged_kernel"]]
    return load_ds_serve(root).parse_args(argv), serve


def warm_up(sched, vocab_size):
    """Every program the window can reach, through the scheduler's own
    path: the prefill chunk, one fused-decode program per horizon bucket
    (a lone request with 1 + h tokens to emit takes exactly bucket h),
    and the batched first-token sample at every batch size up to the
    slot count (n short prompts admitted together finish prefill in the
    same step)."""
    rng = np.random.default_rng(0)

    def prompt(n):
        return rng.integers(0, vocab_size, n, dtype=np.int32)
    for h in sched.horizon_buckets:
        sched.submit(prompt(sched.prefill_chunk + 1), 1 + h)
        sched.run()
    for n in range(2, sched.num_slots + 1):
        for _ in range(n):
            sched.submit(prompt(2), 1)
        sched.run()


class StepClock:
    """Where the longest scheduler step of a run spent its time: wall
    seconds, this process's CPU seconds, seconds inside Python's garbage
    collector, and the longest silence of a heartbeat thread that only
    sleeps ``BEAT_S`` at a time.  Little CPU in a long step means it was
    blocked; if the heartbeat kept time meanwhile, the main thread waited
    on the device or the runtime, and if the heartbeat fell silent too,
    the whole process was held up (the machine, or a call holding the
    interpreter's lock)."""

    BEAT_S = 0.05

    def __init__(self):
        self.gc_s, self._gc_t = 0.0, 0.0
        self.worst = {"step_max_s": 0.0}
        self.over_1s = 0
        self._beat_gap, self._stop = 0.0, threading.Event()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._beat, daemon=True)
        self._thread.start()

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.monotonic()
        else:
            self.gc_s += time.monotonic() - self._gc_t

    def _beat(self):
        last = time.monotonic()
        while not self._stop.wait(self.BEAT_S):
            now = time.monotonic()
            self._beat_gap = max(self._beat_gap, now - last)
            last = now

    def begin(self):
        self._beat_gap = 0.0
        self._t = (time.monotonic(), time.process_time(), self.gc_s)

    def end(self, at_s):
        wall = time.monotonic() - self._t[0]
        self.over_1s += wall > 1.0
        if wall > self.worst["step_max_s"]:
            self.worst = {"step_max_s": wall, "step_max_at_s": at_s,
                          "step_max_cpu_s": time.process_time() - self._t[1],
                          "step_max_gc_s": self.gc_s - self._t[2],
                          "step_max_beat_gap_s": self._beat_gap}

    def close(self):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        return dict(self.worst, steps_over_1s=int(self.over_1s),
                    gc_total_s=self.gc_s)


class Recorder:
    """Per-request records on the window's clock (seconds from t0)."""

    def __init__(self):
        self.t0 = None
        self.rows = []

    def now(self):
        return time.monotonic() - self.t0

    def submit(self, sched, item, prompt, due_s):
        row = {"due_s": due_s, "submit_s": self.now(), "t_first_s": None,
               "t_last_s": None, "finish_s": None, "n_out": 0,
               "n_prompt": len(prompt), "state": "waiting",
               "max_new": item["max_new"], "prompt": prompt, "req": None}

        def on_token(req, tok, row=row):
            t = self.now()
            if row["t_first_s"] is None:
                row["t_first_s"] = t
            row["t_last_s"] = t
            row["n_out"] += 1
        try:
            row["req"] = sched.submit(prompt, item["max_new"],
                                      on_token=on_token)
        except Exception as e:   # QueueFull / oversize: a failed request
            row["state"] = f"refused: {type(e).__name__}: {e}"
        self.rows.append(row)
        return row

    def settle(self):
        """Copy terminal states; a request is finished at its last
        token."""
        done = 0
        for row in self.rows:
            req = row["req"]
            if req is None or row["finish_s"] is not None:
                continue
            if req.state in ("finished", "failed", "shed", "cancelled"):
                row["state"] = req.state
                row["finish_s"] = row["t_last_s"] \
                    if row["t_last_s"] is not None else self.now()
                done += 1
        return done


def drive(sched, items, prompts, mix, seconds, ctx):
    """Offer the mix for ``seconds``, then drain.  Open loop: each
    request is submitted at the first step boundary at or after its due
    time, and the drain serves every request that was due (``prompts``
    holds their tokens, made during set-up).  Closed loop:
    ``clients`` requests are in the system at all times, one that
    finishes is replaced at once, and the requests still in flight when
    the window ends are cancelled and leave the records (they were cut
    by the window, not failed by the system); what they had been served
    by then counts in ``served_tokens_per_s``: every prompt token
    prefilled and every token emitted up to the cut, over the time to
    the cut, whole requests or not."""
    import jax
    ann = jax.profiler.TraceAnnotation
    rec = Recorder()
    closed = mix["loop"] == "closed"
    occupancy, steps, nxt, served = [], 0, 0, None
    clock = StepClock()
    rec.t0 = time.monotonic()
    if closed:
        for _ in range(mix["clients"]):
            rec.submit(sched, items[nxt % len(items)], prompts(nxt), 0.0)
            nxt += 1
    drain_until = seconds + mix.get("drain_s", 30.0)
    while True:
        now = rec.now()
        ctx.tick(now)
        offering = now < seconds if closed else nxt < len(items)
        if closed and not offering:
            if served is None:
                served = (now, sum(
                    r["req"].prefill_pos + r["n_out"] for r in rec.rows
                    if r["req"] is not None))
            for row in rec.rows:
                if row["req"] is not None and row["finish_s"] is None:
                    row["cut"] = True
                    row["req"].cancel()
        elif not closed:
            with ann("bench.submit"):
                while nxt < len(items) and items[nxt]["due_s"] <= now:
                    rec.submit(sched, items[nxt], prompts(nxt),
                               items[nxt]["due_s"])
                    nxt += 1
        clock.begin()
        with ann("bench.sched_step"):
            busy = sched.step()
        clock.end(now)
        steps += 1
        finished = rec.settle()
        if now < seconds:
            occupancy.append(sum(r is not None for r in sched.slot_req)
                             / sched.num_slots)
        if closed and offering:
            with ann("bench.submit"):
                for _ in range(finished):
                    rec.submit(sched, items[nxt % len(items)],
                               prompts(nxt), rec.now())
                    nxt += 1
        elif not busy:
            if not offering:
                break
            wait = items[nxt]["due_s"] - rec.now()
            if wait > 0:
                with ann("bench.wait_arrival"):
                    time.sleep(wait)
        if rec.now() > drain_until:
            break
    ctx.tick(rec.now(), end=True)
    rec.rows = [r for r in rec.rows if not r.get("cut")]
    return rec, {"steps": steps, "steps_in_window": len(occupancy),
                 "step_clock": clock.close(),
                 "served_tokens_per_s": served[1] / served[0]
                 if served else None,
                 "slot_occupancy": float(np.mean(occupancy))
                 if occupancy else None}


def teacher_force(engine, ref_hidden, ref_logits, ref_args, pick, cap):
    """Prompt + served tokens of the requests ``pick`` through the plain
    float32 reference (``ref_hidden(params, ids, **ref_args)`` -> final
    hidden states, ``ref_logits(params, rows)`` -> logits of picked
    rows).  Returns, over the served positions: how far each served
    token's logit lies under the reference's best, the reference's
    logit scale, and how many served tokens are its argmax."""
    import jax
    import jax.numpy as jnp
    new = max(r["max_new"] for r in pick)
    ids = np.zeros((len(pick), cap), np.int32)
    pos = np.zeros((len(pick), new), np.int32)
    valid = np.zeros((len(pick), new), bool)
    for j, r in enumerate(pick):
        p, t = r["prompt"], r["req"].out_tokens
        ids[j, :len(p)] = p
        ids[j, len(p):len(p) + len(t)] = t
        # logits at position i score token i + 1
        pos[j, :len(t)] = len(p) - 1 + np.arange(len(t))
        valid[j, :len(t)] = True
    with jax.default_matmul_precision("highest"):
        hidden = ref_hidden(engine.params, jnp.asarray(ids), **ref_args)
        rows_h = jnp.take_along_axis(hidden, jnp.asarray(pos)[..., None], 1)
        lg = ref_logits(engine.params, rows_h)
    served = jnp.take_along_axis(jnp.asarray(ids), jnp.asarray(pos) + 1, 1)
    got = jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
    margin = np.asarray(jnp.max(lg, -1) - got)[valid]
    scale = float(np.asarray(jnp.max(jnp.abs(lg), -1))[valid].max())
    exact = int((np.asarray(jnp.argmax(lg, -1) == served))[valid].sum())
    return margin, scale, exact


def check_outputs(engine, ref_hidden, ref_logits, ref_args, rows, cap, seed,
                  routed=None):
    """Teacher-force prompt + served tokens of a seeded sample of
    finished requests through the plain float32 reference and hold
    every served token to the eps-argmax rule; where the configuration
    declares a router (``routed``, from ``Context.reference_routed``),
    to the routed rule instead.  Returns (ok, notes)."""
    done = [r for r in rows if r["state"] == "finished" and r["n_out"] > 0]
    if not done:
        return False, {"reference": "no finished request to check"}
    rng = np.random.default_rng(loadgen.seed_words(seed))
    if routed is not None:
        return check_routed(engine, ref_hidden, ref_logits, ref_args, done,
                            cap, rng, routed)
    pick = [done[i] for i in rng.choice(len(done), min(CHECK_SAMPLE,
                                                       len(done)), False)]
    margin, scale, exact = teacher_force(engine, ref_hidden, ref_logits,
                                         ref_args, pick, cap)
    eps = EPS_ULPS * 2.0 ** -8 * scale
    worst = float(margin.max())
    notes = {"reference_worst_margin": worst, "reference_eps": eps,
             "reference_logit_scale": scale,
             "reference_exact_argmax": [exact, len(margin)],
             "reference_requests": len(pick)}
    return bool(np.all(np.isfinite(margin)) and worst <= eps), notes


def check_routed(engine, ref_hidden, ref_logits, ref_args, done, cap, rng,
                 routed):
    """The routed rule (limits: ROUTED_* above).  The sample is the
    longest finished request, then finished requests in an order drawn
    from the seed, until ROUTED_MIN_POSITIONS served positions are in
    it; the reference runs over CHECK_SAMPLE requests at a time, as the
    dense rule's does, so that it fits beside the weights."""
    order = [int(i) for i in rng.permutation(len(done))]
    longest = max(order, key=lambda i: done[i]["n_out"])
    order.remove(longest)
    pick, positions = [], 0
    for i in [longest] + order:
        if len(pick) >= CHECK_SAMPLE and positions >= ROUTED_MIN_POSITIONS:
            break
        pick.append(done[i])
        positions += done[i]["n_out"]
    margins, scale, exact = [], 0.0, 0
    for at in range(0, len(pick), CHECK_SAMPLE):
        m, sc, ex = teacher_force(engine, ref_hidden, ref_logits, ref_args,
                                  pick[at:at + CHECK_SAMPLE], cap)
        margins.append(m)
        scale, exact = max(scale, sc), exact + ex
    margin = np.concatenate(margins)
    eps = EPS_ULPS * 2.0 ** -8 * scale
    worst = float(margin.max())
    share = float(np.mean(margin > eps))
    share_max = routed_share_max(routed)
    notes = {"reference_over_eps_share": share,
             "reference_share_limit": share_max,
             "reference_worst_margin": worst, "reference_eps": eps,
             "reference_worst_limit": ROUTED_WORST_MAX * eps,
             "reference_positions": len(margin),
             "reference_min_positions": ROUTED_MIN_POSITIONS,
             "reference_logit_scale": scale,
             "reference_exact_argmax": [exact, len(margin)],
             "reference_requests": len(pick), "reference_routed": routed}
    ok = np.all(np.isfinite(margin)) and share <= share_max \
        and worst <= ROUTED_WORST_MAX * eps \
        and len(margin) >= ROUTED_MIN_POSITIONS
    return bool(ok), notes


def reference_compared(notes):
    """The reference check's numbers, each beside its limit."""
    if "reference_share_limit" not in notes:
        return {"reference_worst_margin": [
            notes.get("reference_worst_margin"), notes.get("reference_eps")]}
    return {
        "reference_over_eps_share": [notes["reference_over_eps_share"],
                                     notes["reference_share_limit"]],
        "reference_worst_margin": [notes["reference_worst_margin"],
                                   notes["reference_worst_limit"]],
        "reference_positions_at_least": [notes["reference_positions"],
                                         notes["reference_min_positions"]]}


def generator_lateness(m, step_s):
    """The on-time rule (issue 53, in place of issue 25's rule on the
    mean) and the notes' lateness numbers.  The generator submits
    between scheduler steps, so a request is late by half a step on
    average; a MEDIAN lateness over one whole mean step means the loop
    did not offer the load the mix names: a starved generator, a
    ``submit()`` that blocks, or an open loop that has in effect closed
    (every request late, so the median is).  One or two steps in which
    the host stood still for seconds are late for the requests due in
    them alone, and no longer make the run incorrect, as they did under
    the mean (one stall of ~2.5 s in a 40 s window was enough, and the
    tokens of every run so refused matched the reference).  The stall
    stays in the numbers: the tails are timed from the due time and
    carry it in full, the notes keep the mean and the maximum, say with
    ``late_runs_mean_over_step`` whether the old rule would have failed
    the run, and hold the longest step's wall, CPU, GC and
    heartbeat-silence seconds.  Returns (on time, notes)."""
    return m["lateness_median_s"] <= step_s, {
        "lateness_mean_s": m["lateness_mean_s"],
        "lateness_median_s": m["lateness_median_s"],
        "lateness_max_s": m["lateness_max_s"],
        "late_runs_mean_over_step": int(m["lateness_mean_s"] > step_s)}


def run(ctx):
    config, mix = ctx.config, ctx.traffic
    ref_hidden, ref_logits = ctx.reference("hidden"), ctx.reference("logits")
    routed = ctx.reference_routed()
    cli, serve = cli_args(ctx.root, config, mix)
    engine = build_engine(config, ctx.seed, cli)
    ctx.memory("weights")
    ctx.mark("engine_built")
    vocab = config["vocab_size"]
    items = loadgen.make_requests(mix, ctx.seconds)
    if mix["loop"] == "open":
        made = [loadgen.prompt_tokens(ctx.seed, i, it["n_prompt"], vocab)
                for i, it in enumerate(items)]
        prompts = made.__getitem__
    else:
        def prompts(i):
            return loadgen.prompt_tokens(
                ctx.seed, i, items[i % len(items)]["n_prompt"], vocab)
    warm = build_scheduler(engine, cli, serve["max_queue"])
    warm_up(warm, vocab)
    health = warm.health()
    del warm
    gc.collect()
    # a fresh scheduler, so its counters hold the window and nothing else
    sched = build_scheduler(engine, cli, serve["max_queue"])
    ctx.memory("warm")
    ctx.mark("warm_up_done")
    ctx.begin_window()
    rec, counts = drive(sched, items, prompts, mix, ctx.seconds, ctx)
    ctx.end_window()
    ctx.memory("window")

    m = loadgen.request_metrics(rec.rows, ctx.seconds)
    m["served_tokens_per_s"] = counts["served_tokens_per_s"]
    summary = sched.summary()
    step_s = ctx.seconds / max(1, counts["steps_in_window"])
    pa = health.get("paged_attention") or {}
    want_path = serve.get("expect_paged_path", "kernel")
    checks = {
        "all_finished": m["failed"] == 0,
        "paged_path": pa.get("path") == want_path,
    }
    on_time, lateness = generator_lateness(m, step_s)
    if not ctx.traced():
        # a traced run's loop stalls at the profiler's stop
        checks["generator_on_time"] = on_time
    cap = cli.max_pages_per_slot * sched.kv.page_size
    del sched
    gc.collect()
    ok, notes = check_outputs(engine, ref_hidden, ref_logits,
                              ctx.reference_args(), rec.rows, cap, ctx.seed,
                              routed)
    checks["reference"] = ok
    notes.update(counts["step_clock"])
    notes.update(lateness, step_mean_s=step_s,
                 page_util_mean=summary.get("page_util_mean"),
                 slot_occupancy=counts["slot_occupancy"],
                 paged_attention=pa, ttft_p50_ms=m.get("ttft_p50_ms"),
                 tpot_p50_ms=m.get("tpot_p50_ms"),
                 finished_in_window=sum(
                     1 for r in rec.rows if r["state"] == "finished"
                     and r["finish_s"] <= ctx.seconds))
    # every number the program's own summary keeps, so that a metric
    # file can read a counter a later PR adds; the driver's own win
    counters = dict(numeric_items(summary),
                    slot_occupancy=counts["slot_occupancy"],
                    steps=counts["steps"])
    compared = reference_compared(notes)
    compared["failed_requests"] = [m["failed"], 0]
    if "generator_on_time" in checks:
        compared["lateness_median_s"] = [m["lateness_median_s"], step_s]
    return {"checks": checks, "attempted": m["attempted"],
            "failed": m["failed"], "end_to_end": m, "counters": counters,
            "compared": compared, "static": {}, "notes": notes}
