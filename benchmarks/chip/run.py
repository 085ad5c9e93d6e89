"""One cell of the chip benchmark, once.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

device check -> compile cache -> the cell's driver (set-up, warm-up,
measured window, output check) -> one JSON line.  What belongs to one
configuration, traffic mix or per-layer metric is a data file found by
the name ``BENCHMARK.json`` gives it; this file knows kinds of traffic
(``traffic/<mix>.json``: ``"kind"`` -> ``drive_<kind>.py``) and nothing
by name.  Without a TPU, or with fewer chips than the cell asks for, it
exits nonzero and prints no result line.
"""

import time

T_START = time.monotonic()

import argparse            # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import math                # noqa: E402
import os                  # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the profiler runs over the LAST seconds of the window (or the last
# quarter of a short one): its stop takes ~10 s on the chip, and there
# it falls after the window instead of stalling the loop in mid-window
TRACE_SECONDS = 3.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# reference.routed: the names it must carry, and the one it may
ROUTED_KEYS, ROUTED_OPTIONAL = ("layers", "experts", "per_token"), ("held",)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(bench_dir, name):
    """A harness module by file, from ``bench_dir`` (so a copy of the
    harness runs its own files)."""
    if name in sys.modules and getattr(
            sys.modules[name], "__file__", "").startswith(bench_dir):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(bench_dir, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_function(bench_dir, spec, default=None):
    """``"module:function"`` -> the function of ``<module>.py`` in
    ``bench_dir``; a bare name resolves in the module ``default``."""
    mod, _, fn = spec.rpartition(":")
    return getattr(load_module(bench_dir, mod or default), fn)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"{what} {name!r} is not in BENCHMARK.json")


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


class CompileCount:
    """Backend compilations (or loads from the persistent cache) since
    the process began, from jax.monitoring's duration events."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            self.n += 1
            self.secs += secs


class Context:
    """What a driver is given, and the hooks it calls: ``begin_window``
    / ``end_window`` around the measured window, ``tick(now)`` once per
    loop iteration so that a traced run can switch the profiler on for
    a few seconds of the steady window."""

    def __init__(self, root, bench_dir, config, traffic, seed, seconds,
                 trace_dir=None, devices=(), compiles=None, t_start=None,
                 config_file=None):
        self.root, self.bench_dir = root, bench_dir
        self.config, self.traffic = config, traffic
        self.config_file = config_file or config.get("name", "?")
        self.seed, self.seconds = seed, seconds
        self.trace_dir, self.devices = trace_dir, list(devices)
        self.compiles = compiles
        self.t_start = time.monotonic() if t_start is None else t_start
        self.setup_s = None
        self.window_compiles = None
        self._n0 = 0
        self.notes = {}
        self._trace_state = "off" if trace_dir is None else "armed"
        self._trace_len = min(TRACE_SECONDS, seconds / 4)
        self._trace_at = seconds - self._trace_len

    def reference(self, key):
        """The plain reference function the configuration's file names
        under ``reference.<key>``: ``"module:function"`` is looked up in
        ``<module>.py`` of the harness's directory (a new family brings
        its own file), a bare name in ``reference.py``.  A driver calls
        this before any set-up, so a name that resolves nowhere ends
        the run at once."""
        where = f"{self.config_file}: reference.{key}"
        spec = self.config.get("reference", {}).get(key)
        if not isinstance(spec, str):
            raise SystemExit(f"{where} is not set")
        try:
            return load_function(self.bench_dir, spec, "reference")
        except (OSError, AttributeError) as e:
            raise SystemExit(f"{where} = {spec!r} resolves nowhere "
                             f"({type(e).__name__}: {e})")

    def reference_args(self):
        """``reference.args`` maps the functions' keyword names to keys
        of the configuration's file; a value there may be a number, a
        string or a list (a layer pattern)."""
        ref = self.config.get("reference", {})
        return {a: self.config[k] for a, k in ref.get("args", {}).items()}

    def reference_routed(self):
        """``reference.routed`` declares the configuration's router as
        data: each name maps to the key of the configuration's own file
        that holds the size.  ``layers`` routed layers, ``experts`` the
        router scores, ``per_token`` it chooses, and optionally ``held``
        (the experts this chip holds; all of them without the name).
        The served tokens of such a configuration are held to the
        routed rule (``drive_serve.check_routed``).  None where the
        block is absent: the dense rule.  A driver calls this before
        any set-up, so a name the block lacks, or a key the file lacks,
        ends the run at once."""
        block = self.config.get("reference", {}).get("routed")
        if block is None:
            return None
        where = f"{self.config_file}: reference.routed"
        names = ROUTED_KEYS + ROUTED_OPTIONAL
        if not isinstance(block, dict) or set(block) - set(names):
            raise SystemExit(f"{where} is not a map of names among "
                             f"{list(names)} (it is {block!r})")
        out = {}
        for name in names:
            key = block.get(name)
            if key is None and name in ROUTED_OPTIONAL:
                continue
            if not isinstance(key, str):
                raise SystemExit(f"{where}.{name} is not set (it names "
                                 f"the key of this file that holds it)")
            value = self.config.get(key)
            if isinstance(value, bool) or not isinstance(value, int) \
                    or value < 1:
                raise SystemExit(
                    f"{where}.{name} = {key!r}: the file has no whole "
                    f"number of 1 or more under {key!r} (it has "
                    f"{value!r})")
            out[name] = value
        out.setdefault("held", out["experts"])
        if not out["per_token"] <= out["experts"] >= out["held"]:
            raise SystemExit(f"{where}: {out['experts']} experts cannot "
                             f"give {out['per_token']} a token or hold "
                             f"{out['held']}")
        return out

    def mark(self, phase):
        """Seconds from the start of the process to the end of a phase
        of the set-up, under ``setup_phases`` in the notes."""
        self.notes.setdefault("setup_phases", {})[phase] = \
            time.monotonic() - self.t_start

    def begin_window(self):
        self.setup_s = time.monotonic() - self.t_start
        self._n0 = self.compiles.n if self.compiles else 0

    def end_window(self):
        self.window_compiles = (self.compiles.n - self._n0) \
            if self.compiles else 0

    def tracing(self):
        return self._trace_state == "on"

    def traced(self):
        """True in a run that traces: the profiler's start and stop
        stall the loop, so such a run's host-clock numbers are its own."""
        return self._trace_state != "off"

    def memory(self, tag):
        """Bytes in use and the peak so far on the fullest chip, under
        ``tag`` in the notes."""
        stats = [d.memory_stats() for d in self.devices]
        if stats and stats[0]:
            self.notes["mem_" + tag] = [
                max(s["bytes_in_use"] for s in stats),
                max(s["peak_bytes_in_use"] for s in stats)]
            self.notes["mem_limit"] = stats[0].get("bytes_limit")

    def tick(self, now, end=False):
        import jax
        if self._trace_state == "armed" and not end and \
                now >= self._trace_at:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # device planes and the benchmark's own annotations only:
            # the Python tracer and the HLO protos slow the host loop
            # and the stop by seconds and no reader uses them
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            t = time.monotonic()
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.notes["trace_start_s"] = time.monotonic() - t
            self._trace_state = "on"
        elif self._trace_state == "on" and \
                (end or now >= self._trace_at + self._trace_len):
            t = time.monotonic()
            jax.profiler.stop_trace()
            self.notes["trace_stop_s"] = time.monotonic() - t
            self._trace_state = "done"


def layer_metrics(bench_dir, manifest, cell, reader_ctx):
    """The cell's per-layer metrics, each by the reader its own file
    names; a reader that finds nothing to read is left out."""
    out = {}
    for metric in manifest["per_layer"]:
        if not reports(metric, cell):
            continue
        spec = load_json(os.path.join(bench_dir, "layer_metrics",
                                      metric["name"] + ".json"))
        value = load_function(bench_dir, spec["reader"])(
            reader_ctx, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def load_cell(root, bench_dir, workload):
    """The manifest's entry of a cell with the files it names: the
    configuration as it is run and the traffic mix."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find(manifest["workloads"], workload, "workload")
    config_file = find(manifest["configs"], cell["config"], "config")["file"]
    return {"manifest": manifest, "cell": cell, "config_file": config_file,
            "config": load_json(os.path.join(root, config_file)),
            "traffic": load_json(os.path.join(
                bench_dir, "traffic", cell["traffic"] + ".json"))}


def reader_context(loaded, res, e2e, trace, peaks):
    """What every reader is given: the trace, the driver's counters and
    static shapes, the run's end-to-end numbers, the device's peaks, and
    the cell's own files, so that a reader counts operations and bytes
    from the published widths."""
    return {"trace": trace, "counters": res["counters"],
            "static": res["static"], "end_to_end": e2e, "peaks": peaks,
            "chips": loaded["cell"]["chips"], "config": loaded["config"],
            "traffic": loaded["traffic"]}


def run_cell(ctx, kind):
    """The driver of the traffic's kind, from the harness's directory."""
    return load_module(ctx.bench_dir, "drive_" + kind).run(ctx)


def plain(value):
    """A compared number as JSON holds it: one that is not finite goes
    as its name, so that the line stays JSON."""
    if isinstance(value, float) and not math.isfinite(value):
        return repr(value)
    return value


def end_to_end_metrics(manifest, workload, e2e):
    """The cell's end-to-end metrics as the result line holds them."""
    return {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in manifest["end_to_end"]
            if reports(m, workload) and e2e.get(m["name"]) is not None}


def result_line(res, ctx, device, metrics, breakdown=None):
    """The last line of standard output.  Its ``notes`` come last and
    name what decided ``correct``: every check as 0 or 1, and each
    number compared beside its limit."""
    res["checks"]["no_compile_in_window"] = ctx.window_compiles == 0
    res.setdefault("compared", {})["window_compiles"] = [
        ctx.window_compiles, 0]
    line = {"correct": all(res["checks"].values()),
            "attempted": res["attempted"], "failed": res["failed"],
            "device": device, "metrics": metrics}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["notes"] = {
        "setup_phases": ctx.notes["setup_phases"],
        "checks": {k: int(bool(v)) for k, v in res["checks"].items()},
        "compared": {k: [plain(v), plain(limit)]
                     for k, (v, limit) in res["compared"].items()}}
    return line


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = p.parse_args(argv)

    loaded = load_cell(ROOT, HERE, args.workload)
    manifest, cell = loaded["manifest"], loaded["cell"]
    config, traffic = loaded["config"], loaded["traffic"]
    sys.path[:0] = [HERE, ROOT]

    import jax
    t_imports = time.monotonic()
    devices = jax.devices()
    t_devices = time.monotonic()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"{args.workload}: needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} x {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if dev.device_kind not in peaks:
        print(f"{dev.device_kind!r} is not in peaks.json", file=sys.stderr)
        return 1

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program, however small, goes to the persistent cache: the
    # second run of a cell in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload) \
        if args.trace else None
    ctx = Context(ROOT, HERE, config, traffic, args.seed, args.seconds,
                  trace_dir=trace_dir, devices=devices[:cell["chips"]],
                  compiles=CompileCount(), t_start=T_START,
                  config_file=loaded["config_file"])
    # where the set-up's seconds go: the TPU client's start-up is the
    # machine's, what follows is the program's (the drivers mark
    # ``engine_built`` and ``warm_up_done``)
    ctx.notes["setup_phases"] = {"imports_done": t_imports - T_START,
                                 "devices_found": t_devices - T_START}
    res = run_cell(ctx, traffic["kind"])

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  d.memory_stats()["peak_bytes_in_use"]
                  for d in ctx.devices)}
    e2e = dict(res["end_to_end"], setup_s=ctx.setup_s)
    breakdown = None
    if args.trace:
        readers = load_module(HERE, "readers")
        trace = readers.load_trace(trace_dir)
        metrics = layer_metrics(
            HERE, manifest, args.workload,
            reader_context(loaded, res, e2e, trace, peaks[dev.device_kind]))
        if trace is not None:
            device["busy_s"] = trace.busy_s()
            device["window_s"] = trace.window_s
            breakdown = {"device_ops": trace.top_ops(),
                         "idle_gaps": trace.top_gaps()}
    else:
        metrics = end_to_end_metrics(manifest, args.workload, e2e)
    line = result_line(res, ctx, device, metrics, breakdown)
    notes = dict(res["notes"], **ctx.notes)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "checks": res["checks"], "notes": notes,
                      "end_to_end": e2e,
                      "window_compiles": ctx.window_compiles,
                      "compile_events": ctx.compiles.n,
                      "compile_s": ctx.compiles.secs,
                      "cache_dir": cache_dir}))
    print(json.dumps(line))
    sys.stdout.flush()
    # each check, and each number compared beside its limit, as the last
    # lines of standard error
    for name, (value, limit) in res["compared"].items():
        print(f"compared {name}: {value!r} against {limit!r}",
              file=sys.stderr)
    print(f"checks {json.dumps(res['checks'])} window_compiles "
          f"{ctx.window_compiles}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
