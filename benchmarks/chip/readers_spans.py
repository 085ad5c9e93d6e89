"""The device's idle time by what the host was doing in it.

The program names the phases of its host loop with
``jax.profiler.TraceAnnotation`` (``ds.sched.*``, ``ds.engine.*``,
``ds.train.*``: ``deepspeed_tpu/tracing.py``), so in a traced run they
are events of the same ``.xplane.pb``, on the same clock, as the device
plane's "XLA Ops" line.  ``readers.load_trace`` keeps only the
benchmark's own ``bench.*`` host events, so this module opens the run's
file once more and keeps the ``ds.*`` ones; the idle intervals are
``readers``' own (busy union, gaps), cut by the union of the named
spans.  A program without the spans reads None everywhere.
"""

import glob
import os

import readers

HERE = os.path.dirname(os.path.abspath(__file__))
# where run.py puts a traced run's files: <root>/.bench_trace/<cell>/
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                          ".bench_trace")
_PARSED = {}


def read_host_events(path):
    """([bench.* events], [ds.* events]) of the host planes of one
    ``.xplane.pb``, each event ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    bench, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bench.", "ds.")):
                    s = int(ev.start_ns)
                    (bench if ev.name[0] == "b" else spans).append(
                        (ev.name, s, s + int(ev.duration_ns)))
    return bench, spans


def program_spans(ctx):
    """The program's ``ds.*`` host events of THIS run's trace, or None.
    The file is the newest ``.xplane.pb`` under ``TRACE_ROOT`` (run.py
    clears the cell's directory before it starts the profiler); it is
    this run's only if its ``bench.*`` events are the ones the context's
    trace holds.  Parsed once a process."""
    tr = ctx["trace"]
    if tr is None:
        return None
    paths = glob.glob(os.path.join(TRACE_ROOT, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    if path not in _PARSED:
        _PARSED[path] = read_host_events(path)
    bench, spans = _PARSED[path]
    if sorted(bench) != sorted(tuple(s) for s in tr.host_spans):
        return None
    return spans


def intersect(a, b):
    """The parts of the merged intervals ``a`` that merged ``b`` covers."""
    return readers.subtract(a, readers.subtract(a, b))


def host_intervals(spans, lo, hi, inside=(), minus=(), outside=()):
    """Where the host was, as merged intervals of [lo, hi]: in a span
    named in ``inside`` and in none named in ``minus``; or, with
    ``outside``, in no span named there."""
    def union(names):
        return readers.merge(readers.clip(
            [(s, e) for n, s, e in spans if n in names], lo, hi))
    if outside:
        return readers.gaps(union(outside), lo, hi)
    return readers.subtract(union(inside), union(minus))


def idle_share(trace, spans, inside=(), minus=(), outside=()):
    """100 x (device idle AND host in the named phases) / window, mean
    over the trace's devices."""
    lo, hi = trace.lo, trace.hi
    if hi <= lo or not trace.device_ops:
        return None
    host = host_intervals(spans, lo, hi, inside, minus, outside)
    idle = [readers.total(intersect(
        readers.gaps(trace.busy(d), lo, hi), host))
        for d in trace.device_ops]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)


def idle_share_in(ctx, inside=(), minus=(), outside=()):
    """Share of the traced window in which a device ran nothing while
    the host was in a phase named in ``inside`` (and in none named in
    ``minus``), or, with ``outside``, in no phase named there.  None
    where the run's file holds no ``ds.*`` event."""
    spans = program_spans(ctx)
    if not spans:
        return None
    return idle_share(ctx["trace"], spans, inside, minus, outside)
