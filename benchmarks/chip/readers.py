"""From a profiler trace, counters and static shapes to per-layer metrics.

Three parts, all kept with the benchmark so that no later PR which
claims a gain can change how a number is computed:

* interval arithmetic over plain event lists (busy union, idle gaps,
  time of events by name, collective time not covered by compute);
* ``load_trace``: the run's ``.xplane.pb`` through
  ``jax.profiler.ProfileData`` into those plain lists;
* the readers ``layer_metrics/<name>.json`` names as
  ``"readers:<function>"``.  A reader takes the run's context and its
  file's ``args`` and returns a number, or None when there is nothing
  to read, in which case the harness leaves the metric out.

Operations and bytes of a kernel are computed here from static shapes.
"""

import glob
import os

# ----------------------------------------------------------------- intervals


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint
    intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            if cur >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] given merged busy intervals."""
    return subtract([(lo, hi)], busy)


class Trace:
    """A traced window as plain lists.

    ``device_ops``: {device id: [(name, start_ns, end_ns), ...]} from the
    device planes' operation line; ``host_spans``: [(name, start_ns,
    end_ns)] of the benchmark's own ``bench.*`` annotations; the window
    is the hull of both, so the ends of the traced window count as idle
    where no operation ran."""

    def __init__(self, device_ops, host_spans, device_lines=None):
        self.device_ops = device_ops
        self.host_spans = host_spans
        # other lines of the device planes by name, {line: {device: ops}}
        self.device_lines = device_lines or {}
        pts = [t for ops in device_ops.values() for _, s, e in ops
               for t in (s, e)]
        pts += [t for _, s, e in host_spans for t in (s, e)]
        self.lo, self.hi = (min(pts), max(pts)) if pts else (0, 0)

    @property
    def window_s(self):
        return (self.hi - self.lo) / 1e9

    def busy(self, dev, keep=None):
        return merge((s, e) for n, s, e in self.device_ops[dev]
                     if keep is None or keep(n))

    def busy_s(self):
        """Seconds in which an operation ran, averaged over devices."""
        if not self.device_ops:
            return 0.0
        return sum(total(self.busy(d)) for d in self.device_ops) / \
            len(self.device_ops) / 1e9

    def name_seconds(self, substrs=(), line=None, heads=(), all_of=()):
        """Sum of the durations of events that :func:`matches`, averaged
        over devices; on the operation line, or on another line of the
        device planes by name."""
        ops = self.device_ops if line is None \
            else self.device_lines.get(line, {})
        if not ops:
            return 0.0
        tot = sum(e - s for d in ops for n, s, e in ops[d]
                  if matches(n, substrs, heads, all_of))
        return tot / len(ops) / 1e9

    def top_ops(self, k=10):
        """The k operation groups with most device time.  An event's
        name is its whole HLO text, so events are grouped by
        :func:`short_name`; loops and calls, whose events span the
        operations inside them, are left out."""
        acc = {}
        for ops in self.device_ops.values():
            for n, s, e in ops:
                key = short_name(n)
                if key is not None:
                    acc[key] = acc.get(key, 0) + (e - s)
        nd = max(1, len(self.device_ops))
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / nd / 1e9] for n, t in top]

    def top_gaps(self, k=10):
        """The k longest idle gaps of the first device, each labelled by
        the benchmark span that covers most of it."""
        if not self.device_ops:
            return []
        dev = sorted(self.device_ops)[0]
        idle = gaps(self.busy(dev), self.lo, self.hi)
        idle = sorted(idle, key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in idle:
            best, cover = "unattributed", 0
            for n, hs, he in self.host_spans:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = n, c
            out.append([best, (e - s) / 1e9])
        return out


WRAPPERS = ("while", "conditional", "call")


def own_name(name):
    """``%attn.12 = bf16[...] custom-call(%x, ...)`` -> ``attn``: the
    instruction's own name without its number (an event's name is its
    whole HLO text, operands and all)."""
    return name.partition(" = ")[0].lstrip("%").split(".")[0]


def matches(name, substrs=(), heads=(), all_of=()):
    """An event name passes if it contains one of ``substrs`` (where
    given) and every one of ``all_of``, and its :func:`own_name` is one
    of ``heads`` (where given): so that a kernel is told from another
    custom call that merely takes its output as an operand."""
    return (not substrs or any(x in name for x in substrs)) and \
        all(x in name for x in all_of) and \
        (not heads or own_name(name) in heads)


def short_name(name):
    """``%fusion.12 = bf16[32,4096]{...} fusion(...)`` -> ``fusion
    bf16[32,4096]``; a Mosaic kernel keeps its own name and is marked.
    None for a loop, conditional or call."""
    rest = name.partition(" = ")[2]
    base = own_name(name)
    if base in WRAPPERS:
        return None
    if not rest:
        return base
    shape = rest.lstrip("(").split("{")[0].split(" ")[0].rstrip(",")
    tag = " [mosaic]" if "tpu_custom_call" in rest else ""
    return f"{base} {shape}{tag}"[:64]


def load_trace(trace_dir, op_line="XLA Ops", span_prefix="bench."):
    """Read the newest ``.xplane.pb`` under ``trace_dir``.  Device planes
    are those named ``/device:TPU:<n>``; of their lines only ``op_line``
    is read (one event per executed operation: the module and step lines
    above it would count every operation twice).  Host spans are the
    events of any host line whose name starts with ``span_prefix``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    device_ops, device_lines, host_spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                ops = []
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append((ev.name, s, s + int(ev.duration_ns)))
                if line.name == op_line:
                    device_ops[plane.name] = ops
                else:
                    device_lines.setdefault(line.name, {})[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        s = int(ev.start_ns)
                        host_spans.append(
                            (ev.name, s, s + int(ev.duration_ns)))
    return Trace(device_ops, host_spans, device_lines)


def describe_trace(trace_dir, k=40):
    """Planes, lines and the most frequent event names of a trace, for
    reading one by hand before writing a metric against it."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            acc = {}
            for ev in line.events:
                n, t = acc.get(ev.name, (0, 0))
                acc[ev.name] = (n + 1, t + int(ev.duration_ns))
            top = sorted(acc.items(), key=lambda kv: -kv[1][1])[:k]
            out.append({"plane": plane.name, "line": line.name,
                        "events": sum(n for n, _ in acc.values()),
                        "top": [[n, c, t / 1e9] for n, (c, t) in top]})
    return out

# ------------------------------------------------- operations from shapes


def flash_causal_flops(batch, heads, seq, head_dim, backward=True):
    """FLOPs causal attention needs: QK^T and PV over the lower triangle
    (seq * (seq + 1) / 2 pairs), 2 * head_dim multiply-adds each, in the
    forward pass; the backward pass computes dV, dP, dQ and dK — four
    more matmuls of the same size — and a kernel that recomputes S = QK^T
    does so on its own account (recomputation is not required work)."""
    pairs = batch * heads * seq * (seq + 1) / 2
    fwd = 2 * 2 * head_dim * pairs
    return fwd + (4 * 2 * head_dim * pairs if backward else 0)


def train_flops_per_token(n_params, layers, hidden, seq):
    """Model FLOPs per trained token: 6 per parameter (forward and
    backward matmuls) plus causal attention's 6 * layers * hidden * seq
    (``bench.py`` counts 12: the full square.  A causal model needs half
    of it, and the smaller number keeps a utilisation honest).  No
    recomputation is counted."""
    return 6 * n_params + 6 * layers * hidden * seq

# ------------------------------------------------------------------ readers
# ctx: {"trace": Trace|None, "counters": dict, "static": dict,
#       "end_to_end": dict, "peaks": dict, "chips": int,
#       "config": the configuration's file, "traffic": the mix's file}


def idle_share(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0 or not tr.device_ops:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def name_time_share(ctx, substrs=(), line=None, of="window", heads=(),
                    all_of=()):
    """Share of the traced window (``of="window"``) or of the device's
    busy time (``of="busy"``) inside device events that :func:`matches`
    ``substrs`` / ``heads`` / ``all_of``: operations, or the events of
    another line of the device plane (``line="XLA Modules"`` for whole
    jitted programs)."""
    tr = ctx["trace"]
    if tr is None:
        return None
    base = tr.window_s if of == "window" else tr.busy_s()
    secs = tr.name_seconds(substrs, line, heads, all_of)
    return 100.0 * secs / base if secs > 0 and base > 0 else None


def flash_roofline(ctx, substrs=(), heads=(), all_of=()):
    """Least time the chip could take for the flash kernels' required
    FLOPs (causal forward + backward, from the cell's static shapes, per
    traced step) over the time their events took.  Compute bound: at
    seq 1024, d 64 the kernels move ~0.1 byte per FLOP, far under the
    chip's 4.2 FLOP per byte balance point."""
    tr, st = ctx["trace"], ctx["static"]
    if tr is None or "flash" not in st or not ctx["counters"].get(
            "traced_steps"):
        return None
    secs = tr.name_seconds(substrs, None, heads, all_of)
    if secs <= 0:
        return None
    f = st["flash"]
    flops = flash_causal_flops(f["batch_per_chip"], f["heads"], f["seq"],
                               f["head_dim"]) * f["layers"] * \
        ctx["counters"]["traced_steps"]
    return 100.0 * flops / ctx["peaks"]["flops_per_s"] / secs


def train_mfu(ctx):
    """Model FLOPs per token x tokens/s of the traced run over chips x
    peak.  Needs no trace."""
    st, tps = ctx["static"], ctx["end_to_end"].get("train_tokens_per_s")
    if tps is None or "train" not in st:
        return None
    t = st["train"]
    per_tok = train_flops_per_token(t["n_params"], t["layers"],
                                    t["hidden"], t["seq"])
    return 100.0 * per_tok * tps / (ctx["chips"] *
                                    ctx["peaks"]["flops_per_s"])


def counter(ctx, key, scale=1.0, one_minus=False):
    """A counter the driver collected, optionally as (1 - value)."""
    v = ctx["counters"].get(key)
    if v is None:
        return None
    v = 1.0 - v if one_minus else v
    return v * scale


def exposed_collective_share(ctx, collective_substrs):
    """Collective operations' intervals minus their overlap with compute
    operations on the same device, over the traced window; averaged over
    devices.  A collective is an event whose OWN instruction name holds
    one of ``collective_substrs`` (``%all-gather-start.7``): an event's
    name is its whole HLO text, and a fusion that takes ``%all-gather.7``
    as an operand is compute."""
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0 or not tr.device_ops:
        return None

    def is_coll(n):
        own = own_name(n)
        return any(x in own for x in collective_substrs)

    def is_compute(n):
        # a loop's or a call's event spans the operations inside it
        return not is_coll(n) and own_name(n) not in WRAPPERS
    shares = []
    for d in tr.device_ops:
        coll = tr.busy(d, is_coll)
        comp = tr.busy(d, is_compute)
        shares.append(total(subtract(coll, comp)) / 1e9)
    if not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares) / tr.window_s
