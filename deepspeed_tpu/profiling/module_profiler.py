"""Device time by model component and by module, from a device trace.

Reference: ``deepspeed/profiling/flops_profiler/profiler.py:23`` prints
per-module flops/MACs/latency by monkey-patching torch.nn.functional.
Here the numbers are measured: ``jax.profiler.trace`` records every
executed XLA operation's device time on the "XLA Ops" line of a
``/device:TPU:n`` plane.  What a v5e's trace holds of an operation (the
probe of PR 60, one traced run of the chat cell, PERF.md section 7):
the event itself carries its offset and duration only; its METADATA
(``plane.event_stats[ev.metadata_id]``, which
``jax.profiler.ProfileData`` does not show) carries ``tf_op`` -- the
instruction's ``op_name``, i.e. the name stack it was traced under,
flax module names and ``jax.named_scope``s, e.g.
``jit(decode_multi)/horizon/while/body/closed_call/Llama/layers_13/mlp/
w_down/dot_general:`` -- beside ``hlo_category``, ``program_id``,
``flops``, ``bytes_accessed`` / ``raw_bytes_accessed`` and ``source``.
Operations the compiler made itself (copies, asynchronous slices of a
weight) carry no ``tf_op``, and a fusion carries ONE: where XLA put its
metadata.  An executable loaded from the persistent compile cache
carries the paths of the tree that compiled it
(``utils/compile_cache.py``).

This module aggregates those records twice: by COMPONENT
(``tracing.component``: the closed vocabulary the benchmark's
``scope.*`` metrics are pinned to, with ``fwd`` / ``bwd`` in training),
then into the reference-style module tree -- measured, post-fusion
numbers, so it finds layout copies and bandwidth sinks an analytic
profiler cannot see.
"""

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict

import jax

from deepspeed_tpu.profiling.xplane import device_plane, read_xspace
from deepspeed_tpu.tracing import component, pass_of, path_parts

_JIT_PREFIX = re.compile(r"^jit\([^)]*\)/")
# their events span the operations inside them
WRAPPERS = ("while", "conditional", "call")
# path parts that name no module: a loop's or a call's own, and the
# scopes round a whole loop (``tracing.COMPONENTS``: the horizon's and
# the step loop's own bookkeeping reads under them in the component
# table)
_NOT_MODULES = ("while", "body", "cond", "closed_call", "horizon",
                "train_loop")


def capture_trace(step_fn, n_steps=3, trace_dir=None):
    """Run ``step_fn`` (already warmed/compiled) ``n_steps`` times under
    the jax profiler; returns the op records from the device plane.

    Record: {"op", "module", "component", "pass", "leaf_op", "category",
    "duration_ps", "flops", "bytes", "occurrences"} aggregated over the
    traced steps; loops, conditionals and calls, whose events span the
    operations inside them, are left out.
    """
    own = trace_dir is None
    trace_dir = trace_dir or tempfile.mkdtemp(prefix="ds_modprof_")
    try:
        with jax.profiler.trace(trace_dir):
            out = None
            for _ in range(n_steps):
                out = step_fn()
            # fence through a host transfer of a value derived from
            # the last step's output
            leaf = jax.tree.leaves(out)[0] if out is not None else None
            if leaf is not None and hasattr(leaf, "dtype"):
                import jax.numpy as jnp
                float(jax.device_get(jnp.sum(leaf.astype(jnp.float32))))
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(
                "jax.profiler.trace produced no xplane file — the "
                "backend may not support device tracing")
        plane = device_plane(read_xspace(sorted(files)[-1]))
        if plane is None:
            raise RuntimeError("no device plane with XLA Ops in trace")
        return _aggregate(plane, n_steps)
    finally:
        if own:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _aggregate(plane, n_steps):
    by_op = {}
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            meta_stats = plane.event_stats.get(ev.metadata_id, {})
            stats = {**meta_stats, **ev.stats}
            name = plane.event_names.get(ev.metadata_id, "?")
            op = name.split(" = ")[0].lstrip("%")
            if op.split(".")[0] in WRAPPERS:
                continue
            tf_op = stats.get("tf_op", "")
            rec = by_op.setdefault(ev.metadata_id, {
                "op": op,
                "module": _module_path(tf_op),
                "component": component(tf_op, op),
                "pass": pass_of(tf_op),
                "leaf_op": _leaf_op(tf_op),
                "category": stats.get("hlo_category", ""),
                "duration_ps": 0, "flops": 0, "bytes": 0,
                "occurrences": 0,
            })
            rec["duration_ps"] += ev.duration_ps
            rec["occurrences"] += 1
            rec["flops"] += int(stats.get("flops") or 0)
            rec["bytes"] += int(stats.get("raw_bytes_accessed")
                                or stats.get("bytes_accessed") or 0)
    recs = list(by_op.values())
    for r in recs:
        r["steps"] = n_steps
    return recs


def _module_path(tf_op):
    """'jit(f)/transpose(jvp(GPT2))/h_0/attn/qkv/dot_general:' ->
    'GPT2/h_0/attn/qkv [bwd]' — the jvp/transpose autodiff wrappers
    become a fwd/bwd phase tag instead of polluting the tree."""
    if not tf_op:
        return "(unattributed)"
    parts = [p for p in path_parts(_JIT_PREFIX.sub("", tf_op))
             if p not in _NOT_MODULES] or [""]
    head, phase = parts[0], ""
    if head.startswith("transpose("):
        phase = " [bwd]"
        head = head[len("transpose("):].rstrip(")")
    if head.startswith("jvp("):
        if not phase:
            phase = " [fwd]"
        head = head[len("jvp("):].rstrip(")")
    parts[0] = head
    mod = "/".join(p2 for p2 in parts[:-1] if p2)
    return (mod or "(top)") + phase


def _leaf_op(tf_op):
    if not tf_op:
        return ""
    return _JIT_PREFIX.sub("", tf_op).rstrip(":").split("/")[-1]


def aggregate_by_module(records, depth=3):
    """Group op records by module-path prefix of ``depth`` components.
    Returns rows sorted by time desc:
    (module, ms_per_step, flops_per_step, gb_per_step, share)."""
    groups = defaultdict(lambda: [0, 0, 0])
    total_ps = 0
    for r in records:
        key = "/".join(r["module"].split("/")[:depth])
        g = groups[key]
        g[0] += r["duration_ps"]
        g[1] += r["flops"]
        g[2] += r["bytes"]
        total_ps += r["duration_ps"]
    n = records[0]["steps"] if records else 1
    rows = []
    for mod, (ps, fl, by) in groups.items():
        rows.append({
            "module": mod,
            "ms": ps / 1e9 / n,
            "gflops": fl / 1e9 / n,
            "gb": by / 1e9 / n,
            "share": ps / total_ps if total_ps else 0.0,
        })
    rows.sort(key=lambda r: -r["ms"])
    return rows


def aggregate_by_component(records):
    """Rows (component, pass, ms_per_step, share) by time: device time
    by ``tracing.component`` and, in training, forward / backward."""
    groups = defaultdict(int)
    for r in records:
        groups[(r.get("component", "unattributed"),
                r.get("pass", ""))] += r["duration_ps"]
    n = records[0]["steps"] if records else 1
    total_ps = sum(groups.values())
    rows = [{"component": c, "pass": p, "ms": ps / 1e9 / n,
             "share": ps / total_ps if total_ps else 0.0}
            for (c, p), ps in groups.items()]
    rows.sort(key=lambda r: -r["ms"])
    return rows


def top_traffic_consumers(records, k=3):
    """The k op groups moving the most HBM bytes per step — the tool
    that finds layout transposes and unfused read passes (VERDICT r4
    task 7's acceptance probe)."""
    groups = defaultdict(lambda: [0, 0])
    for r in records:
        key = (r["module"], r["leaf_op"] or r["category"])
        groups[key][0] += r["bytes"]
        groups[key][1] += r["duration_ps"]
    n = records[0]["steps"] if records else 1
    rows = [{"module": m, "op": o, "gb": b / 1e9 / n,
             "ms": ps / 1e9 / n}
            for (m, o), (b, ps) in groups.items()]
    rows.sort(key=lambda r: -r["gb"])
    return rows[:k]


def format_profile(records, depth=3, top=25):
    """Reference print_model_profile-style table."""
    rows = aggregate_by_module(records, depth)
    n = records[0]["steps"] if records else 1
    tot_ms = sum(r["ms"] for r in rows)
    tot_gf = sum(r["gflops"] for r in rows)
    tot_gb = sum(r["gb"] for r in rows)
    out = [f"device time by component (measured device trace, {n} steps)",
           f"{'component':20s} {'pass':>5s} {'ms/step':>9s} {'share':>6s}"]
    for c in aggregate_by_component(records):
        out.append(f"{c['component']:20s} {c['pass']:>5s} {c['ms']:9.3f} "
                   f"{c['share']:6.1%}")
    out += [f"per-module profile (measured device trace, {n} steps)",
            f"{'module':44s} {'ms/step':>9s} {'GFLOP':>9s} "
            f"{'GB':>7s} {'share':>6s}"]
    for r in rows[:top]:
        out.append(f"{r['module'][:44]:44s} {r['ms']:9.3f} "
                   f"{r['gflops']:9.2f} {r['gb']:7.3f} "
                   f"{r['share']:6.1%}")
    out.append(f"{'TOTAL':44s} {tot_ms:9.3f} {tot_gf:9.2f} "
               f"{tot_gb:7.3f} {1:6.1%}")
    out.append("top HBM traffic consumers:")
    for t in top_traffic_consumers(records):
        out.append(f"  {t['gb']:7.3f} GB/step  {t['ms']:7.3f} ms  "
                   f"{t['module']}/{t['op']}")
    return "\n".join(out)
