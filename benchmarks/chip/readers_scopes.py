"""Device time by model component.

Every "XLA Ops" event of a device plane carries, on its METADATA, the
``tf_op`` stat: the ``op_name`` of the instruction it ran, which is the
name stack the program traced it under (flax module names and
``jax.named_scope``s: ``jit(decode_multi)/horizon/while/body/
closed_call/Llama/layers_3/mlp/w_down/dot_general:``).
``jax.profiler.ProfileData`` shows an event's own stats only (offset,
duration), so this module reads the run's ``.xplane.pb`` through the
program's raw decoder, ``deepspeed_tpu/profiling/xplane.py`` -- the one
``engine.module_profile()`` and ``ds_serve --profile-steps`` read -- and
resolves each event to a component of ``scope_vocabulary.json``, the
benchmark's copy of ``deepspeed_tpu/tracing.py``'s ``COMPONENTS``:

* of an operation merged from several (``a/mul;b/add``) the first path
  is read; split it on ``/`` (an argument's name,
  ``params['layers_3']['moe']['w_up']`` on a copy of that weight, by
  its keys); the INNERMOST part that is a token of the vocabulary
  decides (``layers_3`` is tried as ``layers``; autodiff's
  ``transpose(jvp(loss))`` as ``loss``; ``jit(name)`` is a function's
  name and never a token);
* a collective is ``comm`` by its opcode whatever its path;
* a path with no token is ``other``; an event with no path (an
  operation the compiler made itself: a copy, an asynchronous slice of
  a weight), or with a path that ends at a loop or a call (the loop's
  own metadata on such an operation beside it), is ``unattributed``;
* loops, conditionals and calls are left out, as ``Trace.top_ops``
  leaves them out: their events span the operations inside them.

The components partition the remaining events: the sum over components
is the sum of those events' durations.  A fusion counts where XLA put
its metadata (PERF.md section 7).  A file whose events carry no path at
all reads None everywhere.

    python benchmarks/chip/readers_scopes.py <file.xplane.pb> [groups]

prints seconds by component and, inside each, the operation groups
(``readers.short_name``) with most time.
"""

import functools
import glob
import json
import os
import re
import sys

import readers
import readers_spans

HERE = os.path.dirname(os.path.abspath(__file__))
_PARSED = {}
_LAYER_INDEX = re.compile(r"_\d+$")
_WRAPPED = re.compile(r"^(?:(?!jit\()\w+\()+([^()]*)\)+$")
_ARGUMENT = re.compile(r"^(\w+)((?:\[[^\]]*\])+)$")
_CONTROL_FLOW = ("while", "body", "cond", "closed_call")


@functools.lru_cache(maxsize=None)
def vocabulary():
    """(component -> tokens in the program's order, collective opcodes)."""
    with open(os.path.join(HERE, "scope_vocabulary.json")) as f:
        v = json.load(f)
    return v["components"], tuple(v["collective_opcodes"])


def classifier(components, collectives):
    """``(path, opcode) -> component`` under the rule above."""
    token = {t: c for c, toks in components.items() for t in toks}

    def component(path, opcode=""):
        if opcode.startswith(collectives):
            return "comm"
        path = path.split(";")[0].rstrip(":")
        arg = _ARGUMENT.match(path)
        parts = [arg.group(1)] + re.findall(r"\['([^']*)'\]", arg.group(2)) \
            if arg else path.split("/")
        if not path or parts[-1] in _CONTROL_FLOW or \
                parts[-1].startswith("branch_"):
            return "unattributed"
        for part in reversed(parts):
            part = _WRAPPED.sub(r"\1", part)
            hit = token.get(part) or token.get(_LAYER_INDEX.sub("", part))
            if hit is not None:
                return hit
        return "other"
    return component


def device_events(path):
    """{device plane: {(event name, tf_op): summed duration_ps}} of the
    "XLA Ops" lines of one ``.xplane.pb``, loops, conditionals and calls
    left out: a few thousand distinct operations for some hundred
    thousand events, so that every metric of a run classifies the
    operations and not the events.  Parsed once a process."""
    if path not in _PARSED:
        from deepspeed_tpu.profiling.xplane import read_xspace
        out = {}
        for plane in read_xspace(path):
            if not plane.name.startswith("/device:TPU:"):
                continue
            by_id = {}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        by_id[ev.metadata_id] = \
                            by_id.get(ev.metadata_id, 0) + ev.duration_ps
            ops = out[plane.name] = {}
            for mid, ps in by_id.items():
                name = plane.event_names.get(mid, "")
                if readers.own_name(name) in readers.WRAPPERS:
                    continue
                key = (name, plane.event_stats.get(mid, {}).get("tf_op")
                       or "")
                ops[key] = ops.get(key, 0) + ps
        _PARSED[path] = out
    return _PARSED[path]


def component_seconds(path, component=None):
    """{component: seconds, mean over the file's device planes}, or
    None where no event of the file carries a path."""
    planes = device_events(path)
    if not any(tf_op for ops in planes.values() for _, tf_op in ops):
        return None
    component = component or classifier(*vocabulary())
    acc = {}
    for ops in planes.values():
        for (name, tf_op), ps in ops.items():
            c = component(tf_op, readers.own_name(name))
            acc[c] = acc.get(c, 0) + ps
    return {c: ps / len(planes) / 1e12 for c, ps in acc.items()}


def trace_file(ctx):
    """This run's ``.xplane.pb``: the newest under the harness's trace
    directory, if ``readers_spans.program_spans`` takes it for this
    run's (its ``bench.*`` events are the context's trace's)."""
    if ctx["trace"] is None or readers_spans.program_spans(ctx) is None:
        return None
    paths = glob.glob(os.path.join(
        readers_spans.TRACE_ROOT, "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(paths, key=os.path.getmtime)


def time_share(ctx, component, tokens=()):
    """100 x (seconds of the device events whose path ``tokens`` make
    ``component``'s) / the traced window, mean over the cell's chips;
    ``unattributed`` is ``other`` + ``unattributed``.  0 where the file
    has paths and none is this component's, None where it has none."""
    path = trace_file(ctx)
    if path is None or ctx["trace"].window_s <= 0:
        return None
    components, collectives = vocabulary()
    components = dict(components, **{component: list(tokens)})
    secs = component_seconds(path, classifier(components, collectives))
    if secs is None:
        return None
    mine = ("other", "unattributed") if component == "unattributed" \
        else (component,)
    return 100.0 * sum(secs.get(c, 0.0) for c in mine) / \
        ctx["trace"].window_s


def describe(path, groups=6):
    """[[component, seconds, [[operation group, seconds], ...]], ...] by
    seconds, for reading a trace by hand."""
    component = classifier(*vocabulary())
    planes = device_events(path)
    acc = {}
    for ops in planes.values():
        for (name, tf_op), ps in ops.items():
            c = acc.setdefault(component(tf_op, readers.own_name(name)), {})
            key = readers.short_name(name)
            c[key] = c.get(key, 0) + ps
    scale = max(1, len(planes)) * 1e12
    rows = [[c, sum(g.values()) / scale,
             [[k, v / scale] for k, v in
              sorted(g.items(), key=lambda kv: -kv[1])[:groups]]]
            for c, g in acc.items()]
    return sorted(rows, key=lambda r: -r[1])


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    for comp, secs, top in describe(
            sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6):
        print(f"{secs:9.4f} s  {comp}")
        for group, s in top:
            print(f"    {s:9.4f}  {group}")
