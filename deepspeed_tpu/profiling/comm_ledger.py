"""Static HLO communication ledger: count collective ops and bytes per
mesh axis from *compiled* executables.

The span tracer (PR 8/9) answers "where did the time go" and the memory
tier (PR 11) "where did the pages go"; this pass answers **"how many
bytes does one dispatch move over which mesh axis"** — statically, from
the post-SPMD-partitioning HLO, so the numbers include every collective
GSPMD inserted (row-parallel psums, paged-KV gather/scatter loops,
argmax all-gathers), not just the ones written in source.

How it works
------------

1. ``jit(fn).lower(args).compile().as_text()`` — the optimized,
   partitioned HLO module (the same seam ``flops_profile()``'s cost
   analysis reads).
2. Parse every computation for collective instructions (``all-reduce``,
   ``all-gather``, ``reduce-scatter``, ``all-to-all``,
   ``collective-permute``, ``collective-broadcast``, and their async
   ``-start`` halves), with operand/output byte sizes and replica
   groups (literal ``{{0,1},...}`` and iota ``[G,S]<=[dims]T(perm)``
   forms).
3. Multiply by loop trip counts: a ``lax.scan`` horizon lowers to a
   ``while`` whose body holds the collectives ONCE — the executed
   truth is body × trip.  Trip counts come from XLA's own
   ``backend_config={"known_trip_count":...}`` (with a
   condition-constant fallback); an undeterminable loop multiplies by
   1 and is counted in ``unknown_trip_counts`` rather than silently
   under-reporting.
4. Attribute each group to mesh axes: partition ids index
   ``mesh.devices`` in flat order (the device-assignment order jax
   hands XLA), so the axes a group *varies over* are exactly the mesh
   axes the traffic rides.  Tier attribution: a group whose members
   span more than one process is **DCN**-tier, else **ICI** (on a
   hybrid multi-slice mesh the outer, slice-crossing axis is the
   process boundary — the rule needs only the mesh, no hardware
   introspection).

Byte definitions (shared with ``comm/telemetry.py`` and documented in
``docs/observability.md``): ``bytes`` is the per-device payload
(operand bytes; all-gather and broadcast count the full output since
their operand is the shard), ``wire_bytes`` is the busbw numerator of
the standard ring algorithms via :func:`comm.telemetry.wire_bytes`.
All figures are per device.
"""

import re

import numpy as np

from deepspeed_tpu.comm.telemetry import wire_bytes

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: HLO collective opcodes -> canonical op name.  ``-done`` halves are
#: skipped (the ``-start`` carries the operands).
_COLLECTIVE_OPS = {
    "all-reduce": "all_reduce",
    "all-reduce-start": "all_reduce",
    "all-gather": "all_gather",
    "all-gather-start": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "collective-permute": "collective_permute",
    "collective-permute-start": "collective_permute",
    "collective-broadcast": "broadcast",
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_HEAD_RE = re.compile(r"^(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*")
_OP_RE = re.compile(r"\s*(?P<op>[\w\-]+)\(")


def _match_instr(line):
    """``(name, shape, op, index of the op's name in line)`` of an HLO
    instruction line, or None.  A tuple shape is read to its matching
    parenthesis: the chip compiler's layouts hold parentheses of their
    own (``bf16[400,1600]{1,0:T(8,128)(2,1)}``)."""
    head = _INSTR_HEAD_RE.match(line)
    if head is None:
        return None
    i = end = head.end()
    if line[i:i + 1] == "(":
        depth = 0
        for end in range(i, len(line)):
            depth += (line[end] == "(") - (line[end] == ")")
            if depth == 0:
                break
        end += 1
    else:
        while end < len(line) and not line[end].isspace():
            end += 1
    op = _OP_RE.match(line, end)
    if op is None:
        return None
    return head.group("name"), line[i:end], op.group("op"), op.start("op")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\{\s*$")
_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{(\{[\d, ]*\}(?:, ?\{[\d, ]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[\d, ]*\}(?:, ?\{[\d, ]*\})*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*([0-9]+)')
_CALLEE_RE = {
    "body": re.compile(r"body=%?([\w.\-]+)"),
    "condition": re.compile(r"condition=%?([\w.\-]+)"),
    "to_apply": re.compile(r"to_apply=%?([\w.\-]+)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
    "true": re.compile(r"true_computation=%?([\w.\-]+)"),
    "false": re.compile(r"false_computation=%?([\w.\-]+)"),
    # a fusion or an async wrapper: the TPU compiler emits a
    # reduce-scatter as a fusion ``calls=%all-reduce-scatter`` of pad +
    # all-reduce + dynamic-slice, and wraps async collectives
    "calls": re.compile(r"\bcalls=%?([\w.\-]+)"),
}
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(s):
    """Total bytes of an HLO shape string (tuple shapes sum)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_bytes_max(s):
    """Largest single component of an HLO shape string.  Async
    ``-start`` ops return ``(operand alias, result, ...)`` tuples —
    summing would double-count the shard; the RESULT (the gathered/
    reduced buffer) is the largest component."""
    best = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n * _DTYPE_BYTES[dt])
    return best


def _parse_brace_groups(s):
    """``{0,1}, {2,3}`` -> [[0,1],[2,3]]."""
    return [[int(x) for x in g.split(",") if x.strip()]
            for g in re.findall(r"\{([\d, ]*)\}", s)]


def _iota_groups(groups_shape, dims, perm):
    """The v2 iota replica-group format: devices are
    ``transpose(reshape(arange(prod(dims)), dims), perm)`` flattened
    then reshaped to ``groups_shape``."""
    arr = np.arange(int(np.prod(dims))).reshape(dims)
    if perm is not None:
        arr = arr.transpose(perm)
    return arr.reshape(groups_shape).tolist()


def _split_operands(line, start):
    """Return (operand_str, attr_str): scan from the '(' at ``start``
    to its matching ')'; attrs follow."""
    depth = 0
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i], line[i + 1:]
    return line[start + 1:], ""


class _Collective:
    __slots__ = ("op", "bytes_in", "bytes_out", "groups", "pairs",
                 "shapes")

    def __init__(self, op, bytes_in, bytes_out, groups, pairs, shapes=""):
        self.op = op
        self.bytes_in = bytes_in
        self.bytes_out = bytes_out
        self.groups = groups      # list of lists of partition ids
        self.pairs = pairs        # collective-permute (src, dst) edges
        self.shapes = shapes      # operand and result shape strings


def _parse_module(text):
    """Split the HLO module into computations, each with its collective
    instructions, callee edges and while trip counts."""
    comps = {}
    entry = None
    name = None
    cur = None
    shapes = {}   # instruction name -> shape string, per computation
    for raw in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(raw)
            if m and "=" not in raw.split("(")[0]:
                name = m.group("name")
                if raw.lstrip().startswith("ENTRY"):
                    entry = name
                cur = {"collectives": [], "whiles": [], "calls": [],
                       "constants": [], "root_lt": False,
                       "parameters": ""}
                shapes = {}
            continue
        line = raw.strip()
        if raw.startswith("}") or line == "}":
            comps[name] = cur
            cur = None
            continue
        if not line or " = " not in line:
            continue
        m = _match_instr(line)
        if m is None:
            continue
        instr_name, instr_shape, op, op_at = m
        shapes[instr_name] = instr_shape
        if op == "parameter":
            cur["parameters"] += " " + instr_shape
        if "calls=" in line:
            cur["calls"].append(_CALLEE_RE["calls"].search(line).group(1))
        if op == "constant" or "constant(" in line:
            cur["constants"] += [int(x) for x in _CONST_RE.findall(line)]
        if "compare(" in line and "direction=LT" in line and \
                line.startswith("ROOT"):
            cur["root_lt"] = True
        if op == "while":
            body = _CALLEE_RE["body"].search(line)
            cond = _CALLEE_RE["condition"].search(line)
            trip = _TRIP_RE.search(line)
            cur["whiles"].append(
                (body.group(1) if body else None,
                 cond.group(1) if cond else None,
                 int(trip.group(1)) if trip else None))
            continue
        if op in ("call", "conditional"):
            if op == "conditional":
                cur["conditionals"] = cur.get("conditionals", 0) + 1
            for key in ("to_apply", "true", "false"):
                cm = _CALLEE_RE[key].search(line)
                if cm:
                    cur["calls"].append(cm.group(1))
            bm = _CALLEE_RE["branches"].search(line)
            if bm:
                cur["calls"] += [b.strip().lstrip("%")
                                 for b in bm.group(1).split(",") if b.strip()]
            continue
        if op not in _COLLECTIVE_OPS:
            continue
        paren = line.find("(", op_at)
        operands, attrs = _split_operands(line, paren)
        bytes_in = _shape_bytes(operands)
        if not bytes_in:
            # this XLA prints operands by name only (``all-reduce(%x)``):
            # their shapes are on the defining instructions, which
            # precede every use within a computation
            operands = " ".join(shapes.get(n, "") for n in
                                _OPERAND_NAME_RE.findall(operands))
            bytes_in = _shape_bytes(operands)
        groups = None
        gm = _GROUPS_LITERAL_RE.search(attrs)
        if gm:
            groups = _parse_brace_groups(gm.group(1))
        else:
            im = _GROUPS_IOTA_RE.search(attrs)
            if im:
                gshape = [int(x) for x in im.group(1).split(",")]
                dims = [int(x) for x in im.group(2).split(",")]
                perm = [int(x) for x in im.group(3).split(",")] \
                    if im.group(3) else None
                groups = _iota_groups(gshape, dims, perm)
        pairs = None
        pm = _PAIRS_RE.search(attrs)
        if pm:
            pairs = [tuple(p) for p in _parse_brace_groups(pm.group(1))]
        out_bytes = _shape_bytes_max(instr_shape) \
            if op.endswith("-start") else _shape_bytes(instr_shape)
        cur["collectives"].append(_Collective(
            _COLLECTIVE_OPS[op], bytes_in, out_bytes, groups, pairs,
            operands + " " + instr_shape))
    return comps, entry


def _trip_count(comps, body, cond, explicit):
    """Trip count of one while: XLA's known_trip_count when present,
    else the single integer constant of a canonical ``i < N``
    condition; None when undeterminable."""
    if explicit is not None:
        return explicit
    c = comps.get(cond)
    if c and c["root_lt"]:
        consts = sorted(set(c["constants"]))
        if len(consts) == 1:
            return consts[0]
    return None


def _multipliers(comps, entry):
    """Executed-times multiplier per computation from the call graph
    (HLO computations cannot recurse, so contribution propagation
    terminates).  Returns (multiplier map, unknown-trip count)."""
    mult = {c: 0 for c in comps}
    unknown = 0
    stack = [(entry, 1)]
    while stack:
        name, m = stack.pop()
        if name not in comps or m == 0:
            continue
        mult[name] += m
        comp = comps[name]
        for body, cond, explicit in comp["whiles"]:
            trip = _trip_count(comps, body, cond, explicit)
            if trip is None:
                unknown += 1
                trip = 1
            if body:
                stack.append((body, m * trip))
            if cond:
                stack.append((cond, m * trip))
        for callee in comp["calls"]:
            stack.append((callee, m))
    return mult, unknown


def _group_axes(groups, mesh_sizes, mesh_names):
    """Mesh axes the group traffic varies over -> a '+'-joined label
    ('' for trivial groups)."""
    varying = set()
    for g in groups:
        if len(g) < 2:
            continue
        base = np.unravel_index(int(g[0]), mesh_sizes)
        for pid in g[1:]:
            c = np.unravel_index(int(pid), mesh_sizes)
            for ax, a, b in zip(mesh_names, base, c):
                if a != b:
                    varying.add(ax)
    return "+".join(ax for ax in mesh_names if ax in varying)


def _group_tier(groups, procs):
    """'dcn' when any group spans more than one OS process, else
    'ici' — the hybrid-mesh tier attribution rule."""
    for g in groups:
        if len({procs[int(p)] for p in g if int(p) < len(procs)}) > 1:
            return "dcn"
    return "ici"


def ledger_from_hlo(text, mesh=None):
    """Build the communication ledger of one compiled HLO module.

    Returns a plain dict (JSON-ready): trip-weighted per-device totals
    (``collectives``, ``bytes``, ``wire_bytes``), the per-op split
    (``per_op``), per-mesh-axis wire bytes (``per_axis`` — multi-axis
    groups key as ``'data+model'``), the per-(axis, op) breakdown
    (``per_axis_op``), ICI/DCN tier wire bytes (``per_tier``), the
    static instruction count and ``unknown_trip_counts``."""
    comps, entry = _parse_module(text)
    mult, unknown = _multipliers(comps, entry) if entry is not None \
        else ({c: 1 for c in comps}, 0)
    # conditionals: every branch is counted as if executed (an upper
    # bound — exactly one branch runs per dispatch), so the overcount
    # is FLAGGED rather than silent, like unknown_trip_counts
    conditionals = sum(c.get("conditionals", 0) * mult.get(n, 0)
                       for n, c in comps.items())
    if mesh is not None:
        mesh_sizes = tuple(int(s) for s in mesh.devices.shape)
        mesh_names = tuple(str(a) for a in mesh.axis_names)
        procs = [getattr(d, "process_index", 0)
                 for d in np.asarray(mesh.devices).flat]
    else:
        mesh_sizes = mesh_names = procs = None
    out = {"instructions": 0, "collectives": 0, "bytes": 0,
           "wire_bytes": 0, "per_op": {}, "per_axis": {},
           "per_axis_op": {}, "per_tier": {"ici": 0, "dcn": 0},
           "unknown_trip_counts": unknown,
           "conditional_branches": int(conditionals)}
    for name, comp in comps.items():
        m = mult.get(name, 0)
        for c in comp["collectives"]:
            if m == 0:
                continue
            out["instructions"] += 1
            groups = c.groups
            if groups is None and c.pairs is not None:
                # permute edges: groups of the communicating pairs
                groups = [[s, d] for s, d in c.pairs if s != d]
            if not groups:
                continue
            n = max(len(g) for g in groups) if c.pairs is None else 2
            if c.pairs is not None:
                # per sending device: payload leaves only on non-self
                # edges; average over the participating senders
                nonself = sum(1 for s, d in c.pairs if s != d)
                frac = nonself / max(len(c.pairs), 1)
                payload = int(c.bytes_in * frac)
                wire = payload
            else:
                payload = c.bytes_out \
                    if c.op in ("all_gather", "broadcast") else c.bytes_in
                wire = wire_bytes(c.op, c.bytes_in, c.bytes_out, n)
            axis = "" if mesh_names is None else \
                _group_axes(groups, mesh_sizes, mesh_names)
            axis = axis or "replicated"
            tier = "ici" if procs is None else _group_tier(groups, procs)
            out["collectives"] += m
            out["bytes"] += m * payload
            out["wire_bytes"] += m * wire
            po = out["per_op"].setdefault(
                c.op, {"count": 0, "bytes": 0, "wire_bytes": 0})
            po["count"] += m
            po["bytes"] += m * payload
            po["wire_bytes"] += m * wire
            out["per_axis"][axis] = out["per_axis"].get(axis, 0) + m * wire
            pao = out["per_axis_op"].setdefault(axis, {})
            pa = pao.setdefault(c.op, {"count": 0, "bytes": 0,
                                       "wire_bytes": 0})
            pa["count"] += m
            pa["bytes"] += m * payload
            pa["wire_bytes"] += m * wire
            out["per_tier"][tier] += m * wire
    return out


def _dims(s):
    return tuple(int(d) for d in s.split(",") if d)


def collective_census(text, param_shapes=(), mesh=None):
    """Which collectives a compiled program holds, by the class of what
    they move: ``param`` where an operand or a result has the dimensions
    of one of ``param_shapes`` (an iterable of shape tuples: a
    parameter's whole shape and its shards), ``other`` where none has —
    an activation, a scalar.  Trip-weighted like the ledger.

    Returns ``{"per_op": {op: {cls: {"count", "bytes", "dtypes",
    "axes"}}}, "other_shapes": {"<op> <dtype>[dims]": bytes}}``:
    ``bytes`` is the ledger's per-device payload, ``dtypes`` the payload
    split by the element type of the LARGEST component (what a gather
    moves, what a gradient is summed in), ``axes`` the payload by the
    mesh axes the groups span (``mesh`` given), ``other_shapes`` the ten
    heaviest movers that are no parameter's, by name."""
    known = {tuple(int(d) for d in sh) for sh in param_shapes}
    known.discard(())
    comps, entry = _parse_module(text)
    mult, _ = _multipliers(comps, entry) if entry is not None \
        else ({c: 1 for c in comps}, 0)
    per_op, others = {}, {}
    if mesh is not None:
        mesh_sizes = tuple(int(n) for n in mesh.devices.shape)
        mesh_names = tuple(str(a) for a in mesh.axis_names)
    for name, comp in comps.items():
        m = mult.get(name, 0)
        # the TPU compiler's reduce-scatter: a fusion of pad +
        # all-reduce + dynamic-slice whose own parameter has the
        # gradient's shape (the all-reduce's is padded to the tiling)
        fused_rs = name.startswith("all-reduce-scatter")
        for c in comp["collectives"] if m else ():
            shapes = c.shapes + (comp["parameters"] if fused_rs else "")
            parts = [(dt, _dims(dims)) for dt, dims in
                     _SHAPE_RE.findall(shapes) if dt in _DTYPE_BYTES]
            if not parts:
                continue
            cls = "param" if any(d in known for _, d in parts) else "other"
            op = "reduce_scatter" if fused_rs and c.op == "all_reduce" \
                else c.op
            payload = c.bytes_out \
                if op in ("all_gather", "broadcast") else c.bytes_in
            dt, dims = max(parts, key=lambda p: int(np.prod(p[1] or (1,)))
                           * _DTYPE_BYTES[p[0]])
            slot = per_op.setdefault(op, {}).setdefault(
                cls, {"count": 0, "bytes": 0, "dtypes": {}, "axes": {}})
            slot["count"] += m
            slot["bytes"] += m * payload
            slot["dtypes"][dt] = slot["dtypes"].get(dt, 0) + m * payload
            groups = c.groups or [list(p) for p in c.pairs or ()
                                  if p[0] != p[1]]
            if mesh is not None and groups:
                axis = _group_axes(groups, mesh_sizes, mesh_names) \
                    or "replicated"
                slot["axes"][axis] = slot["axes"].get(axis, 0) + m * payload
            if cls == "other":
                key = f"{op} {dt}[{','.join(map(str, dims))}]"
                others[key] = others.get(key, 0) + m * payload
    top = sorted(others.items(), key=lambda kv: -kv[1])[:10]
    return {"per_op": per_op, "other_shapes": dict(top)}


def ledger_for(fn, *args, mesh=None, static_argnums=(), **kwargs):
    """Ledger of ``fn`` compiled for the given args (concrete arrays or
    ShapeDtypeStructs carrying shardings) — the comm twin of
    ``profiling.flops_profiler.cost_analysis``, reading the same
    lower->compile seam."""
    import jax
    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, static_argnums=static_argnums)
    compiled = jitted.lower(*args, **kwargs).compile()
    return ledger_from_hlo(compiled.as_text(), mesh=mesh)


def merge_ledgers(ledgers):
    """Sum ledgers (e.g. the gas>1 micro/boundary executables of one
    optimizer step, each pre-scaled with :func:`scale_ledger`)."""
    out = None
    for led in ledgers:
        if out is None:
            out = scale_ledger(led, 1)
            continue
        for k in ("instructions", "collectives", "bytes", "wire_bytes",
                  "unknown_trip_counts", "conditional_branches"):
            out[k] += led.get(k, 0)
        for op, v in led["per_op"].items():
            po = out["per_op"].setdefault(
                op, {"count": 0, "bytes": 0, "wire_bytes": 0})
            for k in po:
                po[k] += v[k]
        for ax, v in led["per_axis"].items():
            out["per_axis"][ax] = out["per_axis"].get(ax, 0) + v
        for ax, ops in led["per_axis_op"].items():
            pao = out["per_axis_op"].setdefault(ax, {})
            for op, v in ops.items():
                pa = pao.setdefault(op, {"count": 0, "bytes": 0,
                                         "wire_bytes": 0})
                for k in pa:
                    pa[k] += v[k]
        for t in ("ici", "dcn"):
            out["per_tier"][t] += led["per_tier"][t]
    return out


def scale_ledger(ledger, k):
    """``ledger`` with every count/byte figure multiplied by ``k``
    (gradient-accumulation micro repeats)."""
    out = {"instructions": ledger["instructions"] * k,
           "collectives": ledger["collectives"] * k,
           "bytes": ledger["bytes"] * k,
           "wire_bytes": ledger["wire_bytes"] * k,
           "per_op": {op: {kk: vv * k for kk, vv in v.items()}
                      for op, v in ledger["per_op"].items()},
           "per_axis": {ax: v * k for ax, v in ledger["per_axis"].items()},
           "per_axis_op": {ax: {op: {kk: vv * k for kk, vv in v.items()}
                                for op, v in ops.items()}
                           for ax, ops in ledger["per_axis_op"].items()},
           "per_tier": {t: v * k for t, v in ledger["per_tier"].items()},
           "unknown_trip_counts": ledger["unknown_trip_counts"] * k,
           "conditional_branches":
               ledger.get("conditional_branches", 0) * k}
    return out
