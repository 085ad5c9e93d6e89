"""Sharding rules: logical param axes -> mesh axes, plus ZeRO staging.

This module is the TPU replacement for the reference's partition bookkeeping
(``runtime/zero/stage_1_and_2.py``, ``stage3.py``, ``partition_parameters.py``):
instead of slicing flat buffers and tracking ownership, each array in the
train state gets a ``NamedSharding``.

Models annotate params with *logical* axis names (flax
``nn.with_partitioning``). ``logical_to_mesh_axes`` maps them through
t5x-style rules; ZeRO stages then add `data`-axis sharding:

  stage 1 — optimizer state sharded over `data`
  stage 2 — + gradient accumulator sharded over `data`
  stage 3 — + parameters sharded over `data` (fsdp)

Which spec a stage-3 leaf has where, and where its collectives come from:

* AT REST (the train state, between steps and on entry to the step) a
  parameter has ``param_pspec(..., zero_stage=3)``: its tensor-parallel
  axes plus `data` on its largest dimension that divides, unless it is
  smaller than the persistence threshold or nothing divides (then it stays
  replicated over `data`).  The engine's cast to the compute dtype is
  elementwise and keeps that spec.
* AT USE it has ``param_pspec(..., zero_stage=2)``: the tensor-parallel axes
  alone.  These specs do not make the compiler gather anything by
  themselves — left to GSPMD, a step keeps the weights sharded and moves the
  batch instead (all-to-alls of activations around every matmul; PERF.md §6
  PR 29).  The all-gather comes from ``runtime/zero/gather.py``: the matmul
  or lookup that consumes a leaf constrains its cast shard to the at-use
  spec where it runs (reference `stage3.py:1076`, "allgathered
  just-in-time"), and pins the activations' batch to `data`.
* THE GRADIENT of such a leaf is computed in float32 on each chip and
  constrained to the at-rest spec by the same op's backward, which is the
  reduce-scatter (reference `stage_1_and_2.py:894`); the engine's
  ``grad_pspecs`` (the optimizer-state specs from stage 2 up) then hold it
  for the update.  Leaves the plan does not hold (persisted, or consumed by
  no such op) get whatever GSPMD derives from their specs, as before.
"""

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default logical-axis rules (logical name -> mesh axis). First match wins;
# an axis already taken by another dim of the same param is skipped.
DEFAULT_LOGICAL_AXIS_RULES = (
    ("batch", "data"),
    ("pipe", "pipe"),
    ("vocab", "model"),
    ("embed", None),
    ("heads", "model"),
    ("kv", None),
    ("mlp", "model"),
    ("expert", "expert"),
    ("expert_mlp", "model"),
    ("seq", "sequence"),
    ("layers", None),
    ("stack", None),
    ("norm", None),
)


def logical_to_mesh_axes(logical_spec, rules=DEFAULT_LOGICAL_AXIS_RULES):
    """Map a tuple of logical axis names to mesh axis names (or None)."""
    if logical_spec is None:
        return None
    rules_d = dict(rules)
    out = []
    used = set()
    for name in logical_spec:
        ax = rules_d.get(name) if name is not None else None
        if ax is not None and ax in used:
            ax = None
        if ax is not None:
            used.add(ax)
        out.append(ax)
    return tuple(out)


def _axis_size(mesh, axis):
    return mesh.shape[axis] if axis in mesh.shape else 1


def add_fsdp_axis(spec, shape, mesh, fsdp_axis="data"):
    """Add `fsdp_axis` to the largest divisible, not-yet-sharded dim of spec.

    This is the ZeRO partitioning decision: the reference flattens and
    slices 1/world per rank (`partition_parameters.py:224`); here we shard a
    whole dimension so the array stays a clean XLA tile.
    """
    size = _axis_size(mesh, fsdp_axis)
    if size == 1 or not shape:
        return spec
    spec = list(spec) if spec is not None else [None] * len(shape)
    spec += [None] * (len(shape) - len(spec))
    used = {a for s in spec if s is not None for a in ((s,) if isinstance(s, str) else s)}
    if fsdp_axis in used:
        return tuple(spec)
    # pick the largest dim divisible by the axis size that is unsharded
    best, best_dim = -1, -1
    for i, (d, s) in enumerate(zip(shape, spec)):
        if s is None and d % size == 0 and d > best:
            best, best_dim = d, i
    if best_dim < 0:
        return tuple(spec)  # nothing divisible: leave replicated
    spec[best_dim] = fsdp_axis
    return tuple(spec)


def _base_pspec(logical_spec, shape, mesh, zero_stage, min_fsdp_stage, rules,
                fsdp_axis):
    """TP spec from logical names + `data`-axis sharding once the ZeRO stage
    reaches the threshold (params at stage 3, optimizer state at stage 1)."""
    mesh_axes = logical_to_mesh_axes(logical_spec, rules)
    if mesh_axes is None:
        mesh_axes = (None,) * len(shape)
    # drop axes that don't divide the dim (tiny fixtures / odd vocab)
    mesh_axes = tuple(
        a if (a is None or (dim % _axis_size(mesh, a) == 0 and _axis_size(mesh, a) > 1)) else None
        for a, dim in zip(mesh_axes, shape))
    if zero_stage >= min_fsdp_stage:
        mesh_axes = add_fsdp_axis(mesh_axes, shape, mesh, fsdp_axis)
    return P(*mesh_axes)


def param_pspec(logical_spec, shape, mesh, zero_stage=0, rules=DEFAULT_LOGICAL_AXIS_RULES,
                fsdp_axis="data", persist_threshold=0):
    """PartitionSpec for a parameter under TP rules + ZeRO stage.

    ``persist_threshold`` is the reference's
    ``stage3_param_persistence_threshold`` (zero/config.py): parameters
    with fewer elements stay replicated over the fsdp axis (their
    all-gather would cost more latency than the memory saved). TP axes
    still apply — persistence is a ZeRO decision only."""
    if persist_threshold and int(np.prod(shape or (1,))) < persist_threshold:
        zero_stage = min(zero_stage, 2)
    return _base_pspec(logical_spec, shape, mesh, zero_stage, 3, rules, fsdp_axis)


def optstate_pspec(logical_spec, shape, mesh, zero_stage=0,
                   rules=DEFAULT_LOGICAL_AXIS_RULES, fsdp_axis="data"):
    """PartitionSpec for optimizer state mirroring a parameter."""
    return _base_pspec(logical_spec, shape, mesh, zero_stage, 1, rules, fsdp_axis)


def get_logical_specs(variables):
    """Extract logical PartitionSpecs from a flax params tree with
    nn.Partitioned metadata; plain arrays get None."""
    import flax.linen as nn

    def f(x):
        if isinstance(x, nn.Partitioned):
            return x.names
        return None

    return jax.tree.map(f, variables,
                        is_leaf=lambda x: isinstance(x, nn.Partitioned))


def unbox(variables):
    """Strip flax Partitioned boxes -> raw arrays."""
    import flax.linen as nn
    return jax.tree.map(
        lambda x: x.value if isinstance(x, nn.Partitioned) else x, variables,
        is_leaf=lambda x: isinstance(x, nn.Partitioned))


def tree_param_shardings(mesh, shapes, logical_specs, zero_stage=0,
                         rules=DEFAULT_LOGICAL_AXIS_RULES):
    """NamedSharding tree for params."""
    return jax.tree.map(
        lambda sh, sp: NamedSharding(
            mesh, param_pspec(sp, sh.shape, mesh, zero_stage, rules)),
        shapes, logical_specs,
        is_leaf=lambda x: x is None or isinstance(x, tuple))


def tree_pspecs(mesh, shapes, logical_specs, zero_stage, kind,
                rules=DEFAULT_LOGICAL_AXIS_RULES, persist_threshold=0):
    """PartitionSpec tree for params ('param') or optimizer state ('opt').
    ``persist_threshold`` applies to params only (see param_pspec)."""
    if kind == "param":
        def leaf(sh, sp):
            return param_pspec(sp, sh.shape, mesh, zero_stage, rules,
                               persist_threshold=persist_threshold)
    else:
        def leaf(sh, sp):
            return optstate_pspec(sp, sh.shape, mesh, zero_stage, rules)

    return jax.tree.map(leaf, shapes, logical_specs,
                        is_leaf=lambda x: x is None or isinstance(x, tuple))


def opt_state_pspecs(opt_state_shapes, params_shapes, params_pspecs):
    """PartitionSpec tree for an optax opt_state.

    Optimizer moments (adam mu/nu, momentum trace, ...) are sub-trees with
    the *same tree structure* as the param tree, so they are detected
    structurally and get the param specs position-for-position — robust even
    when two same-shaped params carry different specs. Remaining leaves
    (step counters, scalars) are replicated.
    """
    pdef = jax.tree.structure(params_shapes)
    pshapes = [tuple(s.shape) for s in jax.tree.leaves(params_shapes)]
    pspecs_flat = jax.tree.leaves(params_pspecs, is_leaf=lambda x: isinstance(x, P))
    specs_tree = jax.tree.unflatten(pdef, pspecs_flat)

    def is_params_like(x):
        try:
            if jax.tree.structure(x) != pdef:
                return False
            return [tuple(l.shape) for l in jax.tree.leaves(x)] == pshapes
        except Exception:
            return False

    def f(node):
        if is_params_like(node):
            return specs_tree
        return P()

    return jax.tree.map(f, opt_state_shapes, is_leaf=is_params_like)


def apply_shardings(tree, mesh, pspecs):
    """device_put a pytree with NamedShardings from a PartitionSpec tree."""
    flat, treedef = jax.tree.flatten(tree)
    flat_specs = treedef.flatten_up_to(pspecs)
    out = [jax.device_put(x, NamedSharding(mesh, p)) for x, p in zip(flat, flat_specs)]
    return jax.tree.unflatten(treedef, out)


def tree_shardings(mesh, pspecs):
    """NamedSharding tree from a PartitionSpec tree."""
    return jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
