"""ZeRO-3 gather-at-use: a parameter sharded over the fsdp (`data`) axis
is all-gathered where it is used, and the batch stays on `data`.

What stage 3 means in the reference ("params freed after use,
allgathered just-in-time", `partitioned_param_coordinator.py:218`), as
sharding constraints the compiler cannot argue with.  A leaf AT REST
carries its stage-3 ``PartitionSpec`` (sharded over `data`; the engine
casts the float32 master on the shard); AT USE it carries its stage-2
spec, the tensor-parallel axes alone.  The ops below take the AT-REST
leaf and do the rest themselves:

* forward: constrain the compute-dtype shard to the at-use spec (an
  all-gather of compute-dtype bytes), then the matmul / the lookup.
  Inside ``nn.remat`` the gathered copy dies with the block and the
  recomputed forward gathers again for the backward.
* backward: the WEIGHT gradient is computed in float32 on each chip (the
  matmul accumulates in float32 anyway; here it also writes float32) and
  constrained to the at-rest spec, so the chips' partial sums meet in a
  float32 reduce-scatter by construction, not in whatever all-reduce a
  partitioned low-precision dot would have been given.
* :meth:`GatherPlan.pin_batch` constrains an activation's leading
  dimension to the batch axis, so GSPMD has no cheaper-looking plan that
  keeps the weights where they are and moves the batch instead (what it
  chose before: all-to-alls of activations around every matmul).

The engine builds one :class:`GatherPlan` from what it can observe (the
leaves' own specs) and hands it to the model through :func:`scope`
around the trace of its loss, the way ``comm.mesh_scope`` hands over the
mesh; ``QDense`` and the models' embedding / head ask :func:`active` for
it.  No plan, or a plan without leaves (stage <= 2, a `data` axis of
size 1, everything under the persistence threshold): :func:`active`
returns None and every caller builds exactly the program it built
before.  A planned leaf that no such op consumes (an expert's weights, a
norm's scale) keeps its at-rest spec and is left to GSPMD, as before.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

_ACTIVE = None


def _axes_of(spec):
    """Mesh axis names a PartitionSpec uses."""
    return {a for e in spec if e is not None
            for a in ((e,) if isinstance(e, str) else e)}


def _path_key(path):
    """A jax key path as a tuple of plain strings (flax's own form)."""
    return tuple(str(getattr(k, "key", getattr(k, "idx", getattr(
        k, "name", k)))) for k in path)


def _to_use(w, at_rest, at_use):
    # pinned to the shard first: left free, an elementwise producer takes
    # the gathered sharding and the all-gather moves what came before it
    with jax.named_scope("zero_gather"):
        w = jax.lax.with_sharding_constraint(w, at_rest)
        return jax.lax.with_sharding_constraint(w, at_use)


def _to_rest(g, at_rest, dtype):
    """A float32 weight gradient into the at-rest spec (the
    reduce-scatter), then the leaf's own dtype."""
    assert g.dtype == jnp.float32, g.dtype
    with jax.named_scope("zero_gather"):
        return jax.lax.with_sharding_constraint(g, at_rest).astype(dtype)


@functools.lru_cache(maxsize=None)
def _einsum_fn(spec, at_rest, at_use):
    """``einsum(spec, x, w)`` with ``w`` gathered at use.  Every index of
    ``spec`` ("a,b->c") is in exactly two of a, b, c (a matmul), so the
    two gradients are einsums too."""
    lhs, out = spec.split("->")
    a, b = lhs.split(",")
    assert all((i in a) + (i in b) + (i in out) == 2
               for i in set(a + b + out)), spec

    @jax.custom_vjp
    def f(x, w):
        return jnp.einsum(spec, x, _to_use(w, at_rest, at_use))

    def fwd(x, w):
        # the gathered copy is the residual: inside ``nn.remat`` it is
        # the recomputed forward's, made in the backward and dead with
        # the block (two gathers a step); outside, it lives from forward
        # to backward like any activation
        w_use = _to_use(w, at_rest, at_use)
        return jnp.einsum(spec, x, w_use), \
            (x, w_use, jnp.zeros((0,), w.dtype))

    def bwd(res, ct):
        x, w_use, like = res
        dx = jnp.einsum(f"{out},{b}->{a}", ct, w_use)
        dw = jnp.einsum(f"{a},{out}->{b}", x, ct,
                        preferred_element_type=jnp.float32)
        return dx.astype(x.dtype), _to_rest(dw, at_rest, like.dtype)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _take_fn(at_rest, at_use, rows):
    """``w[ids]`` (of a table of ``rows`` rows) with ``w`` gathered at
    use."""

    @jax.custom_vjp
    def f(w, ids):
        return _to_use(w, at_rest, at_use)[ids]

    def fwd(w, ids):
        return f(w, ids), (ids, jnp.zeros((0,) + w.shape[1:], w.dtype))

    def bwd(res, ct):
        ids, like = res
        dw = jnp.zeros((rows,) + like.shape[1:], jnp.float32).at[
            ids.reshape(-1)].add(ct.astype(jnp.float32).reshape(
                (-1,) + like.shape[1:]))
        # each chip scatters its own tokens' rows: pinned to the at-use
        # spec first, or GSPMD moves the batch's cotangent onto the
        # table's shards (an all-to-all of an activation) instead
        dw = jax.lax.with_sharding_constraint(dw, at_use)
        return (_to_rest(dw, at_rest, like.dtype),
                np.zeros(ids.shape, jax.dtypes.float0))

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _gather_fn(at_rest, at_use):
    """A leaf used elementwise (a bias), gathered at use."""

    @jax.custom_vjp
    def f(w):
        return _to_use(w, at_rest, at_use)

    def fwd(w):
        return f(w), jnp.zeros((0,), w.dtype)

    def bwd(like, ct):
        return (_to_rest(ct.astype(jnp.float32), at_rest, like.dtype),)

    f.defvjp(fwd, bwd)
    return f


class GatherPlan:
    """Which leaves are gathered at use, from which spec into which.

    ``at_rest`` / ``at_use`` are PartitionSpec trees over the engine's
    parameter tree (stage-3 and stage-2 specs of the same leaves); a
    leaf is in the plan when its at-rest spec holds ``fsdp_axis`` and
    its at-use spec does not.  Leaves stage 3 left replicated (the
    persistence threshold, dimensions that do not divide) are not.
    """

    def __init__(self, mesh, shapes, at_rest, at_use, fsdp_axis="data",
                 batch_axis="data"):
        self.mesh = mesh
        self.leaves = {}
        is_spec = lambda x: isinstance(x, P)
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
        rest = jax.tree.leaves(at_rest, is_leaf=is_spec)
        use = jax.tree.leaves(at_use, is_leaf=is_spec)
        for (path, sds), r, u in zip(flat, rest, use):
            if mesh.shape.get(fsdp_axis, 1) > 1 and \
                    fsdp_axis in _axes_of(r) and fsdp_axis not in _axes_of(u):
                pad = (None,) * (len(sds.shape) - len(r))
                self.leaves[_path_key(path)] = (
                    tuple(r) + pad, tuple(u) + pad, tuple(sds.shape))
        size = mesh.shape.get(batch_axis, 1)
        self._batch = (batch_axis, size) if size > 1 else None
        self.gathered = {}      # path -> bytes one gather moves, as traced

    def __bool__(self):
        return bool(self.leaves)

    def _shardings(self, w, path):
        """(at-rest, at-use) NamedShardings of the leaf at ``path`` as
        the caller holds it — whole, or one layer's slice of a leaf
        stacked over a leading ``nn.scan`` axis — or None where the plan
        does not hold it."""
        hit = self.leaves.get(tuple(path))
        if hit is None or not hasattr(w, "dtype"):
            return None
        rest, use, shape = hit
        if tuple(jnp.shape(w)) == shape[1:] and rest[0] is None:
            rest, use, shape = rest[1:], use[1:], shape[1:]
        if tuple(jnp.shape(w)) != shape:
            return None
        self.gathered[tuple(path)] = \
            int(np.prod(hit[2])) * jnp.dtype(w.dtype).itemsize
        return (NamedSharding(self.mesh, P(*rest)),
                NamedSharding(self.mesh, P(*use)))

    # --------------------------------------------------------- the ops
    def einsum(self, spec, x, w, path):
        """``jnp.einsum(spec, x, w)`` for the parameter ``w`` at ``path``."""
        sh = self._shardings(w, path)
        if sh is None:
            return jnp.einsum(spec, x, w)
        return _einsum_fn(spec, *sh)(x, w)

    def take(self, w, ids, path):
        """``w[ids]`` for the table ``w`` at ``path``."""
        sh = self._shardings(w, path)
        if sh is None:
            return w[ids]
        return _take_fn(*sh, w.shape[0])(w, ids)

    def gather(self, w, path):
        """The parameter ``w`` at ``path`` as an elementwise op uses it."""
        sh = self._shardings(w, path)
        return w if sh is None else _gather_fn(*sh)(w)

    def pin_batch(self, x):
        """Constrain ``x``'s leading dimension to the batch axis and
        leave the others to the compiler."""
        if self._batch is None or not jnp.ndim(x) or \
                jnp.shape(x)[0] % self._batch[1]:
            return x
        spec = P(self._batch[0], *([P.UNCONSTRAINED] * (jnp.ndim(x) - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def summary(self):
        """Leaves and bytes gathered at use, as the last trace saw them."""
        return {"planned_leaves": len(self.leaves),
                "gathered_leaves": len(self.gathered),
                "gathered_bytes": int(sum(self.gathered.values()))}


class scope:
    """Install ``plan`` as the active gather plan while a loss traces
    (None, or a plan without leaves, installs nothing)."""

    def __init__(self, plan):
        self.plan = plan if plan else None
        self._saved = None

    def __enter__(self):
        global _ACTIVE
        self._saved = _ACTIVE
        _ACTIVE = self.plan
        return self.plan

    def __exit__(self, *exc):
        global _ACTIVE
        _ACTIVE = self._saved
        return False


def active():
    """The plan a model applies while it traces, or None."""
    return _ACTIVE
