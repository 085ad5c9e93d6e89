"""Serving sharding layer: logical serving axes -> mesh axes.

The serving stack names its array dimensions with *logical* axes — the
same t5x-style indirection the training params use
(parallel/sharding.py) — and maps them onto the device mesh through one
rule table, so running the paged KV cache and every serving primitive
over a multi-chip topology is a config change, not a rewrite:

  ===========  ================  =============================================
  logical      default mesh ax   carried by
  ===========  ================  =============================================
  kv_heads     model             KV page pools [pages, page_size, KV_H, dim]
                                 (a LATENT pool [pages, page_size, dim] has
                                 one head and no head dim: nothing to pin,
                                 and a `model` axis over it is refused)
  slots        data              per-slot carries (tok/active/lengths/
                                 emitted/budgets/eos), token blocks
                                 [SLOTS, H|K+1], the page table [SLOTS, maxp]
  pages        (replicated)      the page dim of the pools — page ids are
                                 GLOBAL: the host-side free list / page
                                 table / radix cache never know the mesh
  vocab        model             boundary logits a prefill chunk returns
  ssm_heads    model             a recurrent layer's state [SLOTS, SSM_H, p, n]
                                 (its conv tail [SLOTS, k-1, c] shards slots
                                 alone)
  experts      expert            the held experts of a routed layer (their
                                 stacked weights' leading dim)
  ===========  ================  =============================================

Weights already shard over ``model`` through the engine's
``_param_shardings``; this module covers the serving-only state.  The
page dim stays replicated by design: every device holds the full page
*index space* (its slice of every page along kv_heads), so
``PagedKVManager`` / ``PrefixCache`` bookkeeping — allocation,
refcounts, donation, COW, eviction — is mesh-agnostic host logic and a
page id means the same thing on every chip.

A multi-slice ICI x DCN topology IS the same config (landed):
``parallel.topology.make_hybrid_mesh`` builds the device array with
``mesh_utils.create_hybrid_device_mesh`` (ICI parallelism within a
slice, DCN across slices — the t5x/MaxText split), ``model`` stays on
the ICI-innermost axis, ``slots`` ride the DCN-spanning data axis, and
this rule table is untouched — the engine takes the split as pure
config (``mesh_dcn=`` / ``ds_serve --mesh ...,dcn.data=N``).  The
shard_map'd paged kernel (ops/attention/decode.py) reads this same
table through :func:`active_rules` so its per-shard split always
agrees with the pinned pool/carry shardings.
"""

import dataclasses

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Default logical serving axis -> mesh axis. None = replicated.
SERVING_AXIS_RULES = (
    ("kv_heads", "model"),
    ("slots", "data"),
    ("pages", None),
    ("vocab", "model"),
    ("sequence", "sequence"),
    ("ssm_heads", "model"),
    ("experts", "expert"),
)

# the logical axes of a pools leaf that is not a K/V page array, by the
# leaf's name in its layer's entry (ops/ssm/state.py; a window layer's
# ring, ops/attention/window.py; the routing counters of
# moe/held_experts.py, in two leaves; a latent layer's one page leaf,
# ops/quant/kv.py, which has no head dim); every other leaf is a K/V page
# array
POOL_LEAF_AXES = {
    "c_pages": ("pages", None, None),
    "conv": ("slots", None, None),
    "ssm": ("slots", "ssm_heads", None, None),
    "k_ring": ("slots", None, "kv_heads", None),
    "v_ring": ("slots", None, "kv_heads", None),
    "routing": (None,),
    "walked": (None,),
}


def _mesh_axis_size(mesh, axis):
    return int(mesh.shape[axis]) if axis is not None and axis in mesh.shape \
        else 1


@dataclasses.dataclass(frozen=True)
class ServingShardingConfig:
    """Logical-axis rules for the serving stack (immutable; the engine
    resolves it against a concrete mesh + model once, at serving
    setup)."""
    rules: tuple = SERVING_AXIS_RULES

    def axis(self, logical):
        return dict(self.rules).get(logical)

    def validate(self, mesh, num_kv_heads):
        """Mesh-shape validation for sharded serving: the axis carrying
        ``kv_heads`` must divide the model's KV head count — anything
        else would shard mid-head, the exact regime the legacy SPMD
        partitioner silently miscompiles (~1e-2 drift, PR-2 triage).
        Raises a ValueError naming the axis and head count instead."""
        ax = self.axis("kv_heads")
        size = _mesh_axis_size(mesh, ax)
        if size > 1 and num_kv_heads == 1:
            raise ValueError(
                f"mesh axis '{ax}' has size {size}, and this model's page "
                "pool has ONE head a token (a latent cache that every "
                "query head reads, or multi-query attention): one head "
                f"splits over no '{ax}' axis, and serving it replicated "
                f"beside weights split over '{ax}' is not built. Serve "
                f"it on a mesh whose '{ax}' size is 1.")
        if size > 1 and num_kv_heads % size != 0:
            raise ValueError(
                f"mesh axis '{ax}' has size {size}, which does not divide "
                f"num_kv_heads={num_kv_heads}: the paged KV pools shard "
                f"their head dim over '{ax}', and an indivisible head "
                "count would shard mid-head (silent numeric drift on "
                f"legacy SPMD partitioners). Pick a mesh whose '{ax}' "
                f"size divides {num_kv_heads}, or a model whose KV head "
                f"count is a multiple of {size}.")

    def validate_heads(self, mesh, num_heads):
        """Construction-time attention-TP validation (the engine calls
        this for every model with a head-count contract, serving or
        not): the configured head axis must divide ``num_heads`` —
        intra-head tensor parallelism silently drifts ~1e-2 on legacy
        SPMD partitioners and has no serving sharding.  Fail loudly,
        naming the axis and count.  (A page pool with ONE head a token —
        a latent cache, multi-query attention — passes here on its query
        heads; :meth:`validate` refuses it by name when the pools are
        built, instead of failing on ``1 % size``.)"""
        ax = self.axis("kv_heads")
        size = _mesh_axis_size(mesh, ax)
        if size > 1 and num_heads % size != 0:
            raise ValueError(
                f"mesh axis '{ax}' has size {size}, which does not "
                f"divide num_heads={num_heads}: intra-head tensor "
                "parallelism silently drifts on legacy SPMD partitioners"
                f" and has no serving sharding. Pick a '{ax}' size that "
                f"divides {num_heads} (tensor_parallel.tp_size / mesh"
                "={'%s': ...})." % ax)

    def resolve(self, mesh, *, num_kv_heads, vocab_size=None,
                num_slots=None):
        """Concrete :class:`ServingShardings` for one mesh + model.
        Validates kv-head divisibility (hard error — see
        :meth:`validate`); the vocab and slot axes degrade to
        replicated when they do not divide (tiny fixture vocabularies;
        a slot count smaller than / uneven over the data axis — jax
        requires dim % shards == 0, and a toy server on a big mesh
        should run replicated, not crash)."""
        self.validate(mesh, num_kv_heads)
        kv_ax = self.axis("kv_heads")
        if _mesh_axis_size(mesh, kv_ax) == 1:
            kv_ax = None
        slot_ax = self.axis("slots")
        if _mesh_axis_size(mesh, slot_ax) == 1 or (
                num_slots is not None and
                num_slots % _mesh_axis_size(mesh, slot_ax) != 0):
            slot_ax = None
        page_ax = self.axis("pages")
        if _mesh_axis_size(mesh, page_ax) == 1:
            page_ax = None
        vocab_ax = self.axis("vocab")
        if _mesh_axis_size(mesh, vocab_ax) == 1 or (
                vocab_size is not None and
                vocab_size % _mesh_axis_size(mesh, vocab_ax) != 0):
            vocab_ax = None
        return ServingShardings(mesh=mesh, config=self, kv_axis=kv_ax,
                                slot_axis=slot_ax, page_axis=page_ax,
                                vocab_axis=vocab_ax)


@dataclasses.dataclass(frozen=True)
class SeqParallelPlan:
    """Resolved sequence-parallel prefill plan for one mesh + model.

    ``axis`` is the mesh axis the prompt chunk shards over, ``size``
    its device count, ``impl`` the attention transport — ``"ulysses"``
    (all-to-all head-scatter/seq-gather) when the per-model-shard head
    count divides the axis, ``"ring"`` (ppermute hops) otherwise.  When
    the path is unusable ``axis`` is None and ``reason`` says why; the
    scheduler degrades to the chunked loop instead of crashing."""
    axis: object = None
    size: int = 1
    impl: object = None
    reason: object = None

    @property
    def usable(self):
        return self.axis is not None


def resolve_sequence_plan(mesh, config, *, num_heads, num_kv_heads):
    """Pick the sequence-parallel transport for one mesh + model.

    Decision table (mirrored in serving/README.md):

    * no ``sequence`` mesh axis, or size 1 -> degrade (chunked loop);
    * heads-per-model-shard % axis size == 0 -> ``ulysses`` — the
      all-to-all trades the seq split for a head split, which needs a
      whole number of heads per sequence rank;
    * otherwise -> ``ring`` — ppermute hops never split heads, so any
      head count rides the axis.

    KV heads are NOT a constraint here: the paged landing goes through
    ``paged_write`` against the kv-head-sharded pool exactly like the
    chunked path, and ring/ulysses run on the post-projection
    full-head q/k/v of the chunk."""
    ax = (config or ServingShardingConfig()).axis("sequence")
    size = _mesh_axis_size(mesh, ax)
    if ax is None or ax not in getattr(mesh, "shape", {}):
        return SeqParallelPlan(reason=f"mesh has no '{ax}' axis")
    if size <= 1:
        return SeqParallelPlan(reason=f"mesh axis '{ax}' has size 1")
    model_sz = _mesh_axis_size(mesh, (config or ServingShardingConfig())
                               .axis("kv_heads"))
    local_heads = num_heads // max(1, model_sz)
    if local_heads % size == 0:
        return SeqParallelPlan(axis=ax, size=size, impl="ulysses")
    return SeqParallelPlan(axis=ax, size=size, impl="ring")


@dataclasses.dataclass(frozen=True)
class ServingShardings:
    """Resolved NamedShardings for every serving array family.

    ``slot`` covers the [num_slots] device carries, ``block`` the
    [num_slots, H|K+1] token/valid blocks AND the [num_slots,
    max_pages] page table (both shard dim 0 over the slots axis),
    ``pool`` the per-layer [num_pages, page_size, kv_heads, head_dim]
    KV pools, ``logits`` a prefill dispatch's [rows, vocab] boundary
    logits."""
    mesh: object
    config: ServingShardingConfig
    kv_axis: object
    slot_axis: object
    page_axis: object
    vocab_axis: object

    @property
    def replicated(self):
        return NamedSharding(self.mesh, P())

    @property
    def pool(self):
        return NamedSharding(
            self.mesh, P(self.page_axis, None, self.kv_axis, None))

    def leaf(self, logical, shape):
        """The sharding of one array from its logical axes: ``slots``
        as resolved for this scheduler, any other name through the rule
        table, replicated where the mesh axis is trivial or does not
        divide the dim."""
        spec = []
        for name, dim in zip(logical, shape):
            ax = self.slot_axis if name == "slots" else \
                self.config.axis(name) if name is not None else None
            size = _mesh_axis_size(self.mesh, ax)
            spec.append(ax if size > 1 and dim % size == 0 else None)
        return NamedSharding(self.mesh, P(*spec))

    def pool_tree(self, pools):
        """One sharding a leaf for a pools pytree (arrays or their
        ShapeDtypeStructs) that holds more than K/V pages."""
        return {"layers": [
            {name: self.leaf(POOL_LEAF_AXES[name], leaf.shape)
             if name in POOL_LEAF_AXES else self.pool
             for name, leaf in entry.items()}
            for entry in pools["layers"]]}

    @property
    def slot(self):
        return NamedSharding(self.mesh, P(self.slot_axis))

    @property
    def block(self):
        return NamedSharding(self.mesh, P(self.slot_axis, None))

    @property
    def logits(self):
        return NamedSharding(self.mesh, P(None, self.vocab_axis))

    def describe(self):
        """Logical-axis -> resolved mesh axis map (health()/logs)."""
        out = {"kv_heads": self.kv_axis, "slots": self.slot_axis,
               "pages": self.page_axis, "vocab": self.vocab_axis}
        for name in ("ssm_heads", "experts"):
            ax = self.config.axis(name)
            if _mesh_axis_size(self.mesh, ax) > 1:
                out[name] = ax
        return out


def split_pools(pools):
    """(the K/V page leaves, the other leaves) of a pools pytree, each
    as a list of per-layer dicts: pages are billed by the page, per-slot
    state and the ``routing`` counters are not; the ``walked`` counters
    are billed to neither (models/nemotron_h.ROUTING_LEAVES has the
    reason)."""
    kv, other = [], []
    for entry in pools["layers"]:
        kv.append({n: a for n, a in entry.items() if is_page_leaf(n)})
        other.append({n: a for n, a in entry.items()
                      if not is_page_leaf(n) and n != "walked"})
    return kv, other


def is_page_leaf(name):
    """True for a pools leaf whose leading dim is the PAGE dim (K, V and
    their scales; a latent layer's ``c_pages``): what a page id indexes,
    so what a page copy, a hand-off and the page ledgers touch."""
    return name not in POOL_LEAF_AXES or \
        POOL_LEAF_AXES[name][0] == "pages"


def pool_bytes_per_device(pools):
    """Per-device bytes of a (possibly sharded) KV pool pytree — each
    device holds its shard of every page, so this is total bytes
    divided by the kv-head sharding factor."""
    total = 0
    for leaf in jax.tree.leaves(pools):
        shard = leaf.sharding.shard_shape(leaf.shape) \
            if hasattr(leaf, "sharding") else leaf.shape
        total += int(np.prod(shard)) * leaf.dtype.itemsize
    return total


_ACTIVE_CONFIG = None


class config_scope:
    """Trace-time channel from the engine to the in-graph KV-pool
    constraint: the engine wraps every serving trace in
    ``config_scope(engine.serving_sharding)`` (alongside
    ``dist.mesh_scope``) so :func:`constrain_kv_pages` constrains with
    the engine's CONFIGURED rule table — a custom table must constrain
    consistently with the pinned out_shardings, or GSPMD would insert a
    full-pool reshard inside every dispatch."""

    def __init__(self, config):
        self.config = config
        self._saved = None

    def __enter__(self):
        global _ACTIVE_CONFIG
        self._saved = _ACTIVE_CONFIG
        _ACTIVE_CONFIG = self.config
        return self.config

    def __exit__(self, *exc):
        global _ACTIVE_CONFIG
        _ACTIVE_CONFIG = self._saved
        return False


def active_rules():
    """The ACTIVE logical-axis rule table as a dict (trace-time): the
    engine-configured table inside a serving trace (``config_scope``),
    the default table otherwise.  The shard_map'd paged kernel resolves
    its per-shard axes through this, so a custom rule table partitions
    the kernel consistently with the pinned shardings."""
    cfg = _ACTIVE_CONFIG
    return dict(cfg.rules if cfg is not None else SERVING_AXIS_RULES)


def constrain_kv_pages(pages):
    """Pin the serving KV pool's mesh sharding on a traced pool array
    ([num_pages, page_size, kv_heads, head_dim]) inside the paged
    attention code.  Reads the engine-installed mesh and rule table at
    TRACE time (``dist.mesh_scope`` + :class:`config_scope` wrap every
    serving trace), so GSPMD never has to guess whether the pool
    scatter/gather should keep the kv-head split; a no-op without a
    mesh, with a trivial model axis, or with an indivisible head count
    (the engine validates the real serving path long before this
    point)."""
    from deepspeed_tpu import comm as dist
    mesh = dist.get_mesh()
    cfg = _ACTIVE_CONFIG
    rules = dict(cfg.rules if cfg is not None else SERVING_AXIS_RULES)
    ax = rules.get("kv_heads")
    if mesh is None or ax is None or ax not in mesh.shape \
            or pages.ndim != 4:      # a latent leaf has no head dim to pin
        return pages
    size = int(mesh.shape[ax])
    if size <= 1 or pages.shape[2] % size != 0:
        return pages
    return jax.lax.with_sharding_constraint(
        pages, NamedSharding(mesh, P(rules.get("pages"), None, ax, None)))
