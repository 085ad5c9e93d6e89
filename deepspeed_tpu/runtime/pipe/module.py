"""Pipeline-parallel module (reference: ``runtime/pipe/module.py`` —
``LayerSpec`` :36, ``PipelineModule`` :85, partitioning :353 via
``partition_balanced`` ``runtime/utils.py:599``).

TPU redesign: instead of per-rank layer ownership + p2p send/recv
(reference ``runtime/pipe/p2p.py``, engine instruction loop), the pipeline
is ONE SPMD program over the `pipe` mesh axis:

  * per-stage block params are **stacked** on a leading axis sharded over
    `pipe` (logical name "pipe");
  * a ``shard_map`` + ``lax.scan`` runs the GPipe fill-drain: every step
    each stage applies its blocks to its current activation, then
    ``ppermute`` shifts activations to the next stage while stage 0
    ingests the next microbatch;
  * backward is jax autodiff through the scan — the reverse pipeline
    (grad ppermute in the opposite direction) is generated, not hand
    written; remat inside the block bounds live activations like 1F1B.

Embedding and head run outside the pipelined region (they are
data-parallel work; at scale their cost is dominated by the blocks).

``LayerSpec``/``partition_balanced`` are kept for API parity and for the
host-driven schedule tests (pipe/schedule.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

import flax.linen as nn


# --------------------------------------------------------- reference parity
class LayerSpec:
    """Deferred layer construction (reference LayerSpec, pipe/module.py:36)."""

    def __init__(self, typename, *module_args, **module_kwargs):
        self.typename = typename
        self.module_args = module_args
        self.module_kwargs = module_kwargs

    def build(self):
        return self.typename(*self.module_args, **self.module_kwargs)

    def __repr__(self):
        return f"LayerSpec({getattr(self.typename, '__name__', self.typename)})"


class TiedLayerSpec(LayerSpec):
    """Layer whose params are shared across stages (reference :63). In the
    TPU design tied weights live outside the pipelined region (embed/head),
    so tying is structural rather than an allreduce."""

    def __init__(self, key, typename, *args, forward_fn=None, **kwargs):
        super().__init__(typename, *args, **kwargs)
        self.key = key
        self.forward_fn = forward_fn


def partition_balanced(weights, num_parts):
    """Balanced contiguous partition of weighted items: returns part
    boundaries of length num_parts+1 (reference ``partition_balanced``,
    runtime/utils.py:599 — binary search over prefix sums)."""
    weights = list(weights)
    n = len(weights)
    if num_parts >= n:
        return list(range(n + 1)) + [n] * (num_parts - n)
    prefix = np.concatenate([[0], np.cumsum(weights)])

    def parts_needed(max_weight):
        parts, cur = 1, 0.0
        for w in weights:
            if w > max_weight:
                return num_parts + 1
            if cur + w > max_weight:
                parts += 1
                cur = w
            else:
                cur += w
        return parts

    lo, hi = max(weights), float(prefix[-1])
    for _ in range(60):
        mid = (lo + hi) / 2
        if parts_needed(mid) <= num_parts:
            hi = mid
        else:
            lo = mid
    # build boundaries greedily at weight hi; a stage must also break when
    # the remaining items are only enough to give each remaining stage one
    # (otherwise trailing stages end up empty, e.g. 4 blocks / 3 stages)
    bounds, cur = [0], 0.0
    for i, w in enumerate(weights):
        parts_left = num_parts - (len(bounds) - 1)
        must_break = (n - i) <= (parts_left - 1) and i > bounds[-1]
        if (cur + w > hi or must_break) and len(bounds) < num_parts:
            bounds.append(i)
            cur = w
        else:
            cur += w
    while len(bounds) < num_parts:
        bounds.append(n)
    bounds.append(n)
    return bounds


# ------------------------------------------------------------ SPMD pipeline
def _rebox(tree, prefix_names, like):
    """Box `tree`'s leaves with `prefix_names` + the logical names carried
    by the corresponding (Partitioned-boxed) leaves of `like`."""
    from deepspeed_tpu.parallel import sharding as shd
    names = shd.get_logical_specs(like)   # same structure as unboxed `tree`

    def f(x, nm):
        inner = tuple(nm) if nm is not None \
            else (None,) * (np.ndim(x) - len(prefix_names))
        return nn.Partitioned(x, tuple(prefix_names) + inner)

    return jax.tree.map(f, tree, names)


def pipeline_spmd_forward(stage_params, x, *, block_apply, mesh,
                          num_microbatches, rng=None):
    """Run stacked-stage blocks as a GPipe pipeline over the `pipe` axis.

    stage_params: pytree, leaves [S, k, ...] ('pipe'-sharded on dim 0).
    x: activations [batch, ...] (batch divisible by num_microbatches).
    Returns activations [batch, ...] after all S*k blocks.
    """
    S = mesh.shape["pipe"]
    M = num_microbatches
    b = x.shape[0]
    assert b % M == 0, f"batch {b} not divisible by microbatches {M}"
    xs = x.reshape(M, b // M, *x.shape[1:])

    def use(ax, dim):
        return ax if ax in mesh.shape and mesh.shape[ax] > 1 and \
            dim % mesh.shape[ax] == 0 else None

    # microbatch tensors: batch may stay data-sharded through the pipeline
    x_spec = P(None, use("data", xs.shape[1]), *([None] * (xs.ndim - 2)))
    p_spec = jax.tree.map(lambda a: P("pipe", *([None] * (a.ndim - 1))),
                          stage_params)

    def per_stage(params_loc, xs_loc):
        params_loc = jax.tree.map(lambda a: a[0], params_loc)  # [k, ...]
        stage = lax.axis_index("pipe")
        T = M + S - 1
        # derive a stage-varying zero so scan carries have consistent
        # device-varying axes (see ops/attention/ring.py)
        svar = jax.tree.leaves(params_loc)[0].ravel()[0] * 0.0
        cur0 = jnp.zeros_like(xs_loc[0]) + svar.astype(xs_loc.dtype)
        outs0 = jnp.zeros_like(xs_loc) + svar.astype(xs_loc.dtype)

        def body(carry, t):
            cur, outs = carry
            inp = jnp.where(stage == 0, xs_loc[jnp.clip(t, 0, M - 1)], cur)
            # decorrelate dropout across stages and pipeline steps
            step_rng = None if rng is None else \
                jax.random.fold_in(jax.random.fold_in(rng, t), stage)
            y = block_apply(params_loc, inp, step_rng)
            # record the finished microbatch on the last stage
            out_t = t - (S - 1)
            is_last = stage == S - 1
            valid = jnp.logical_and(out_t >= 0, is_last)
            idx = jnp.clip(out_t, 0, M - 1)
            outs = outs.at[idx].set(jnp.where(valid, y, outs[idx]))
            # shift activations downstream (stage i -> i+1)
            shifted = lax.ppermute(y, "pipe",
                                   [(i, i + 1) for i in range(S - 1)])
            return (shifted, outs), None

        (_, outs), _ = lax.scan(body, (cur0, outs0), jnp.arange(M + S - 1))
        # only the last stage holds real outputs; broadcast them
        mask = (stage == S - 1).astype(outs.dtype)
        return lax.psum(outs * mask, "pipe")

    out_spec = x_spec
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=(p_spec, x_spec),
                       out_specs=out_spec)
    outs = fn(stage_params, xs)
    return outs.reshape(b, *x.shape[1:])


class PipelineModule:
    """Uniform-block pipeline model with engine-compatible init/apply.

    Construction (TPU-native path):
        PipelineModule(block=BlockModule, num_blocks=L, num_stages=S,
                       embed=EmbedModule, head=HeadModule,
                       num_microbatches=M)

    Reference-parity path: ``PipelineModule(layers=[LayerSpec, ...])`` is
    accepted for host-side partitioning math (``stage_ranges``); fused SPMD
    execution requires the uniform-block form.
    """

    def __init__(self, layers=None, *, block=None, num_blocks=None,
                 num_stages=None, embed=None, head=None,
                 num_microbatches=None, partition_method="parameters",
                 loss_fn=None, tied_head=False, schedule="1f1b",
                 layer_weights=None):
        self.layers = layers
        self.block = block
        self.num_blocks = num_blocks
        self.num_stages = num_stages
        self.embed = embed
        self.head = head
        self.num_microbatches = num_microbatches or (num_stages or 1)
        self.partition_method = partition_method
        self.loss_fn = loss_fn
        # tied_head: head receives the embed params (reference
        # TiedLayerSpec — embeddings shared between first and last stage;
        # here both live outside the pipelined region, so tying is direct)
        self.tied_head = tied_head
        # "1f1b": training runs the bounded-memory interleaved schedule
        # (one_f_one_b.py); "gpipe": autodiff through the fill-drain scan
        assert schedule in ("1f1b", "gpipe"), schedule
        self.schedule = schedule
        if block is not None:
            assert num_blocks is not None and num_stages is not None
            # non-uniform stages (reference LayerSpec weights +
            # partition_balanced, runtime/utils.py:599): each stage's
            # stack is padded to the max and padded slots are skipped
            w = list(layer_weights) if layer_weights is not None \
                else [1] * num_blocks
            assert len(w) == num_blocks, (len(w), num_blocks)
            bounds = partition_balanced(w, num_stages)
            self.k_per_stage = tuple(bounds[i + 1] - bounds[i]
                                     for i in range(num_stages))
            assert min(self.k_per_stage) >= 1, \
                f"empty pipeline stage: {self.k_per_stage}"
            self.layers_per_stage = max(self.k_per_stage)
            self.uniform = len(set(self.k_per_stage)) == 1

    # --------------------------------------------------------- 1F1B loss
    def make_loss_fn(self, per_token_loss=None):
        """Engine-compatible ``loss_fn(params, batch, rng)`` running the
        1F1B schedule (runtime/pipe/one_f_one_b.py). The default
        per-token loss is next-token CE with -100 ignore (the reference
        PipelineEngine's loss_fn contract, pipe/engine.py:285)."""
        from deepspeed_tpu.runtime.pipe.one_f_one_b import (
            make_pipeline_loss_fn)

        if per_token_loss is None:
            from deepspeed_tpu.models.gpt2 import gpt2_loss_fn

            def per_token_loss(logits, labels):
                return gpt2_loss_fn(logits, {"labels": labels})

        cache = {}

        def resolve(batch):
            from deepspeed_tpu import comm as dist
            mesh = dist.get_mesh()
            assert mesh is not None and \
                mesh.shape.get("pipe") == self.num_stages, \
                "active mesh must carry the pipe axis sized num_stages"
            key = id(mesh)
            if key not in cache:
                cache[key] = make_pipeline_loss_fn(
                    self, per_token_loss, mesh=mesh,
                    num_microbatches=self.num_microbatches)
            ids = batch["input_ids"]
            labels = batch.get("labels")
            if labels is None:
                labels = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)),
                                 constant_values=-100)
            return cache[key], ids, labels

        def split(params):
            return {"stages": params["stages"],
                    "embed": params.get("embed", {}),
                    "head": params.get("head", {})}

        def loss_fn(params, batch, rng):
            fn, ids, labels = resolve(batch)
            return fn(split(params), ids, labels)

        def loss_and_grads(params, batch):
            """One interleaved scan for (loss, grads) — the engine's
            training fast path. Going through value_and_grad would run
            the forward-only pipeline AND the interleaved scan (3x
            forward FLOPs); this is the reference's 2x (forward +
            activation-checkpoint recompute)."""
            fn, ids, labels = resolve(batch)
            return fn.pipeline_bwd_grads(split(params), ids, labels)

        loss_fn.loss_and_grads = loss_and_grads
        return loss_fn

    # ---------------------------------------------------- reference parity
    def stage_ranges(self, weights=None):
        """Layer index ranges per stage for a LayerSpec pipeline."""
        assert self.layers is not None
        n = len(self.layers)
        w = weights or [1] * n
        bounds = partition_balanced(w, self.num_stages)
        return [(bounds[i], bounds[i + 1]) for i in range(self.num_stages)]

    # ------------------------------------------------------- flax protocol
    def init(self, rng, x, *args, **kwargs):
        assert self.block is not None, \
            "fused pipeline needs the uniform-block construction"
        S, k = self.num_stages, self.layers_per_stage
        r_embed, r_blocks, r_head = jax.random.split(rng, 3)
        params = {}
        a = x
        if self.embed is not None:
            ev = self.embed.init(r_embed, x)
            params["embed"] = ev.get("params", ev)
            a = self.embed.apply({"params": nn.meta.unbox(params["embed"])}, x)

        keys = jax.random.split(r_blocks, S * k)
        inner = self.block.init(keys[0], a).get("params", None)  # for names
        stacked = jax.vmap(
            lambda r: nn.meta.unbox(self.block.init(r, a)
                                    .get("params", None)))(keys)
        stacked = jax.tree.map(
            lambda leaf: leaf.reshape(S, k, *leaf.shape[1:]), stacked)
        params["stages"] = _rebox(stacked, ("pipe", "layers"), like=inner)

        if self.head is not None:
            kw = {"embed_params": nn.meta.unbox(params["embed"])} \
                if self.tied_head else {}
            hv = self.head.init(r_head, a, **kw)
            params["head"] = hv.get("params", hv)
        return {"params": params}

    def apply(self, variables, x, *args, deterministic=True, rngs=None,
              mutable=None, **kwargs):
        from deepspeed_tpu import comm as dist
        params = variables["params"]
        params = nn.meta.unbox(params) if _has_boxes(params) else params
        mesh = dist.get_mesh()
        assert mesh is not None and mesh.shape["pipe"] == self.num_stages, \
            "active mesh must carry the pipe axis sized num_stages"

        a = x
        if self.embed is not None:
            a = self.embed.apply({"params": params["embed"]}, x)

        block = self.block
        drop_rng = (rngs or {}).get("dropout")

        uniform = self.uniform
        k_per_stage = self.k_per_stage

        def block_apply(kparams, h, step_rng):
            k_local = None if uniform else \
                jnp.asarray(k_per_stage)[lax.axis_index("pipe")]

            def one(carry, xs):
                h, i = carry
                layer_params = xs
                kw = {}
                if step_rng is not None:
                    kw["rngs"] = {"dropout": jax.random.fold_in(step_rng, i)}
                y = block.apply({"params": layer_params}, h,
                                deterministic, **kw)
                if isinstance(y, tuple):  # blocks with a (x, cache) contract
                    y = y[0]
                if k_local is not None:   # padded slot on a short stage
                    y = jnp.where(i < k_local, y, h)
                return (y, i + 1), None
            (h, _), _ = lax.scan(one, (h, jnp.int32(0)), kparams)
            return h

        a = pipeline_spmd_forward(params["stages"], a,
                                  block_apply=block_apply, mesh=mesh,
                                  num_microbatches=self.num_microbatches,
                                  rng=drop_rng)
        if self.head is not None:
            kw = {"embed_params": params["embed"]} if self.tied_head else {}
            a = self.head.apply({"params": params["head"]}, a, **kw)
        if mutable is not None:
            return a, {}
        return a


def _has_boxes(tree):
    return any(isinstance(l, nn.Partitioned)
               for l in jax.tree.leaves(
                   tree, is_leaf=lambda x: isinstance(x, nn.Partitioned)))
