"""1F1B pipeline training as one SPMD program.

Reference: ``runtime/pipe/schedule.py:189`` (``TrainSchedule`` — the 1F1B
instruction stream) and ``runtime/pipe/engine.py:599-1099`` (its
executor: per-rank p2p send/recv, PartitionedTensor activations, tied
grads). The defining property of 1F1B over GPipe is *bounded in-flight
activations*: a stage holds at most O(S) microbatch activations, not
O(M + S).

TPU redesign: the schedule is a single ``lax.scan`` under ``shard_map``
over the ``pipe`` mesh axis, with every stage running the same program
and stage-dependent predicates. One scan tick = one forward AND one
backward slot (the 1F1B steady state):

  * forward of microbatch m runs on stage s at tick ``t = m + s``;
    activations hop downstream via ``ppermute``;
  * backward of m runs on stage s at tick ``t = 2(S-1) - s + m``; grads
    hop upstream via the reverse ``ppermute``;
  * each stage keeps a **ring buffer** of its block-stack inputs, size
    ``R = 2S-1`` — the 1F1B in-flight bound. The backward tick re-runs
    the stage forward under ``jax.vjp`` from the saved input (DeepSpeed's
    PP + activation-checkpointing configuration) and accumulates param
    grads in the scan carry;
  * the embedding runs inside stage 0 and the head + loss inside stage
    S-1, so the only cross-stage reduction at the end is the scalar loss
    and the (small) embed/head grads — the GPipe path's x S broadcast of
    full activations (VERDICT weak #3) does not exist here. Tied
    embeddings get grad contributions from both ends of the pipe, summed
    by the same psum (reference ``pipe/module.py:406`` tied allreduce).

Autodiff never sees the pipeline: the public entry is a
``jax.custom_vjp`` whose forward is a residual-free forward-only scan
and whose backward IS the interleaved 1F1B scan returning hand-built
grads — so ``jax.value_and_grad`` (what the engine calls) works
unchanged on top.

Total ticks: forward-only ``M + S - 1``; interleaved ``M + 2(S-1)``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P


def _unwrap(y):
    return y[0] if isinstance(y, tuple) else y


def make_pipeline_loss_fn(pipe, per_token_loss, *, mesh, num_microbatches):
    """Build ``loss_fn(variables, ids, labels) -> scalar`` running the
    1F1B pipeline over `mesh`'s pipe axis.

    pipe: a PipelineModule (uniform stacked stages, embed + head).
    per_token_loss: ``(logits, labels) -> scalar mean loss`` (e.g.
    models.gpt2.gpt2_loss_fn's core).
    """
    S = mesh.shape.get("pipe")
    assert S, "mesh must carry a pipe axis"
    M = num_microbatches
    block = pipe.block
    embed = pipe.embed
    head = pipe.head
    tied = pipe.tied_head

    def use(ax, dim):
        return ax if ax in mesh.shape and mesh.shape[ax] > 1 and \
            dim % mesh.shape[ax] == 0 else None

    uniform = getattr(pipe, "uniform", True)
    k_per_stage = getattr(pipe, "k_per_stage", None)

    def stack_fwd(params_k, h):
        k_local = None if uniform else \
            jnp.asarray(k_per_stage)[lax.axis_index("pipe")]

        def one(carry, p):
            h, j = carry
            y = _unwrap(block.apply({"params": p}, h))
            if k_local is not None:      # padded slot on a short stage
                y = jnp.where(j < k_local, y, h)
            return (y, j + 1), None
        (h, _), _ = lax.scan(one, (h, jnp.int32(0)), params_k)
        return h

    def head_loss(head_params, embed_params, h, labels_m):
        kw = {"embed_params": embed_params} if tied else {}
        logits = head.apply({"params": head_params}, h, **kw)
        return per_token_loss(logits, labels_m)

    # ---------------------------------------------------- forward only
    def fwd_loss(params, ids, labels):
        stages, embed_p, head_p = params["stages"], params["embed"], \
            params["head"]
        b = ids.shape[0]
        assert b % M == 0, f"batch {b} % microbatches {M} != 0"
        mb = b // M
        ids_m = ids.reshape(M, mb, *ids.shape[1:])
        lab_m = labels.reshape(M, mb, *labels.shape[1:])

        x_spec = P(None, use("data", mb), *([None] * (ids_m.ndim - 2)))
        p_spec = jax.tree.map(lambda a: P("pipe", *([None] * (a.ndim - 1))),
                              stages)
        r_spec = jax.tree.map(lambda a: P(*([None] * np.ndim(a))), embed_p)
        h_spec = jax.tree.map(lambda a: P(*([None] * np.ndim(a))), head_p)

        def per_stage(stages_loc, embed_loc, head_loc, ids_loc, lab_loc):
            params_k = jax.tree.map(lambda a: a[0], stages_loc)
            s = lax.axis_index("pipe")
            # a zero that is device-varying over EVERY manual axis in
            # play (pipe from params, data from the batch), so scan
            # carries pass the shard_map vma type discipline
            svar = (jax.tree.leaves(params_k)[0].ravel()[0]
                    .astype(jnp.float32) * 0.0 +
                    ids_loc.ravel()[0].astype(jnp.float32) * 0.0)

            embed0 = embed.apply({"params": embed_loc}, ids_loc[0])
            cur0 = jnp.zeros_like(embed0) + svar.astype(embed0.dtype)

            def tick(carry, t):
                cur, loss_acc = carry
                m_f = t - s
                emb = embed.apply({"params": embed_loc},
                                  ids_loc[jnp.clip(m_f, 0, M - 1)])
                inp = jnp.where(s == 0, emb, cur)
                y = stack_fwd(params_k, inp)
                is_last = s == S - 1
                fwd_on = jnp.logical_and(m_f >= 0, m_f < M)
                lm = head_loss(head_loc, embed_loc, y,
                               lab_loc[jnp.clip(m_f, 0, M - 1)])
                loss_acc = loss_acc + jnp.where(
                    jnp.logical_and(is_last, fwd_on), lm, 0.0)
                nxt = lax.ppermute(y, "pipe",
                                   [(i, i + 1) for i in range(S - 1)])
                return (nxt, loss_acc), None

            (_, loss_acc), _ = lax.scan(
                tick, (cur0, jnp.float32(0.0) + svar), jnp.arange(M + S - 1))
            loss = lax.psum(loss_acc, "pipe") / M
            if use("data", mb):
                loss = lax.pmean(loss, "data")
            return loss

        fn = jax.shard_map(per_stage, mesh=mesh,
                           in_specs=(p_spec, r_spec, h_spec, x_spec, x_spec),
                           out_specs=P())
        return fn(stages, embed_p, head_p, ids_m, lab_m)

    # ------------------------------------------------- interleaved 1F1B
    # grads computed at unit cotangent; the caller scales by the real
    # cotangent afterwards (shard_map must not close over tracers)
    def bwd_grads(params, ids, labels):
        stages, embed_p, head_p = params["stages"], params["embed"], \
            params["head"]
        b = ids.shape[0]
        mb = b // M
        ids_m = ids.reshape(M, mb, *ids.shape[1:])
        lab_m = labels.reshape(M, mb, *labels.shape[1:])
        R = 2 * S - 1
        T = M + 2 * (S - 1)

        x_spec = P(None, use("data", mb), *([None] * (ids_m.ndim - 2)))
        p_spec = jax.tree.map(lambda a: P("pipe", *([None] * (a.ndim - 1))),
                              stages)
        r_spec = jax.tree.map(lambda a: P(*([None] * np.ndim(a))), embed_p)
        h_spec = jax.tree.map(lambda a: P(*([None] * np.ndim(a))), head_p)

        def per_stage(stages_loc, embed_loc, head_loc, ids_loc, lab_loc):
            params_k = jax.tree.map(lambda a: a[0], stages_loc)
            s = lax.axis_index("pipe")
            # a zero that is device-varying over EVERY manual axis in
            # play (pipe from params, data from the batch), so scan
            # carries pass the shard_map vma type discipline
            svar = (jax.tree.leaves(params_k)[0].ravel()[0]
                    .astype(jnp.float32) * 0.0 +
                    ids_loc.ravel()[0].astype(jnp.float32) * 0.0)

            embed0 = embed.apply({"params": embed_loc}, ids_loc[0])
            act_shape = embed0.shape
            zeros_act = jnp.zeros(act_shape, embed0.dtype)
            cur0 = zeros_act + svar.astype(embed0.dtype)
            gcur0 = jnp.zeros(act_shape, jnp.float32) + svar
            ring0 = jnp.zeros((R,) + act_shape, embed0.dtype) + \
                svar.astype(embed0.dtype)
            # Gradient/vma discipline: under shard_map's vma type system,
            # jax.vjp w.r.t. values that are REPLICATED over a manual axis
            # auto-inserts a psum over that axis (the transpose of the
            # implicit broadcast). So: (a) every cotangent is pre-gated —
            # masking after the vjp would be too late, the invalid
            # devices' contributions are already summed in; (b) no manual
            # psum/pmean on grads of replicated params — the vjp already
            # produced the global sum; (c) the data-parallel 1/dp
            # normalization rides in the seed cotangent.
            dpn = float(mesh.shape["data"]) if use("data", mb) else 1.0
            pg0 = jax.tree.map(lambda a: a.astype(jnp.float32) * 0.0,
                               params_k)
            eg0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                               embed_loc)
            hg0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                               head_loc)

            def tick(carry, t):
                cur, gcur, ring, pg, eg, hg, loss_acc = carry
                # ---------------- forward slot: microbatch m_f = t - s
                m_f = t - s
                fwd_on = jnp.logical_and(m_f >= 0, m_f < M)
                emb = embed.apply({"params": embed_loc},
                                  ids_loc[jnp.clip(m_f, 0, M - 1)])
                inp = jnp.where(s == 0, emb, cur)
                inp = jnp.where(fwd_on, inp, zeros_act)
                ring = lax.dynamic_update_index_in_dim(
                    ring, inp.astype(ring.dtype), jnp.mod(t, R), 0)
                y = stack_fwd(params_k, inp)

                # last stage: head loss + dy for the SAME microbatch
                # (its backward tick coincides with its forward tick)
                is_last = s == S - 1
                lab_f = lab_loc[jnp.clip(m_f, 0, M - 1)]
                lm, head_vjp = jax.vjp(
                    lambda hp, ep, h: head_loss(hp, ep, h, lab_f),
                    head_loc, embed_loc, y)
                hgate = jnp.where(jnp.logical_and(is_last, fwd_on), 1.0, 0.0)
                ct = (hgate / (M * dpn)).astype(lm.dtype) + \
                    svar.astype(lm.dtype)
                dhp, dep_h, dy = head_vjp(ct)
                hg = jax.tree.map(lambda a, d: a + d.astype(jnp.float32),
                                  hg, dhp)
                eg = jax.tree.map(lambda a, d: a + d.astype(jnp.float32),
                                  eg, dep_h)
                loss_acc = loss_acc + jnp.where(
                    jnp.logical_and(is_last, fwd_on), lm, 0.0)

                # --------------- backward slot: microbatch m_b
                m_b = t - (2 * (S - 1) - s)
                bwd_on = jnp.logical_and(m_b >= 0, m_b < M)
                t_saved = m_b + s                       # its forward tick here
                inp_b = lax.dynamic_index_in_dim(
                    ring, jnp.mod(jnp.clip(t_saved, 0, T - 1), R), 0,
                    keepdims=False)
                inp_b = jnp.where(bwd_on, inp_b, zeros_act)
                g_in = jnp.where(is_last, dy.astype(jnp.float32), gcur)
                g_in = jnp.where(bwd_on, g_in, jnp.zeros_like(gcur))

                # recompute stage forward under vjp (activation ckpt);
                # g_in is gated, so dp/dx vanish on idle slots
                _, stack_vjp = jax.vjp(stack_fwd, params_k, inp_b)
                dp, dx = stack_vjp(g_in.astype(inp_b.dtype))
                pg = jax.tree.map(lambda a, d: a + d.astype(jnp.float32),
                                  pg, dp)

                # stage 0 consumes dx into embedding grads: the stage gate
                # multiplies the COTANGENT (the vjp auto-psums over pipe)
                dx_emb = jnp.where(s == 0, dx, jnp.zeros_like(dx))
                _, emb_vjp = jax.vjp(
                    lambda ep: embed.apply(
                        {"params": ep}, ids_loc[jnp.clip(m_b, 0, M - 1)]),
                    embed_loc)
                (dep,) = emb_vjp(dx_emb.astype(embed0.dtype))
                eg = jax.tree.map(lambda a, d: a + d.astype(jnp.float32),
                                  eg, dep)

                # hops: activations downstream, grads upstream
                nxt = lax.ppermute(y, "pipe",
                                   [(i, i + 1) for i in range(S - 1)])
                gnxt = lax.ppermute(dx.astype(jnp.float32), "pipe",
                                    [(i, i - 1) for i in range(1, S)])
                return (nxt, gnxt, ring, pg, eg, hg, loss_acc), None

            carry0 = (cur0, gcur0, ring0, pg0, eg0, hg0,
                      jnp.float32(0.0) + svar)
            (_, _, _, pg, eg, hg, loss_acc), _ = lax.scan(
                tick, carry0, jnp.arange(T))

            loss = lax.psum(loss_acc, "pipe") / M
            if use("data", mb):
                loss = lax.pmean(loss, "data")
            pg = jax.tree.map(lambda a: a[None], pg)   # [1, k, ...] shard
            return loss, pg, eg, hg

        fn = jax.shard_map(per_stage, mesh=mesh,
                           in_specs=(p_spec, r_spec, h_spec, x_spec, x_spec),
                           out_specs=(P(), p_spec, r_spec, h_spec))
        loss, pg, eg, hg = fn(stages, embed_p, head_p, ids_m, lab_m)
        grads = {"stages": jax.tree.map(
                     lambda g, p: g.astype(jnp.asarray(p).dtype), pg, stages),
                 "embed": jax.tree.map(
                     lambda g, p: g.astype(jnp.asarray(p).dtype), eg, embed_p),
                 "head": jax.tree.map(
                     lambda g, p: g.astype(jnp.asarray(p).dtype), hg, head_p)}
        return loss, grads

    # ------------------------------------------------------ custom_vjp
    @jax.custom_vjp
    def loss_fn(params, ids, labels):
        return fwd_loss(params, ids, labels)

    def fwd(params, ids, labels):
        return fwd_loss(params, ids, labels), (params, ids, labels)

    def bwd(res, gbar):
        params, ids, labels = res
        _, grads = bwd_grads(params, ids, labels)
        grads = jax.tree.map(lambda g: g * gbar.astype(g.dtype), grads)
        zero_i = np.zeros(np.shape(ids), jax.dtypes.float0)
        zero_l = np.zeros(np.shape(labels), jax.dtypes.float0)
        return grads, zero_i, zero_l

    loss_fn.defvjp(fwd, bwd)
    loss_fn.pipeline_bwd_grads = bwd_grads   # exposed for direct tests
    return loss_fn
