"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

DeepSpeed-Ulysses (postdates the reference snapshot; SURVEY.md §5.7 marks
it as the gap to fill): attention inputs arrive sequence-sharded
[b, L/P, h, d]; an all-to-all re-shards to head-sharded [b, L, h/P, d] so
each device runs *full-sequence* attention on a subset of heads (any
kernel works locally — including the Pallas flash kernel), then an inverse
all-to-all restores sequence sharding. Communication volume is O(L·h·d/P)
per device vs allgather's O(L·h·d).

Requires num_heads % P == 0.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention.reference import mha_reference
from deepspeed_tpu.ops.attention.ring import NEG_INF, _bhd_spec


def ulysses_attention_local(q, k, v, axis_name, *, causal=True,
                            attn_fn=None):
    """Per-shard body (under shard_map; inputs [b, chunk, h, d])."""
    attn_fn = attn_fn or (lambda q, k, v: mha_reference(q, k, v,
                                                        causal=causal))

    def seq_to_heads(x):
        # [b, L/P, h, d] -> [b, L, h/P, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = attn_fn(qh, kh, vh)
    return heads_to_seq(oh)


def ulysses_prefill_attention_local(q, k, v, k_pref, v_pref, prefix_len,
                                    axis_name, *, scale=None):
    """Per-shard body for one sequence-parallel PREFILL chunk.

    q/k/v: [b, L/P, h, d] — the chunk, sequence-sharded on dim 1;
    k_pref/v_pref: [b, maxT, h/P, d] — the paged-pool gather,
    head-sharded over the SEQUENCE axis (rank j holds exactly the head
    block its all-to-all output computes, see the sharded entry);
    prefix_len: valid prefix rows (everything at position >= prefix_len
    in the gather — including the chunk itself, just written — is
    masked; the chunk attends to itself causally through the fresh
    k/v instead).

    ONE softmax spans [prefix | chunk]: after the head-scatter/
    seq-gather all-to-all each rank holds the FULL chunk for its head
    subset, so row i's global chunk position IS i and a plain
    [prefix-mask | tril] concatenated bias is exact — no online-softmax
    merge needed on this path."""
    b, c, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    def seq_to_heads(x):
        # [b, L/P, h, d] -> [b, L, h/P, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    L, maxT = qh.shape[1], k_pref.shape[1]
    logits_p = jnp.einsum("bqhd,bkhd->bhqk", qh, k_pref,
                          preferred_element_type=jnp.float32) * scale
    live_p = (jnp.arange(maxT) < prefix_len)[None, None, None, :]
    logits_p = jnp.where(live_p, logits_p, NEG_INF)
    logits_c = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                          preferred_element_type=jnp.float32) * scale
    causal = jnp.tril(jnp.ones((L, L), bool))[None, None]
    logits_c = jnp.where(causal, logits_c, NEG_INF)
    logits = jnp.concatenate([logits_p, logits_c], axis=-1)
    m = logits.max(axis=-1, keepdims=True)
    w = jnp.exp(logits - m)
    # every row keeps at least its causal diagonal, so the sum is > 0
    # even for padding rows past n_valid (their output is garbage the
    # boundary-row slice discards)
    w = (w / w.sum(axis=-1, keepdims=True)).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w[..., :maxT], v_pref) + \
        jnp.einsum("bhqk,bkhd->bqhd", w[..., maxT:], vh)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_prefill_attention(q, k, v, k_pref, v_pref, prefix_len, mesh, *,
                              axis="sequence", scale=None):
    """Sequence-parallel prefill chunk attention against a paged prefix.

    q/k/v [b, L, h, d] are the chunk (L shards over ``axis``);
    k_pref/v_pref [b, maxT, h, d] the full paged-pool gather.  The
    prefix enters head-sharded over ``(model, sequence)``: with
    ``h_sub = h / (model_size * seq_size)``, the all-to-all hands rank
    (m, j) head block ``m*P + j`` — exactly the ``(model, sequence)``
    partition of the head dim, so no per-rank slicing is needed and
    GSPMD reshards the (replicated) gather with a local slice, not a
    collective."""
    n = mesh.shape[axis]
    spec = _bhd_spec(mesh, q.shape, axis)
    model_ax = spec[2]
    local_heads = q.shape[2] // (mesh.shape[model_ax] if model_ax else 1)
    assert local_heads % n == 0, \
        (f"heads per model shard ({local_heads}) must divide the "
         f"sequence axis size ({n}) for the Ulysses all-to-all — "
         "resolve_sequence_plan routes this case to ring")
    head_axes = (model_ax, axis) if model_ax is not None else axis
    pspec = P(spec[0], None, head_axes, None)
    fn = functools.partial(ulysses_prefill_attention_local,
                           axis_name=axis, scale=scale)
    sharded = jax.shard_map(fn, mesh=mesh,
                            in_specs=(spec, spec, spec, pspec, pspec, P()),
                            out_specs=spec)
    return sharded(q, k, v, k_pref, v_pref, prefix_len)


def ulysses_attention_sharded(q, k, v, mesh, *, axis="sequence", causal=True,
                              attn_fn=None):
    """Global entry: q/k/v [b, L, h, d]; shards L over `axis`, swaps to
    heads for compute (DistributedAttention in deepspeed/sequence/layer.py
    of later snapshots)."""
    n = mesh.shape[axis]
    assert q.shape[2] % n == 0, \
        f"num_heads {q.shape[2]} must divide sequence axis size {n}"
    from deepspeed_tpu.ops.attention.ring import _bhd_spec
    spec = _bhd_spec(mesh, q.shape, axis)
    if spec[2] is not None:
        # heads already model-sharded: the per-shard head count must still
        # divide the sequence axis for the all-to-all swap
        assert (q.shape[2] // mesh.shape["model"]) % n == 0, \
            "heads per model shard must divide the sequence axis size"
    fn = functools.partial(ulysses_attention_local, axis_name=axis,
                           causal=causal, attn_fn=attn_fn)
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec)
    return sharded(q, k, v)
