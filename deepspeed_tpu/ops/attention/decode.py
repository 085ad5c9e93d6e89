"""Pallas TPU KV-cache decode attention (the `softmax_context` kernel).

TPU-native replacement for the reference's inference attention kernel
(csrc/transformer/inference/csrc/pt_binding.cpp `softmax_context`,
`inference_context.h` KV workspace): single-token queries attend over a
device-resident cache buffer without materializing [heads, max_len]
score tensors in HBM, with additive bias (position mask, ALiBi).

Design:
  * caches stay in their storage layout [batch, max_len, kv_heads, dim] —
    BlockSpecs index directly into it, no transpose copies per token.
  * grid = (batch, k_blocks) / (the live (slot, page) pairs of the active
    slots, a dynamic bound); every block spans ALL kv
    heads (Mosaic refuses a block whose last two dims are neither
    (8,128)-divisible nor the array's own), and the k axis is innermost
    so the online-softmax state lives in VMEM scratch across grid steps
    (same scheme as ops/attention/flash.py).
  * the paged kernel takes q grouped [slots, kv_heads, group, dim], so a
    GQA pool is never expanded to num_heads and MHA is group == 1; the
    contiguous kernel expands a grouped cache with `_repeat_kv`.
  * the contiguous kernel's bias [batch, heads, 1, max_len] carries the
    validity mask and any ALiBi term; the paged kernel computes the
    mask in-kernel from the per-slot position.  fp32 statistics
    throughout.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.attention.flash import NEG_INF, _inside_shard_map


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, nk):
    """One grid step: ALL heads against one kv block. Blocks span the
    full head dims (equal-to-array, so any head count satisfies the TPU
    (8,128) tiling rule), and the per-head products use dot_general
    batch dims directly on the cache's storage layout — Mosaic rejects
    both the reshape ([h,d]->[kv,grp,d], "unsupported shape cast") and
    per-head sub-8 blocks, so no reshapes or transposes appear here."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    h = q_ref.shape[1]
    q = q_ref[0]                                          # [h, 1, d]
    k = k_ref[0].transpose(1, 0, 2)                       # [h, bk, d]
    v = v_ref[0].transpose(1, 0, 2)                       # [h, bk, d]
    # leading-batch dot over heads (Mosaic supports batch dims only at
    # position 0 on both sides — hence q pre-shaped [h, 1, d] outside)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale       # [h, 1, bk]
    s = s + bias_ref[0]                                   # [h, 1, bk]
    s = jnp.maximum(s, NEG_INF)  # keep masked slots finite (see flash.py)

    m_prev = m_scr[:h, :1]
    l_prev = l_scr[:h, :1]
    m_cur = jnp.max(s, axis=2)                            # [h, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    row_live = m_new > NEG_INF / 2
    alpha = jnp.where(row_live, jnp.exp(m_prev - m_new), 0.0)
    p = jnp.where(row_live[..., None], jnp.exp(s - m_new[..., None]), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=2)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)               # [h, 1, d]
    acc_scr[:h] = acc_scr[:h] * alpha + pv[:, 0, :]
    m_scr[:h] = jnp.broadcast_to(m_new, (h, m_scr.shape[1]))
    l_scr[:h] = jnp.broadcast_to(l_new, (h, l_scr.shape[1]))

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:h, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = ((acc_scr[:h] / l)[:, None, :]).astype(o_ref.dtype)


def _decode_pallas(q, k_cache, v_cache, bias, *, scale, block_k, interpret):
    b, one, h, d = q.shape
    max_len, kv_h = k_cache.shape[1], k_cache.shape[2]
    if kv_h != h:
        # GQA: expand the cache to full heads for the kernel (the
        # per-kv-head block formulation violates the (8,128) tiling rule
        # for small groups); the expansion costs grp x cache traffic,
        # still a net win over materializing [h, max_len] scores
        k_cache = _repeat_kv(k_cache, h // kv_h)
        v_cache = _repeat_kv(v_cache, h // kv_h)
    nk = max_len // block_k
    scr_rows = max(h, 8)   # TPU sublane tile
    # q enters as [b, h, 1, d]: the kernel needs the head dim leading
    # for Mosaic's batch-dim-0 dot rule (the [h, d] -> [kv, grp, d]
    # reshape of the head dim is an unsupported shape cast in-kernel)
    q_t = q.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_kernel, scale=scale, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, h, 1, d), lambda ib, j: (ib, 0, 0, 0)),
            pl.BlockSpec((1, block_k, h, d), lambda ib, j: (ib, j, 0, 0)),
            pl.BlockSpec((1, block_k, h, d), lambda ib, j: (ib, j, 0, 0)),
            pl.BlockSpec((1, h, 1, block_k), lambda ib, j: (ib, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, h, 1, d), lambda ib, j: (ib, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((scr_rows, 128), jnp.float32),
            pltpu.VMEM((scr_rows, 128), jnp.float32),
            pltpu.VMEM((scr_rows, d), jnp.float32),
        ],
        interpret=interpret,
    )(q_t, k_cache, v_cache, bias)
    return out.transpose(0, 2, 1, 3)                      # [b, 1, h, d]


def _repeat_kv(x, n_rep):
    if n_rep == 1:
        return x
    b, l, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None], (b, l, h, n_rep, d)) \
        .reshape(b, l, h * n_rep, d)


def _gqa_reference(q, k, v, bias, scale):
    """``mha_reference`` over a GROUPED cache, without expanding it:
    query head ``kv * g + i`` attends kv head ``kv`` (the ``_repeat_kv``
    grouping), so the products, the fp32 softmax statistics and the
    output are those of ``mha_reference(q, _repeat_kv(k), _repeat_kv(v))``
    — but K/V are read once per kv head instead of being materialized
    ``g`` times.  A batched multi-token prefill over a slot's whole
    capacity spent more device time writing that expansion than in its
    matmuls.  ``bias`` is 4-D, broadcastable to [b, h, l, max_len]."""
    b, l, h, d = q.shape
    kv_h = k.shape[2]
    g = h // kv_h
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(b, l, kv_h, g, d), k,
                        preferred_element_type=jnp.float32) * scale
    bias = bias.astype(jnp.float32)
    bias = bias[:, :, None] if bias.shape[1] == 1 else \
        bias.reshape(bias.shape[0], kv_h, g, *bias.shape[2:])
    logits = logits + bias
    weights = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    weights = weights / weights.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights.astype(v.dtype), v)
    return out.reshape(b, l, h, v.shape[-1]).astype(q.dtype)


def _multichip_mesh():
    """True when the trace-time serving mesh spans more than one device
    on the ``model``/``data`` axes — AND we are not already inside a
    ``shard_map`` body (the per-shard context sees only local arrays;
    re-triggering the mesh bypass there would route every shard to the
    gather reference and defeat the dispatch).

    GSPMD cannot partition a ``pallas_call``, so on a multi-device mesh
    the paged decode runs the kernel through the ``shard_map`` dispatch
    in :func:`paged_decode_attention` (each device runs the kernel over
    its kv-head/slot shard); the dense-cache :func:`decode_attention`
    still falls back to the jnp reference, which shards cleanly under
    GSPMD.  ``force_kernel`` still overrides (single-device parity
    tests)."""
    from deepspeed_tpu import comm as dist
    mesh = dist.get_mesh()
    if mesh is None:
        return False
    if not any(int(mesh.shape.get(a, 1)) > 1 for a in ("model", "data")):
        return False
    return not _inside_shard_map(mesh)


def _segment_entries(live, width):
    """A flat work list from per-segment counts: ``live`` int32 [n] says
    how many entries (at most ``width``) segment s holds; entry i of the
    list, in segment order, is (``seg[i]``, ``k[i]``), the k-th entry of
    segment seg.  Returns (seg, k, total), seg and k int32 [n * width]
    (past ``total`` they still name a valid segment and offset, so a
    table lookup through them stays in range) and total int32 [1]."""
    n = live.shape[0]
    ends = jnp.cumsum(live)
    i = jnp.arange(n * width, dtype=jnp.int32)
    seg = jnp.minimum(
        jnp.searchsorted(ends, i, side="right", method="compare_all"),
        n - 1).astype(jnp.int32)
    k = jnp.minimum(i - (ends - live)[seg], width - 1).astype(jnp.int32)
    return seg, k, ends[-1:].astype(jnp.int32)


def _first_live_page(positions, page_size, window):
    """The first page a decode query at ``positions`` visits under a
    sliding ``window`` (the page of position ``pos - window + 1``); 0
    without one."""
    if not window:
        return 0
    return jnp.maximum(positions - window + 1, 0) // page_size


def _live_pairs(page_table, positions, active, page_size, window=0):
    """The decode kernel's grid, from the step's own inputs: the live
    (slot, page) pairs of the ACTIVE slots in slot order.  A slot holds
    ``positions // page_size + 1`` live pages (its cursor's page
    included; under a ``window`` those from :func:`_first_live_page`
    on) if it is active and none if not.  Returns (``pair`` int32
    [slots * max_pages]: entry i is ``slot * max_pages + k`` of the i-th
    live pair, the flat index of its page-table entry; ``pages`` int32
    [slots * max_pages]: that entry's page id, 0 past the live entries;
    ``n`` int32 [1]: how many entries are live).  Nothing here depends
    on a layer, so XLA computes it once a decode step for all of them."""
    slots, maxp = page_table.shape
    live = jnp.minimum(positions // page_size + 1, maxp)
    if window:
        lo = _first_live_page(positions, page_size, window)
        live = live - lo
    if active is not None:
        live = jnp.where(active, live, 0)
    slot, k, n = _segment_entries(live, maxp)
    if window:
        k = jnp.minimum(k + lo[slot], maxp - 1)
    pair = slot * maxp + k
    pages = jnp.where(jnp.arange(pair.shape[0]) < n[0],
                      page_table.reshape(-1)[pair], 0)
    return pair, pages, n


def _paged_decode_kernel(pair_ref, page_ref, pos_ref, n_ref, q_ref, k_ref,
                         *rest, scale, page_size, maxp, quantized,
                         value_dim=None, window=0):
    """Paged variant of ``_decode_kernel``: one grid step is ALL kv heads
    of one slot against ONE cache page, and the grid is the step's LIVE
    (slot, page) pairs in slot order (:func:`_live_pairs`, prefetched;
    its length is the grid's dynamic bound) — a slot that is idle, still
    prefilling or finished appears in no pair, and a page past a slot's
    cursor in none either, so the kernel's work is what the batch holds
    and not ``slots * max_pages``.  The BlockSpec index maps pick the
    pair's slot (q, output) and page id (K/V), so K/V stream page-by-page
    from the shared pool — the gathered [slots, max_len] copy of the jnp
    fallback never exists — and the next pair's page is in flight while
    this one is computed, across slot boundaries too.  The
    online-softmax state (float32 scratch) is reset at a slot's first
    page and the slot's output row written at its last; a slot in no
    pair keeps the zeros its row of the output starts from.

    ``q`` arrives grouped [slots, kv_heads, group, d] (query head
    kv*group + g belongs to kv head kv — the contiguous grouping
    ``_repeat_kv`` spells out), so MHA is the group == 1 case of the
    same kernel and a GQA pool is never expanded to full heads.  Every
    block spans the pool's trailing (kv_heads, d) dims whole: Mosaic's
    lowering refuses a per-kv-head block (its last two dims must be
    (8,128)-divisible or equal the array's).  The validity mask is
    computed in-kernel from the prefetched per-slot position: key
    position ``page * page_size + offset`` is live iff <= the slot's
    current position (only the cursor's page has dead ones).

    ``quantized`` appends the per-row scale refs ([1, page_size,
    kv_heads, 1] blocks of the parallel scale pool, fetched through the
    SAME page-id index map, so a page and its scales are one unit)
    and dequantizes in VMEM right before the dot — only quantized bytes
    ever stream from HBM.

    ``value_dim`` marks a LATENT pool (ops/quant/kv.py ``c_pages``
    [num_pages, page_size, stored]: one head, no head dim): there is no
    ``v_ref`` — the value is the leading ``value_dim`` features of the
    K block this step already holds in VMEM, so a page costs one DMA.

    ``window`` > 0: the query sees the last ``window`` positions alone
    (its own included), and the slot's pairs start at
    :func:`_first_live_page`, whose oldest visible key keeps the
    statistics finite as position 0 does without a window."""
    if value_dim is None:
        v_ref, rest = rest[0], rest[1:]
    if quantized:
        ks_ref, vs_ref, _, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        _, o_ref, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    ki = jax.lax.rem(pair_ref[i], maxp)
    pos = pos_ref[jax.lax.div(pair_ref[i], maxp)]

    @pl.when(ki == _first_live_page(pos, page_size, window))
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0]                                          # [kv_h, g, d]
    if value_dim is not None:
        k = k_ref[...]                                    # [1, ps, d]
        v = k[:, :, :value_dim]
    else:
        k = k_ref[0]                                      # [ps, kv_h, d]
        v = v_ref[0]
        if quantized:
            k = (k.astype(jnp.float32) *
                 ks_ref[0].astype(jnp.float32)).astype(q.dtype)
            v = (v.astype(jnp.float32) *
                 vs_ref[0].astype(jnp.float32)).astype(q.dtype)
        # leading-batch dot over kv heads (Mosaic supports batch dims
        # only at position 0 on both sides)
        k = k.transpose(1, 0, 2)                          # [kv_h, ps, d]
        v = v.transpose(1, 0, 2)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale       # [kv_h, g, ps]
    k_pos = ki * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, page_size), 2)
    seen = k_pos <= pos
    if window:
        seen &= k_pos > pos - window
    s = jnp.where(seen, s, NEG_INF)

    m_prev = m_scr[:, :, :1]                              # [kv_h, g, 1]
    l_prev = l_scr[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # position 0 of page 0 is live for every listed slot, so m_new is
    # finite from a slot's first page on and exp() needs no
    # fully-masked-row guard
    p = jnp.exp(s - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)               # [kv_h, g, d]
    acc_scr[:] = acc_scr[:] * alpha + pv
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # the slot's last listed page: its cursor's.  With no live pair at
    # all the grid is one step over entry 0, whose row must read zeros
    live = i < n_ref[0]

    @pl.when(jnp.logical_or(
        ki == jnp.minimum(jax.lax.div(pos, page_size), maxp - 1),
        jnp.logical_not(live)))
    def _finalize():
        o_ref[0] = jnp.where(live, acc_scr[:] / l_scr[:, :, :1],
                             0.0).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pages, v_pages, page_table, positions, *,
                         scale, interpret, k_scale=None, v_scale=None,
                         active=None, value_dim=None, window=0):
    slots, one, h, d = q.shape
    page_size = k_pages.shape[1]
    if value_dim is not None:
        # a latent pool [num_pages, page_size, d]: one head, the value
        # read from the key block
        assert v_pages is None and k_scale is None and k_pages.ndim == 3
        kv_h, d_v = 1, value_dim
    else:
        kv_h = k_pages.shape[2]
        d_v = v_pages.shape[3]      # a value may be narrower than a key
    maxp = page_table.shape[1]
    group = h // kv_h
    quantized = k_scale is not None
    # [slots, 1, h, d] -> [slots, kv_h, group, d]: head kv*group + g is
    # kv head kv's g-th query head (the _repeat_kv grouping)
    q_g = q.reshape(slots, kv_h, group, d)
    with jax.named_scope("cache"):
        pair, pages, n = _live_pairs(page_table, positions, active,
                                     page_size, window)

    def slot_index(i, pair, pages, pos, n):
        return (pair[i] // maxp, 0, 0, 0)

    def page_index(i, pair, pages, pos, n):
        return (pages[i], 0, 0, 0)

    q_spec = pl.BlockSpec((1, kv_h, group, d), slot_index)
    out_spec = pl.BlockSpec((1, kv_h, group, d_v), slot_index)
    if value_dim is not None:
        in_specs = [q_spec, pl.BlockSpec(
            (1, page_size, d), lambda *a: page_index(*a)[:3])]
        operands = [q_g, k_pages]
    else:
        in_specs = [q_spec,
                    pl.BlockSpec((1, page_size, kv_h, d), page_index),
                    pl.BlockSpec((1, page_size, kv_h, d_v), page_index)]
        operands = [q_g, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec((1, page_size, kv_h, 1), page_index)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    # the output starts as zeros (aliased in, never fetched): the rows
    # of slots in no pair are never visited
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(jnp.zeros((slots, kv_h, group, d_v), q.dtype))
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               page_size=page_size, maxp=maxp,
                               quantized=quantized, value_dim=value_dim,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(n[0], 1),),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((kv_h, group, 128), jnp.float32),
            pltpu.VMEM((kv_h, group, 128), jnp.float32),
            pltpu.VMEM((kv_h, group, d_v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, kv_h, group, d_v), q.dtype),
        input_output_aliases={4 + len(operands) - 1: 0},
        interpret=interpret,
    )(pair, pages, positions, n, *operands)
    return out.reshape(slots, 1, h, d_v)


_KERNEL_MODE = None       # None -> "auto"; see kernel_mode_scope

PAGED_KERNEL_MODES = ("auto", "force", "reference")


class kernel_mode_scope:
    """Trace-time channel for the paged-kernel dispatch policy: the
    engine wraps every serving trace in
    ``kernel_mode_scope(engine.paged_kernel_mode)`` so
    :func:`paged_decode_attention` resolves kernel-vs-reference with
    the engine's CONFIGURED mode ("auto" | "force" | "reference").
    The mode is an engine-lifetime static — it picks the traced branch,
    so flipping it after the serving fns compiled would not retrace
    (same contract as the mesh/rule-table scopes)."""

    def __init__(self, mode):
        self.mode = mode
        self._saved = None

    def __enter__(self):
        global _KERNEL_MODE
        self._saved = _KERNEL_MODE
        _KERNEL_MODE = self.mode
        return self.mode

    def __exit__(self, *exc):
        global _KERNEL_MODE
        _KERNEL_MODE = self._saved
        return False


def paged_kernel_decision(*, num_heads, num_kv_heads, page_size,
                          mesh=None, mode="auto", has_bias=False,
                          backend=None, multi_token=False):
    """THE paged-attention kernel-eligibility decision, as data: returns
    ``{"path": "kernel"|"reference", "dispatch": "shard_map"|"direct"|
    None, "reason": str}``.  :func:`paged_decode_attention` makes this
    exact decision at trace time; the engine surfaces it through
    ``serving_mesh_info()``/``health()`` (one-shot logged at pool
    construction) so an accidental reference-path fallback is VISIBLE
    instead of silent — the decision depends only on static config
    (model head counts, page size, mesh, backend, mode), never on
    per-step data, so the two views cannot disagree.

    ``multi_token`` answers for the prefill / verify path
    (``kv_cache._paged_multi``) instead of single-token decode: the
    same facts decide — the ``paged_prefill`` kernel
    (ops/attention/paged_prefill.py) takes every chunk length (it pads
    and tiles the chunk itself), quantized pools and the decode
    kernel's ``shard_map`` axes — and the reason names the kernel or
    the fallback of THAT path.  A sequence-parallel prefill dispatch
    never asks: its attention is the distributed transport's.

    A latent pool (one leaf read as key and value) asks as one KV head:
    the same rule decides, and its ``shard_map`` form splits slots over
    ``data`` alone (the engine refuses a ``model`` axis over one head).

    ``dispatch`` says HOW the kernel runs: "direct" is a plain
    ``pallas_call`` (single device), "shard_map" wraps it per-shard
    over the mesh (each device runs the kernel on its kv-head/slot
    shard — GSPMD cannot partition a ``pallas_call``, so multi-chip
    kernels only exist through this dispatch)."""
    if mode not in PAGED_KERNEL_MODES:
        raise ValueError(f"unknown paged-kernel mode {mode!r}; pick one "
                         f"of {PAGED_KERNEL_MODES}")
    multi = False
    if mesh is not None:
        multi = any(int(mesh.shape.get(a, 1)) > 1
                    for a in ("model", "data"))
    disp = "shard_map" if multi else "direct"

    def ref(reason):
        if multi_token:
            reason += " — prefill and verify gather each row's whole " \
                      "page table and attend in jnp"
        return {"path": "reference", "dispatch": None, "reason": reason}

    def kernel(reason):
        if multi_token:
            reason += " — prefill and verify run the paged_prefill " \
                      "kernel over each row's live pages"
        return {"path": "kernel", "dispatch": disp, "reason": reason}

    if has_bias:
        return ref("additive bias (ALiBi) rides the gather reference "
                   "(the paged kernel computes only the positional "
                   "mask in-kernel)")
    if num_kv_heads and num_heads % num_kv_heads != 0:
        return ref(f"num_heads={num_heads} is not a multiple of "
                   f"num_kv_heads={num_kv_heads}")
    if mode == "reference":
        return ref("paged_kernel='reference' pins the gather fallback")
    if mode == "force":
        return kernel("paged_kernel='force' pins the kernel "
                      "(interpret mode off-TPU)")
    backend = jax.default_backend() if backend is None else backend
    if backend != "tpu":
        return ref(f"off-TPU backend {backend!r}: interpret-mode Pallas "
                   "is slower than the jnp reference "
                   "(paged_kernel='force' overrides for parity runs)")
    if page_size is None:
        return ref("page size unknown until the paged pool is built")
    if page_size % 128 != 0:
        # `blocker` is the STRUCTURED spelling of this gate: the
        # engine's constructor-time warning keys on it, never on the
        # human-readable reason wording
        out = ref(f"page_size={page_size} is not a multiple of 128 "
                  "(the TPU lane tile): the paged Pallas kernel "
                  "cannot tile its pages — pick page_size 128/256 to "
                  "enable the kernel path")
        out["blocker"] = "page_size"
        return out
    return kernel("TPU backend, 128-aligned pages"
                  + (" — shard_mapped over the mesh" if multi else ""))


def trace_time_decision(num_heads, num_kv_heads, page_size, *, has_bias,
                        force_kernel=False, multi_token=False):
    """(:func:`paged_kernel_decision` for the program being traced, the
    mesh its ``shard_map`` dispatch runs over — None for every other
    dispatch).  The engine's configured mode rides
    :class:`kernel_mode_scope`.  Inside a ``shard_map`` body the mesh
    axes are bound: no mesh is seen there and the decision resolves
    "direct", so the per-shard kernel never re-triggers the multi-chip
    dispatch."""
    from deepspeed_tpu import comm as dist
    mesh = dist.get_mesh()
    if mesh is not None and _inside_shard_map(mesh):
        mesh = None
    mode = "force" if force_kernel else (_KERNEL_MODE or "auto")
    decision = paged_kernel_decision(
        num_heads=num_heads, num_kv_heads=num_kv_heads,
        page_size=page_size, mesh=mesh, mode=mode, has_bias=has_bias,
        multi_token=multi_token)
    return decision, mesh if decision["dispatch"] == "shard_map" else None


def _shard_map_axes(mesh, slots, h, kv_h):
    """Resolve which mesh axes the shard_map dispatch partitions over,
    from the ACTIVE serving rule table (serving/sharding.py
    ``config_scope`` — the same trace-time channel
    ``constrain_kv_pages`` reads, so the per-shard split always agrees
    with the pinned pool/carry shardings).  An axis that cannot divide
    its dim degrades to replicated for that dim — exactly mirroring
    ``ServingShardingConfig.resolve``'s slot-family degrade."""
    from deepspeed_tpu.serving.sharding import active_rules
    rules = active_rules()
    kv_ax = rules.get("kv_heads")
    slot_ax = rules.get("slots")
    msize = int(mesh.shape.get(kv_ax, 1)) if kv_ax else 1
    dsize = int(mesh.shape.get(slot_ax, 1)) if slot_ax else 1
    head_ax = kv_ax if (msize > 1 and kv_h % msize == 0 and
                        h % msize == 0) else None
    s_ax = slot_ax if (dsize > 1 and slots % dsize == 0) else None
    return head_ax, s_ax


def _paged_decode_shard_map(q, k_pages, v_pages, page_table, positions,
                            *, scale, interpret, mesh, k_scale=None,
                            v_scale=None, active=None, value_dim=None,
                            window=0):
    """Run the paged kernel per-shard over the serving mesh: kv pools
    enter sharded [pages, ps, KV_H/model, dim] (each device holds its
    kv-head slice of EVERY page — page ids are global, the host-side
    page table needs no translation), q/page_table/positions/active
    shard their slot dim over ``data``, and each shard runs the ordinary
    kernel on its local arrays — its work list is its own slots' live
    pages — and GQA groups stay intact (the q-head group belonging
    to the local kv shard rides in; a sharded MHA model sees grouped
    heads the same way).  Inside the body ``_multichip_mesh`` reports
    False (the axis names are bound), so nothing re-triggers the mesh
    bypass.  A latent pool (``value_dim`` set, ``v_pages`` None) enters
    whole on every device — one head, nothing to split — and only the
    slots shard."""
    from jax.sharding import PartitionSpec as P
    slots, _, h, d = q.shape
    latent = value_dim is not None
    kv_h = 1 if latent else k_pages.shape[2]
    head_ax, slot_ax = _shard_map_axes(mesh, slots, h, kv_h)
    q_spec = P(slot_ax, None, head_ax, None)
    pool_spec = P(None, None, None) if latent else \
        P(None, None, head_ax, None)
    if active is None:
        active = jnp.ones((slots,), bool)
    in_specs = [q_spec, pool_spec, None if latent else pool_spec,
                P(slot_ax, None), P(slot_ax), P(slot_ax)]
    args = [q, k_pages, v_pages, page_table, positions, active]
    if k_scale is not None:
        in_specs += [pool_spec, pool_spec]
        args += [k_scale, v_scale]

    def body(q_, kp_, vp_, pt_, pos_, act_, *scales):
        ks, vs = scales if scales else (None, None)
        return _paged_decode_pallas(q_, kp_, vp_, pt_, pos_, scale=scale,
                                    interpret=interpret, k_scale=ks,
                                    v_scale=vs, active=act_,
                                    value_dim=value_dim, window=window)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=q_spec, check_vma=False)(*args)


def gather_pages(pages, page_table):
    """[num_pages, page_size, kv_h, d] gathered through [slots, maxp] ->
    contiguous per-slot buffers [slots, maxp*page_size, kv_h, d].
    Unallocated table entries must point at a valid page id (0); the
    caller's validity mask covers those positions."""
    g = pages[page_table]
    s, mp, ps, h, d = g.shape
    return g.reshape(s, mp * ps, h, d)


def paged_decode_attention(q, k_pages, v_pages, page_table, positions, *,
                           scale=None, bias=None, interpret=None,
                           force_kernel=False, k_scale=None,
                           v_scale=None, active=None, value_dim=None,
                           window=0):
    """Single-token attention of ``q`` [slots, 1, heads, d] over a PAGED
    cache: a shared pool ``k_pages``/``v_pages`` [num_pages, page_size,
    kv_heads, d] indexed through ``page_table`` [slots, max_pages] with
    per-slot query ``positions`` [slots] (key positions <= position are
    live — the current token's k/v must already be written).

    ``active`` (optional, bool [slots]; None = every slot) marks the
    slots whose output the step uses.  The kernel walks the live pages
    of those alone — its work is proportional to what the batch holds,
    not to ``slots * max_pages`` — and writes an inactive slot's row as
    zeros; the fallback computes every row (an inactive row's output is
    finite and ignored).

    ``k_scale``/``v_scale`` (optional, [num_pages, page_size, kv_heads,
    1] f32) mark a QUANTIZED pool (int8/fp8 payload + per-row scales,
    ops/quant/kv.py): the Pallas path fetches each page's scale block
    through the same scalar-prefetched page-table index map and
    dequantizes in VMEM right before the dot (only quantized bytes
    stream from HBM — the bandwidth win), while the fallback gathers
    payload + scales and dequantizes the contiguous buffers (the jnp
    reference for CPU/mesh parity).

    The Pallas path streams K/V page-by-page via scalar-prefetched table
    lookups (true PagedAttention: no per-slot contiguous copy); GQA
    pools run the same kernel with q grouped per kv head (the pool is
    never expanded to full heads), and on a multi-device
    mesh it runs per-shard under ``shard_map`` (kv heads over
    ``model``, slots over ``data``; see ``_paged_decode_shard_map``).
    The fallback gathers pages into contiguous buffers and reuses
    :func:`decode_attention` — correct everywhere (it is the jnp
    correctness oracle, and what GSPMD partitions when the kernel is
    ineligible), but it materializes [slots, max_pages*page_size] K/V
    transiently.  :func:`paged_kernel_decision` is the one
    kernel-vs-reference rule; the engine exports it through
    ``serving_mesh_info()``/``health()``.

    ``bias`` (optional, broadcastable to [slots, heads, 1, max_len])
    carries extra additive terms (ALiBi); when present the fallback path
    runs (the paged kernel computes only the positional mask in-kernel).

    ``v_pages=None, value_dim=n`` is the SHARED READ of a latent pool
    (``k_pages`` is the one leaf ``c_pages`` [num_pages, page_size, d]
    of ops/quant/kv.py, one head and no head dim): every query head's
    key is the stored vector and its value that vector's leading ``n``
    features — the kernel fetches a page once and slices the block in
    VMEM; the fallback gathers the leaf once.  The output is ``n`` wide.

    ``window`` > 0 is sliding-window attention: key positions ``position
    - window < p <= position`` are live, and the kernel walks the pages
    that hold them alone.

    Both paths are ``lax.scan``-compatible: every branch decision here
    is made on static python values, and ``positions``/``page_table``
    may be traced carries — the fused multi-step serving decode
    (``InferenceEngine.decode_multi``) scans this step with on-device
    token feedback, so nothing in here may force a host sync or a
    per-iteration retrace.
    """
    slots, l, h, d = q.shape
    page_size = k_pages.shape[1]
    kv_h = 1 if value_dim is not None else k_pages.shape[2]
    max_len = page_table.shape[1] * page_size
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    positions = positions.astype(jnp.int32)

    # Kernel-vs-reference dispatch (all static, scan-safe): the
    # decision is paged_kernel_decision's — the same function the
    # engine surfaces through serving_mesh_info()/health(), so the
    # active path is always visible to operators.  On a multi-device
    # mesh the kernel runs through the shard_map dispatch, each device
    # over its kv-head/slot shard
    # (GSPMD cannot partition a pallas_call, so this dispatch is the
    # ONLY multi-chip kernel path — the jnp reference below remains
    # the GSPMD-partitionable correctness oracle).
    decision, mesh = trace_time_decision(
        h, kv_h, page_size, has_bias=bias is not None,
        force_kernel=force_kernel)
    if l == 1 and decision["path"] == "kernel":
        call = _paged_decode_pallas if mesh is None else \
            functools.partial(_paged_decode_shard_map, mesh=mesh)
        return call(q, k_pages, v_pages, page_table.astype(jnp.int32),
                    positions, scale=scale, interpret=interpret,
                    k_scale=k_scale, v_scale=v_scale, active=active,
                    value_dim=value_dim, window=window)

    if value_dim is not None:
        k_full = gather_pages(k_pages[:, :, None], page_table)
        v_full = k_full[..., :value_dim]
    else:
        k_full = gather_pages(k_pages, page_table)
        v_full = gather_pages(v_pages, page_table)
    if k_scale is not None:
        from deepspeed_tpu.ops.quant.kv import dequantize_kv_rows
        k_full = dequantize_kv_rows(k_full, gather_pages(k_scale,
                                                         page_table),
                                    q.dtype)
        v_full = dequantize_kv_rows(v_full, gather_pages(v_scale,
                                                         page_table),
                                    q.dtype)
    k_pos = jnp.arange(max_len)
    mask = k_pos[None, None, None, :] <= positions[:, None, None, None]
    if window:
        mask &= k_pos[None, None, None, :] > \
            positions[:, None, None, None] - window
    full_bias = jnp.where(mask, 0.0, jnp.finfo(jnp.float32).min)
    if bias is not None:
        full_bias = full_bias + bias.astype(jnp.float32)
    return decode_attention(q, k_full, v_full, bias=full_bias, scale=scale,
                            interpret=interpret)


def decode_attention(q, k_cache, v_cache, *, bias, scale=None,
                     interpret=None, block_k=None, force_kernel=False):
    """Attention of `q` [b, l, heads, d] over a cache buffer
    [b, max_len, kv_heads, d] with additive `bias` (broadcastable to
    [b, heads, l, max_len]) carrying the validity mask.

    Single-token decode (l == 1) runs the Pallas kernel on TPU;
    multi-token (prefill into a cache) falls back to the jnp oracle. GQA
    caches (kv_heads < heads) are consumed directly by the kernel.

    Off-TPU the kernel would run in interpret mode — a grid of emulated
    Mosaic steps that is both slower at runtime than the plain jnp
    reference and much heavier to trace, which matters now that the
    serving decode loops this step under ``lax.scan``
    (``InferenceEngine.decode_multi`` traces the body once per horizon
    bucket). Interpret-mode decode therefore routes to the reference
    path unless ``force_kernel`` pins the kernel (parity tests).
    """
    from deepspeed_tpu.ops.attention.reference import mha_reference

    b, l, h, d = q.shape
    kv_h = k_cache.shape[2]
    max_len = k_cache.shape[1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    if l == 1 and h % kv_h == 0 and max_len % (block_k or 128) == 0 and \
            v_cache.shape[-1] == d and \
            (force_kernel or not (interpret or _multichip_mesh())):
        # the K and V blocks span ALL heads and are double-buffered (4
        # resident copies): cap one block at 2 MiB so the set stays
        # inside Mosaic's 16 MiB scoped-VMEM default at any head count
        # (max_len is a multiple of 128 here, so 128 always divides)
        cap = (2 << 20) // (h * d * k_cache.dtype.itemsize)
        block_k = block_k or next(
            (bk for bk in (1024, 512, 256) if bk <= cap
             and max_len % bk == 0), 128)
        bias_full = jnp.broadcast_to(
            bias.astype(jnp.float32), (b, h, 1, max_len))
        return _decode_pallas(q, k_cache, v_cache, bias_full, scale=scale,
                              block_k=block_k, interpret=interpret)

    if h == kv_h:
        return mha_reference(q, k_cache, v_cache, causal=False, bias=bias,
                             scale=scale)
    return _gqa_reference(q, k_cache, v_cache, bias, scale)
