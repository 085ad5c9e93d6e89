"""The attention seam: what a KV cache looks like, and which kernel reads it.

A model file owns its projections (and their LoRA deltas), rotary /
ALiBi and the head geometry; everything between ``q, k, v`` and the
attention output is :func:`attend`.  Three caches exist (and a serving
dispatch's pools may hold four kinds of per-layer entry: pages of K and
V, here; LATENT pages — one vector a token that every query head reads
as key and, in its leading features, as value (multi-head latent
attention), ops/quant/kv.py ``latent_pool_layer`` — here too; a
recurrent layer's per-slot STATE, ops/ssm/state.py; a sliding-window
layer's per-slot RING, ops/attention/window.py):

* ``None`` — training / full forward: ``attn_impl`` picks the kernel.
* the dense cache of ``generate()`` (:func:`init_dense`): per layer
  ``{"k", "v": [batch, max_len, kv_heads, d], "index"}``, appended with
  ``dynamic_update_slice``.  It is the oracle serving is held against.
* a :class:`PagedStep` — one serving dispatch over the paged pools
  (``ops/quant/kv.py`` owns their layout and quantisation): K/V live in
  a shared fixed-page pool indexed through a per-slot page table, so
  sequences of any length share one preallocated cache and the jit
  signature is fixed by (slots, chunk, pool, table) shapes regardless
  of request churn.  Whoever builds the step NAMES its mode
  (:func:`prefill_step`, :func:`verify_step`, :func:`decode_step`);
  nothing is inferred from which fields are present.

The top level of a model asks for :func:`positions`, layer ``i``'s
:func:`layer_view`, the :func:`head_rows` worth a vocabulary projection
and the :func:`advance`-d cache to return.

Everything here is a plain function called from inside the model's own
``attn`` flax scope: the benchmark's readers find the paged DECODE
kernel and the flash kernels by that scope name in the HLO, so no flax
submodule, ``jax.named_scope`` or kernel ``name=`` may come between
``attn`` and either of those two.  The third kernel, paged flash-prefill
(ops/attention/paged_prefill.py), is NAMED for the same reason turned
round: inside the same scope it would be counted as paged decode, so
its call is jitted under its own name and its instruction reads
``paged_prefill``.  The scopes this file does open (``cache`` round the
page writes and the lengths' bookkeeping, ``head`` round the boundary
rows) close before a kernel is called: they name what
``tracing.component`` reads and leave every kernel's name the
``attn`` scope's.
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.ops.attention import window as window_ops
from deepspeed_tpu.ops.attention.decode import (_repeat_kv,
                                                decode_attention,
                                                paged_decode_attention,
                                                trace_time_decision)
from deepspeed_tpu.ops.attention.flash import flash_attention
from deepspeed_tpu.ops.attention.paged_prefill import paged_flash_prefill
from deepspeed_tpu.ops.attention.reference import mha_reference
from deepspeed_tpu.ops.quant.kv import (LATENT_LEAF, page_leaf,
                                        paged_gather, paged_pool_layer,
                                        paged_write)


@dataclasses.dataclass(frozen=True)
class PagedStep:
    """One serving dispatch over the paged pools.  ``layers`` is the
    list of per-layer pool dicts at the model's top level and ONE
    layer's dict in a :func:`layer_view`; ``adapters`` likewise."""
    mode: str                   # "prefill" | "verify" | "decode" (static)
    layers: Any
    page_table: Any             # int32 [slots, max_pages]
    lengths: Any                # int32 [slots]
    count: Any                  # per batch row: columns whose K/V is written
    rows: Any = None            # prefill: the slot id of each batch row
    adapters: Any = None        # stacked LoRA pack (models/lora.py) or None
    seq_parallel: Optional[Tuple[str, str]] = None   # (mesh axis, impl)

    @property
    def pools(self):
        """The pools pytree the engine donates and takes back."""
        return {"layers": self.layers}


def prefill_step(layers, page_table, lengths, slot, n_valid, *,
                 adapters=None, seq_parallel=None):
    """Chunked prefill, one row per prefilling slot: row r carries the
    next chunk of ``slot[r]`` (b == rows, l == chunk).  Columns past
    ``n_valid[r]`` are padding (a padding ROW has n_valid == 0): their
    K/V writes drop and their outputs are unused.  ``seq_parallel`` =
    (axis, impl) runs the one-row chunk's attention distributed over a
    sequence mesh axis (static, from the engine's sequence plan)."""
    return PagedStep("prefill", layers, page_table, lengths, n_valid,
                     rows=slot, adapters=adapters,
                     seq_parallel=seq_parallel)


def verify_step(layers, page_table, lengths, widths, *, adapters=None):
    """Teacher-forced multi-token verify (speculative decode): b ==
    slots, l == K+1 candidate tokens per slot.  Column j of slot s
    writes position lengths[s] + j when j < widths[s] (0 for inactive
    slots) — one batched forward scores every draft instead of one scan
    step per token."""
    return PagedStep("verify", layers, page_table, lengths, widths,
                     adapters=adapters)


def decode_step(layers, page_table, lengths, active, *, adapters=None):
    """Continuous-batch decode: b == slots, l == 1; inactive slots
    write nowhere and produce ignored outputs."""
    return PagedStep("decode", layers, page_table, lengths, active,
                     adapters=adapters)


def init_dense(num_layers, batch_size, max_len, kv_heads, head_dim, dtype):
    """Empty dense KV cache pytree (reference inference_context.h
    workspace)."""
    shape = (batch_size, max_len, kv_heads, head_dim)
    return {"layers": [{"k": jnp.zeros(shape, dtype),
                        "v": jnp.zeros(shape, dtype),
                        "index": jnp.int32(0)} for _ in range(num_layers)]}


def init_paged(num_layers, num_pages, page_size, kv_heads, head_dim, dtype):
    """Per-layer paged KV pools: ``num_pages`` fixed pages of
    ``page_size`` tokens shared by every live sequence through a page
    table (host-owned, passed per step; only the pools live here).
    GQA pools are sized to the kv heads and stay grouped.  ``dtype``
    may be a quantized kv-dtype name ("int8"/"fp8"): payload pools plus
    parallel per-row f32 scale pools (ops/quant/kv.py).  A model whose
    layers differ (in head counts, in kind of cache) builds its entries
    itself: ``paged_pool_layer`` takes one layer's geometry, a value
    width beside the key's included."""
    return {"layers": [paged_pool_layer(num_pages, page_size, kv_heads,
                                        head_dim, dtype)
                       for _ in range(num_layers)]}


# ------------------------------------------------ a model's top level

def positions(cache, b, l):
    """Absolute positions [b, l] of this call's tokens.  A prefill row
    starts at ``lengths[slot]``, which a prefix-cache hit seeds to the
    cached boundary (not 0, not page-aligned)."""
    with jax.named_scope("cache"):
        if isinstance(cache, PagedStep):
            lens = cache.lengths if cache.mode != "prefill" \
                else cache.lengths[cache.rows]
            pos = lens[:, None]
            if cache.mode != "decode":
                pos = pos + jnp.arange(l)[None, :]
            return jnp.broadcast_to(pos, (b, l))
        start = 0 if cache is None else cache["layers"][0]["index"]
        return jnp.broadcast_to(start + jnp.arange(l)[None], (b, l))


def layer_view(cache, i):
    """Layer ``i``'s cache: its pools / dense buffers, the step's shared
    fields, and its slice of the adapter pack (ids/scale shared)."""
    if not isinstance(cache, PagedStep):
        return None if cache is None else cache["layers"][i]
    ad = cache.adapters
    if ad is not None:
        ad = dict(ad["layers"][i], ids=ad["ids"], scale=ad["scale"])
    return dataclasses.replace(cache, layers=cache.layers[i], adapters=ad)


def adapters_of(cache):
    """(a layer view's adapters, the slot id of each batch row — None
    where row r IS slot r: decode and verify run b == num_slots)."""
    if not isinstance(cache, PagedStep):
        return None, None
    return cache.adapters, cache.rows


def head_rows(cache, x):
    """Chunked prefill consumes ONLY each row's boundary position —
    skip the full-vocab head for the chunk's other positions (~30% of a
    prefill step at gpt2-small shapes)."""
    if isinstance(cache, PagedStep) and cache.mode == "prefill":
        with jax.named_scope("head"):
            return jnp.take_along_axis(
                x, jnp.maximum(cache.count - 1, 0)[:, None, None], axis=1)
    return x


def advance(cache, new_layers):
    """The cache a model returns beside its logits."""
    if not isinstance(cache, PagedStep):
        return {"layers": new_layers}
    with jax.named_scope("cache"):
        if cache.mode == "prefill":
            lengths = cache.lengths.at[cache.rows].add(cache.count)
        elif cache.mode == "verify":
            # widths columns written per slot (already 0 for inactive
            # slots); the engine's verify primitive rewinds this to the
            # emitted-token count after acceptance
            lengths = cache.lengths + cache.count
        else:
            lengths = cache.lengths + cache.count.astype(jnp.int32)
    return dataclasses.replace(cache, lengths=lengths, layers=new_layers)


# ------------------------------------------------------ inside ``attn``

def attend(q, k, v, positions, cache, *, impl="auto", window=0,
           key_bias=None, sink=None, scale=None, value_dim=None):
    """Attention of q [b, l, h, d] over k [b, l, kv_h, d] / v [b, l,
    kv_h, d_v] and what ``cache`` (a :func:`layer_view`) already holds.
    Returns (out [b, l, h, d_v], the layer's updated cache or None).
    ``window`` > 0 is local sliding-window attention (a query sees the
    last ``window`` positions, its own included); on a
    :class:`PagedStep` such a layer's entry is a ring a slot, not pages
    (ops/attention/window.py): XLA ops over a ring of ``window`` rows,
    the paged kernels below over one of two pages more, through
    ``window.page_view``.  ``key_bias`` maps key positions [n] to
    an additive bias broadcastable to [b, h, l, n] (ALiBi: softmax is
    shift-invariant per query row, so slopes * key_pos == slopes *
    (key_pos - query_pos)).  ``sink`` [h] is one logit a query head
    that joins the softmax and whose column is dropped.  A sink, or a
    value narrower than a key, takes the jnp forms of window.py
    wherever no page pool is involved; over pages the two paged
    kernels take ``d != d_v`` as they are.  ``scale`` (over pages only)
    replaces ``1 / sqrt(d)`` where q and k arrive zero-padded to the
    width the pool stores.

    The LATENT case — ``v`` None and ``value_dim`` set, on a
    :class:`PagedStep` whose entry is a latent leaf only: ``k`` [b, l,
    w] is ONE vector a token (the normed latent and the rotated shared
    key), ``q`` the absorbed queries; every head scores against the
    cached vector and sums its leading ``value_dim`` features, so the
    output is [b, l, h, value_dim].  A chunk's ``q`` arrives [b, l, h,
    stored], at the width the POOL stores (``ops/quant/kv.
    latent_stored_dim``: the model's absorption contraction writes the
    zero columns itself, models/deepseek_v3.py), and the key alone is
    padded here — a [b, l, w] tensor, 1 / heads of the query; a ``q``
    at the published ``w`` (a decode step's few rows) is widened too.
    ``scale`` is required (the width is not the published head's).  The
    chunk's rows are written through the page table first
    (``paged_write``'s rules), then decode or the prefill kernel read
    each page ONCE."""
    if value_dim is not None:
        assert v is None and scale is not None and key_bias is None \
            and sink is None and window == 0
        assert isinstance(cache, PagedStep) and LATENT_LEAF in cache.layers, \
            "the latent form of attend runs over a latent page pool only"
        # the pool's own width (ops/quant/kv.latent_stored_dim): zeros
        # add nothing to a score, and the value is the leading features.
        # A chunk's q is at that width already: what is padded there is
        # the key alone, and no pad of 0 columns is traced
        stored = cache.layers[LATENT_LEAF].shape[-1]

        def widen(x):
            lacks = stored - x.shape[-1]
            return x if not lacks else jnp.pad(
                x, ((0, 0),) * (x.ndim - 1) + ((0, lacks),))
        q, k = widen(q), widen(k)
    elif sink is not None or q.shape[-1] != v.shape[-1]:
        assert key_bias is None, "no key bias beside a sink or d != d_v"
        if cache is None:
            return window_ops.attend_fresh(q, k, v, window=window,
                                           sink=sink), None
        if not isinstance(cache, PagedStep):
            return window_ops.attend_dense(q, k, v, positions, cache,
                                           window=window, sink=sink)
    if cache is None:
        return _attend_fresh(q, k, v, impl, window, key_bias), None
    if not isinstance(cache, PagedStep):
        return _attend_dense(q, k, v, positions, cache, window, key_bias)
    ring = None
    if window > 0:
        assert key_bias is None, "a window ring takes no key bias"
        ring = cache.layers
        page = window_ops.ring_page_size(ring, window)
        if not page:
            return window_ops.attend_ring(q, k, v, positions, cache,
                                          window=window, sink=sink)
        # a ring longer than its window: a page pool of the layer's
        # own, read by the paged kernels below through a derived table
        assert cache.seq_parallel is None, \
            "sequence-parallel prefill takes no window ring"
        cache, positions = window_ops.page_view(cache, positions, window,
                                                page)
    assert sink is None, "a sink logit over pages: no kernel takes one"
    if cache.mode != "decode":
        out, pools = _paged_multi(q, k, v, positions, cache, key_bias,
                                  scale, value_dim, window)
    else:
        # single-token decode, written out HERE and not behind a call of
        # its own: the Pallas kernel's body is traced below this frame
        # for every layer of every decode program, and on CPython 3.12
        # that trace costs more the deeper the Python stack it runs at
        # (~0.1 s a frame a program, PERF §6 PR 33 c).
        # paged_decode_attention owns the kernel-vs-reference dispatch
        # (the engine's paged_kernel mode rides the trace scope): GQA
        # pools run the per-kv-head BlockSpec kernel grouped, and on a
        # multi-device mesh the kernel runs per-shard under shard_map —
        # kv heads over `model`, slots over `data`, the page table
        # global — so this call site never changes with the topology
        pools, pt, pos = cache.layers, cache.page_table, positions[:, 0]
        num_pages, ps = page_leaf(pools).shape[:2]
        bias = None if key_bias is None else \
            key_bias(jnp.arange(pt.shape[1] * ps))
        with jax.named_scope("cache"):
            page_ids = jnp.where(
                cache.count, pt[jnp.arange(q.shape[0]), pos // ps],
                num_pages)
            pools = paged_write(pools, page_ids, pos % ps, k[:, 0],
                                None if v is None else v[:, 0])
        out = paged_decode_attention(
            q, page_leaf(pools), pools.get("v_pages"), pt, pos, bias=bias,
            k_scale=pools.get("k_scale"), v_scale=pools.get("v_scale"),
            active=cache.count, scale=scale, value_dim=value_dim,
            window=window)
    if ring is not None:
        return out, window_ops.ring_entry(pools, ring)
    # multi-chip serving: pin the pools' kv-head sharding on the updated
    # arrays so GSPMD keeps the scatter/gather split over the `model`
    # axis (no-op on a single-device mesh; GQA pools shard num_kv_heads,
    # so the axis size must divide it — engine-validated); the quantized
    # scale pools share the payload's [pages, ps, kv_heads, 1] axis
    # family and pin identically
    from deepspeed_tpu.serving.sharding import constrain_kv_pages
    return out, {name: constrain_kv_pages(arr)
                 for name, arr in pools.items()}


def _causal_bias(k_pos, pos, key_bias, window=0):
    mask = k_pos[None, None, :] <= pos[:, :, None]            # [b, l, n]
    if window > 0:
        mask &= k_pos[None, None, :] > pos[:, :, None] - window
    bias = jnp.where(mask, 0.0, jnp.finfo(jnp.float32).min)[:, None]
    return bias if key_bias is None else bias + key_bias(k_pos)


def _paged_multi(q, k, v, pos, step, key_bias, scale=None, value_dim=None,
                 window=0):
    """Prefill and verify: write the ``count[r]`` valid columns of each
    row through its row of the page table, then attend causally over
    the row's pages — the ``paged_prefill`` kernel over the LIVE pages
    where :func:`paged_kernel_decision` allows it, else the reference:
    gather the row's whole table and mask.  Writes only touch positions
    >= the row's start, so shared read-only pages below a prefix-cache
    boundary stay immutable, and the write-before-attend order makes
    stale K/V (a copy-on-write tail page, columns a verifier later
    rejects) harmless: every stale position is either overwritten first
    or masked out by k_pos <= position."""
    pools, pt = step.layers, step.page_table
    num_pages, ps = page_leaf(pools).shape[:2]
    b, l = pos.shape
    with jax.named_scope("cache"):
        write = jnp.arange(l)[None, :] < step.count[:, None]
        rows = jnp.arange(b) if step.rows is None else step.rows
        page_ids = jnp.where(write, pt[rows[:, None], pos // ps], num_pages)
        # out-of-bounds page ids drop; quantized pools carry parallel
        # per-row scale pools that the same masked ids update atomically
        pools = paged_write(pools, page_ids, pos % ps, k, v)
        pt_rows = pt if step.rows is None else pt[step.rows]
    if step.seq_parallel is None:
        latent = value_dim is not None
        decision, mesh = trace_time_decision(
            q.shape[2], 1 if latent else k.shape[2], ps,
            has_bias=key_bias is not None, multi_token=True)
        if decision["path"] == "kernel":
            return paged_flash_prefill(q, pools, pt_rows, pos[:, 0],
                                       step.count, mesh=mesh,
                                       scale=scale, value_dim=value_dim,
                                       window=window), pools
    k_slot, v_slot = paged_gather(pools, pt_rows, q.dtype, value_dim)
    if step.seq_parallel is None:
        bias = _causal_bias(jnp.arange(pt.shape[1] * ps), pos, key_bias,
                            window)
        return decode_attention(q, k_slot, v_slot, bias=bias,
                                scale=scale), pools
    assert scale is None and value_dim is None, \
        "sequence-parallel prefill takes no scale and no latent pool"
    # sequence-parallel prefill: the write above already landed the
    # chunk's KV — with ids sequence-sharded, GSPMD all-gathers k/v over
    # the axis for the pool scatter, the collective the comm ledger
    # prices — and attention runs distributed over the axis against the
    # pool gather.  Pages in the pool are identical to the chunked
    # path's, so decode/COW/donation/handoff downstream never notice.
    # The distributed transports take full-head k/v, so GQA pools expand
    # to h heads HERE only — the pool itself stays grouped
    assert b == 1, "sequence-parallel prefill is one row"
    assert key_bias is None, \
        "sequence-parallel prefill does not support alibi"
    from deepspeed_tpu import comm as dist
    from deepspeed_tpu.sequence.prefill import paged_prefill_attention
    rep = q.shape[2] // k.shape[2]
    axis, impl = step.seq_parallel
    out = paged_prefill_attention(
        q, _repeat_kv(k, rep), _repeat_kv(v, rep), _repeat_kv(k_slot, rep),
        _repeat_kv(v_slot, rep), pos[0, 0], dist.get_mesh(), axis=axis,
        impl=impl)
    return out, pools


def _attend_dense(q, k, v, positions, cache, window, key_bias):
    """Append k/v at ``index`` and attend over the whole buffer with a
    positional mask: slot j is visible to the query at absolute position
    p iff j <= p (``index`` is traced, so no dynamic slicing).
    Single-token steps hit the Pallas softmax_context kernel; GQA caches
    are consumed grouped, never expanded."""
    with jax.named_scope("cache"):
        at = (0, cache["index"], 0, 0)
        k_cache = lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), at)
        v_cache = lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), at)
    new_cache = {"k": k_cache, "v": v_cache,
                 "index": cache["index"] + q.shape[1]}
    bias = _causal_bias(jnp.arange(k_cache.shape[1]), positions, key_bias,
                        window)
    return decode_attention(q, k_cache, v_cache, bias=bias), new_cache


def _attend_fresh(q, k, v, impl, window, key_bias):
    l = q.shape[1]
    rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, rep), _repeat_kv(v, rep)
    if window > 0:
        # local sliding-window causal attention (GPT-Neo "local"):
        # query attends to keys in (q_pos - window, q_pos]
        q_pos = jnp.arange(l)[:, None]
        k_pos = jnp.arange(l)[None, :]
        mask = (k_pos <= q_pos) & (k_pos > q_pos - window)
        bias = jnp.where(mask, 0.0, jnp.finfo(jnp.float32).min)[None, None]
        return mha_reference(q, k, v, causal=False, bias=bias)
    if key_bias is not None:
        return mha_reference(q, k, v, causal=True,
                             bias=key_bias(jnp.arange(l)))
    if impl == "auto":
        # Pallas kernel needs block-aligned seq lens; oracle otherwise
        impl = "flash" if (jax.default_backend() == "tpu" and
                           l % 128 == 0) else "reference"
    if impl == "flash":
        return flash_attention(q, k, v, causal=True)
    if impl in ("ring", "ulysses"):
        # sequence/context parallelism over the `sequence` mesh axis
        from deepspeed_tpu import comm as dist
        from deepspeed_tpu.sequence import DistributedAttention
        mesh = dist.get_mesh()
        assert mesh is not None and mesh.shape.get("sequence", 1) > 1, \
            f"attn_impl={impl} needs a mesh with a sequence axis > 1"
        return DistributedAttention(mesh, impl=impl)(q, k, v)
    return mha_reference(q, k, v, causal=True)
