"""Pallas TPU paged flash-prefill attention (the `paged_prefill` kernel).

Multi-token queries — a chunk of chunked prefill, the K+1 candidates of
a speculative verify — attend causally over a PAGED cache, reading K/V
page by page straight from the shared pool through the scalar-prefetched
page table, as ``_paged_decode_kernel`` (ops/attention/decode.py) does
for one token.  What the jnp reference in ``kv_cache._paged_multi`` pays
for — a gathered copy of each row's whole page table, a [chunk, max_len]
bias and float32 scores in HBM, all sized by the slot's CAPACITY — never
exists: a row costs its LIVE pages.

Design:
  * grid = the dispatch's LIVE (row, q tile, page) steps, row-major,
    tile-major, page-minor (:func:`_live_steps`, scalar-prefetched; its
    length is the grid's dynamic bound, as ``_live_pairs`` is the decode
    kernel's): tile t of row r lists the pages up to the last position
    it may see and no more, so a dispatch walks the pages its rows hold
    and not ``rows * tiles * max_pages``.  Pages stay innermost, so the
    online-softmax state (m, l, acc; float32) lives in VMEM scratch
    across a tile's pages, as in the decode kernel and flash.py: reset
    at a tile's page 0, written out at its last listed page.
  * q arrives regrouped per kv head, [rows, kv_heads, l * group, d]
    (row ``j * group + g`` of a kv head's tile is column j of query head
    ``kv * group + g``): one MXU-shaped tile per kv head — Mistral's
    chunk of 32 x group 4 is 128 x 128 — and MHA is group == 1.  A chunk
    whose tile would outgrow VMEM is split over the q-tile grid axis.
  * live pages only: a page is listed iff its first position is <=
    the last position the tile may see, so a page past it costs no DMA
    and no grid step; the next step's page is in flight while this one
    is computed, across tile and row boundaries too.  A padding row
    (``count == 0``) lists ONE step, position 0's page, so every output
    block is written (finite, meaningless) and none needs a zero fill.
    The causal mask ``k_pos <= start + j`` is computed in-kernel.
  * operands in the pool's / q's dtype, scores, statistics and
    accumulator in float32, P cast to V's dtype before P·V: the
    arithmetic of ``_gqa_reference``.  Quantized pools dequantize in
    VMEM right before the dot, as the decode kernel does.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.attention.decode import (_segment_entries,
                                                _shard_map_axes)
from deepspeed_tpu.ops.attention.flash import NEG_INF
from deepspeed_tpu.ops.quant.kv import LATENT_LEAF


def _last_page(start, last, ti, cols, page_size, maxp):
    """Index, in its row's table, of the last page q tile ``ti`` of a
    row may attend to: the page of the row's last WRITTEN position
    ``last``, or of the tile's own last column if that comes first."""
    return jnp.minimum(
        jnp.minimum(last, start + (ti + 1) * cols - 1) // page_size,
        maxp - 1)


def _live_steps(page_table, start, last, cols, tiles, page_size):
    """The kernel's grid, from the dispatch's own inputs: the live
    (row, q tile, page) steps in row-major, tile-major, page-minor
    order.  Tile t of row r holds pages ``0 ... _last_page(r, t)``;
    ``last`` is 0 for a padding row, which therefore holds page 0 alone
    (one step a tile: its output block is written like any other).
    Returns three int32 lists of the grid's capacity, ``rows * tiles *
    max_pages`` -- entry i of ``tile`` is ``r * tiles + t`` of the i-th
    live step, of ``k`` its page's index in the row's table, of
    ``pages`` that page's id (0 past the live entries) -- and ``n``
    int32 [1], how many entries are live: at least one a tile.  Nothing
    here depends on a layer, so XLA computes it once a dispatch for all
    of them.  The lists ride in scalar memory: 256 rows x 256 pages
    still compile for a v5e, 256 x 512 do not."""
    maxp = page_table.shape[1]
    t = jnp.arange(tiles, dtype=jnp.int32)
    live = _last_page(start[:, None], last[:, None], t[None], cols,
                      page_size, maxp).reshape(-1) + 1
    tile, k, n = _segment_entries(live, maxp)
    pages = jnp.where(jnp.arange(tile.shape[0]) < n[0],
                      page_table[tile // tiles, k], 0)
    return tile, k, pages, n


def _row_and_tile(tile, tiles):
    """(row, q tile) of a :func:`_live_steps` ``tile`` entry; a chunk in
    one q tile, every serving geometry so far, divides nothing."""
    if tiles == 1:
        return tile, 0
    return jax.lax.div(tile, tiles), jax.lax.rem(tile, tiles)


def _paged_prefill_kernel(tile_ref, k_idx_ref, page_ref, start_ref, last_ref,
                          q_ref, k_ref, *rest, scale, page_size, group,
                          maxp, tiles, quantized, value_dim=None):
    """One grid step: one q tile of one row, ALL kv heads, against ONE
    cache page; the grid is the dispatch's live steps
    (:func:`_live_steps`), so every step computes.  Blocks span the
    pool's trailing (kv_heads, d) dims whole (see
    ``_paged_decode_kernel``); the per-kv-head products are
    leading-batch dots.  ``value_dim`` marks a latent pool: no
    ``v_ref``, the value is the leading ``value_dim`` features of the K
    block (one DMA a page), as in the decode kernel."""
    if value_dim is None:
        v_ref, rest = rest[0], rest[1:]
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    ri, ti = _row_and_tile(tile_ref[i], tiles)
    ki = k_idx_ref[i]
    tq = q_ref.shape[2]
    cols = tq // group

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    # position 0 is live for every row, so a tile's page 0 is always
    # listed and the statistics are finite from the first page on
    q = q_ref[0]                                          # [kv_h, tq, d]
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
        k = k.transpose(1, 0, 2)                          # [kv_h, ps, d]
        v = v.transpose(1, 0, 2)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale       # [kv_h, tq, ps]
    # tile row r is column r // group: key position p is visible iff
    # p <= start + col, i.e. (p - first column's position) * group
    # <= r — no integer division in-kernel
    k_rel = ki * page_size - start_ref[ri] - ti * cols + \
        jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size), 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, tq, 1), 1)
    s = jnp.where(k_rel * group <= row, s, NEG_INF)

    m_prev = m_scr[:, :, :1]                              # [kv_h, tq, 1]
    l_prev = l_scr[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)               # [kv_h, tq, d]
    acc_scr[:] = acc_scr[:] * alpha + pv
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # the tile's last listed page
    @pl.when(ki == _last_page(start_ref[ri], last_ref[ri], ti, cols,
                              page_size, maxp))
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:, :, :1]).astype(o_ref.dtype)


def _tile_cols(l, kv_h, group):
    """(columns a q tile, padded chunk length): columns are a multiple
    of 8 (the float32 sublane tile, so ``cols * group`` rows tile for
    any group) and one float32 [kv_h, rows, 128] buffer — the scores,
    the accumulator, each statistic — stays within 1 MiB."""
    cap = max(8, (2048 // (kv_h * group)) // 8 * 8)
    cols = min(cap, -(-l // 8) * 8)
    return cols, -(-l // cols) * cols


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "value_dim"))
def paged_prefill(q, k_pages, v_pages, k_scale, v_scale, page_table, start,
                  count, *, scale, interpret, value_dim=None):
    """The kernel call, jitted under its own name: every layer of a
    serving program (and every program of one geometry) shares ONE trace
    of the kernel body — a bare ``pallas_call`` re-traces it per call
    site — and the Mosaic instruction is named ``paged_prefill`` in the
    HLO, so readers that find the decode and flash kernels by the
    model's ``attn`` scope never count this one.  ``v_pages=None,
    value_dim=n`` is the shared read of a latent pool (``k_pages`` the
    one leaf [num_pages, page_size, d]; see ``paged_decode_attention``)."""
    b, l, h, d = q.shape
    page_size = k_pages.shape[1]
    if value_dim is not None:
        assert v_pages is None and k_scale is None and k_pages.ndim == 3
        kv_h, d_v = 1, value_dim
    else:
        kv_h = k_pages.shape[2]
        d_v = v_pages.shape[3]      # a value may be narrower than a key
    maxp = page_table.shape[1]
    group = h // kv_h
    quantized = k_scale is not None
    cols, l_pad = _tile_cols(l, kv_h, group)
    tq = cols * group
    # [b, l, h, d] -> [b, kv_h, l_pad * group, d]: head kv*group + g is
    # kv head kv's g-th query head (the _repeat_kv grouping)
    q_g = jnp.pad(q, ((0, 0), (0, l_pad - l), (0, 0), (0, 0))) \
        .reshape(b, l_pad, kv_h, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kv_h, l_pad * group, d)
    start = start.astype(jnp.int32)
    # a padding row (count == 0) sees position 0 alone: finite, unused
    last = jnp.where(count > 0, start + count.astype(jnp.int32) - 1, 0)

    tiles = l_pad // cols
    tile, k_idx, pages, n = _live_steps(page_table.astype(jnp.int32), start,
                                        last, cols, tiles, page_size)

    def page_index(i, tile, k_idx, pages, st, ls):
        return (pages[i], 0, 0, 0)

    def tile_index(i, tile, k_idx, pages, st, ls):
        ri, ti = _row_and_tile(tile[i], tiles)
        return (ri, 0, ti, 0)

    q_spec = pl.BlockSpec((1, kv_h, tq, d), tile_index)
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
    kernel = functools.partial(_paged_prefill_kernel, scale=scale,
                               page_size=page_size, group=group, maxp=maxp,
                               tiles=tiles, quantized=quantized,
                               value_dim=value_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n[0],),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv_h, tq, d_v), tile_index),
        scratch_shapes=[
            pltpu.VMEM((kv_h, tq, 128), jnp.float32),
            pltpu.VMEM((kv_h, tq, 128), jnp.float32),
            pltpu.VMEM((kv_h, tq, d_v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, name="paged_prefill",
        out_shape=jax.ShapeDtypeStruct(q_g.shape[:3] + (d_v,), q.dtype),
        interpret=interpret,
    )(tile, k_idx, pages, start, last, *operands)
    return out.reshape(b, kv_h, l_pad, group, d_v).transpose(0, 2, 1, 3, 4) \
        .reshape(b, l_pad, h, d_v)[:, :l]


def paged_flash_prefill(q, pools, page_table, start, count, *,
                        mesh=None, scale=None, interpret=None,
                        value_dim=None):
    """Causal attention of ``q`` [rows, l, heads, d] over a PAGED cache:
    column j of row r sits at position ``start[r] + j`` and sees key
    positions <= its own through ``page_table`` [rows, max_pages] (the
    row's slot's table row).  ``pools`` is a layer's pool dict with the
    chunk ALREADY written (``paged_write``); ``count[r]`` columns of row
    r are valid — pages past the last valid column are never read, and
    the outputs of padding columns and padding rows are finite and
    meaningless.  ``mesh`` set runs the kernel per shard under
    ``shard_map`` with the decode kernel's axes (kv heads over
    ``model``, rows over ``data`` where they divide).  A latent layer's
    ``pools`` (its one leaf, ``value_dim`` set) runs the shared read;
    under ``shard_map`` the leaf enters whole and only the rows shard."""
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    call = functools.partial(paged_prefill, scale=scale,
                             interpret=interpret, value_dim=value_dim)
    latent = value_dim is not None
    args = (q, pools[LATENT_LEAF] if latent else pools["k_pages"],
            pools.get("v_pages"), pools.get("k_scale"),
            pools.get("v_scale"), page_table, start, count)
    if mesh is None:
        return call(*args)
    from jax.sharding import PartitionSpec as P
    head_ax, row_ax = _shard_map_axes(
        mesh, q.shape[0], q.shape[2],
        1 if latent else pools["k_pages"].shape[2])
    q_spec = P(row_ax, None, head_ax, None)
    pool_spec = P(None, None, head_ax, None)
    scale_spec = pool_spec if "k_scale" in pools else None
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(q_spec, P(None, None, None) if latent else pool_spec,
                  None if latent else pool_spec, scale_spec, scale_spec,
                  P(row_ax, None), P(row_ax), P(row_ax)),
        out_specs=q_spec, check_vma=False)(*args)
