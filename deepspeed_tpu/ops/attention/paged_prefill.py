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
  * a grid step is a KEY BLOCK: one q tile of one row against ``block``
    consecutive pages of the row's table (:func:`_block_pages`; 8 pages
    of 128 tokens for most geometries), each page its own ``BlockSpec``
    of the same pool operand, laid side by side in VMEM.  The online-
    softmax update — two cross-lane reductions, ``alpha``, the float32
    accumulator's rescale and store — happens once a block, and ``p · v``
    is one contraction over the block's keys, so the sum over its pages
    is the MXU's.  (At one page a step the two reductions alone were
    over half of a step: PERF.md section 6, PR 56.)
  * grid = the dispatch's LIVE steps, row-major, tile-major, key-minor
    (:func:`_live_steps`, scalar-prefetched; its length is the grid's
    dynamic bound, as ``_live_pairs`` is the decode kernel's): tile t of
    row r lists the pages up to the last position it may see and no
    more.  Keys stay innermost, so the online-softmax state (m, l, acc;
    float32) lives in VMEM scratch across a tile's steps, as in the
    decode kernel and flash.py: reset at a tile's page 0, written out at
    the step that holds its last page.
  * a tile holds ``_last_page + 1`` pages, not a multiple of ``block``
    (:func:`_tile_steps`): a tail of one page or under half a block is
    WALKED, one page a step through the same body at one page's width
    (a padding row's one page, a short prompt's two); a longer tail is
    one more block whose dead pages the causal mask hides.  A page slot
    that a step does not read keeps the id it last held, so it costs no
    DMA and never names a page the dispatch does not hold.
  * ``block`` comes from the shapes alone: about 1,024 keys, 512 where
    a key is 512 features or wider (there the two matmuls bind at 512
    and a longer block only lengthens tails), halved while the block's
    buffers would outgrow :data:`VMEM_BUDGET`; one page a step where
    nothing larger fits.
  * q arrives regrouped per kv head, [rows, kv_heads, l * group, d]
    (row ``j * group + g`` of a kv head's tile is column j of query head
    ``kv * group + g``): one MXU-shaped tile per kv head — Mistral's
    chunk of 32 x group 4 is 128 x 128 — and MHA is group == 1.  A chunk
    whose tile would outgrow VMEM is split over the q-tile grid axis.
    Over a LATENT pool (one head, group == heads) q and the output are
    HEAD-MAJOR instead, [heads, rows, l, d]: the contractions on either
    side of the call are batched over heads (the absorption that makes
    q, the one that takes the output back to a head's value), and a dot
    batched over heads writes and reads heads outermost — so [rows, 1,
    l * heads, d] cost a layout copy of the whole query in and of the
    whole output out, a layer a dispatch (PERF.md section 6, PR 63).  A
    tile is [heads, cols, d], merged in VMEM to the same ``heads *
    cols`` MXU rows (row ``g * cols + j`` is column j of head g).
  * live pages only: a page is listed iff its first position is <=
    the last position the tile may see, so a page past it costs no DMA
    and no grid step; the next step's pages are in flight while this one
    is computed, across tile and row boundaries too.  A padding row
    (``count == 0``) lists ONE step, position 0's page, so every output
    block is written (finite, meaningless) and none needs a zero fill.
    The causal mask ``k_pos <= start + j`` is computed in-kernel.
  * ``window`` > 0 (static; a sliding-window layer's ring read as a
    page pool of its own, ops/attention/window.py): column j sees the
    last ``window`` positions, its own included.  A tile's steps start
    at the page of its first column's oldest visible position
    (:func:`_first_page`) instead of page 0, so a row costs its
    window's pages whatever its context, and the mask gains ``k_pos >
    q_pos - window``.  ``window=0`` traces the program it always was
    (tests/unit/test_window_paged.py holds its jaxpr).
  * operands in the pool's / q's dtype, scores, statistics and
    accumulator in float32, P cast to V's dtype before P·V: the
    arithmetic of ``_gqa_reference``.  Quantized pools dequantize in
    VMEM right before the dot, as the decode kernel does.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.attention.decode import (_segment_entries,
                                                _shard_map_axes)
from deepspeed_tpu.ops.attention.flash import NEG_INF
from deepspeed_tpu.ops.quant.kv import LATENT_LEAF

BLOCK_KEYS = 1024           # keys a grid step, at most 8 pages
WIDE_KEY = 512              # a key this wide: BLOCK_KEYS // 2
VMEM_LIMIT = 48 << 20       # the call's scoped-VMEM limit (v5e: 128 MiB)
VMEM_BUDGET = 32 << 20      # what _vmem_bytes may count of it


def _last_page(start, last, ti, cols, page_size, maxp, minimum=jnp.minimum):
    """Index, in its row's table, of the last page q tile ``ti`` of a
    row may attend to: the page of the row's last WRITTEN position
    ``last``, or of the tile's own last column if that comes first."""
    return minimum(
        minimum(last, start + (ti + 1) * cols - 1) // page_size, maxp - 1)


def _first_page(start, ti, cols, page_size, window, live):
    """Index, in its row's table, of the first page q tile ``ti`` of a
    row visits under a sliding ``window``: the page of the oldest
    position the tile's FIRST column sees, ``start + ti * cols - window
    + 1``, and never past the tile's last page ``live - 1`` (a tile of
    padding columns, a padding row).  Without a window every tile
    starts at page 0 and nobody asks."""
    return jnp.minimum(
        jnp.maximum(start + ti * cols - window + 1, 0) // page_size,
        live - 1)


def _tail_walked(tail, block):
    """Whether the ``tail`` pages a tile holds past its last whole block
    are walked a page a step: one page, or under half a block."""
    return 2 * tail < max(block, 3)


def _tile_steps(live, block):
    """(key blocks, one-page steps) of a tile that holds ``live`` pages:
    its whole blocks, and the ``live % block`` pages left over walked
    page by page (:func:`_tail_walked`) or as one more block, masked
    past the last live page.  numpy or jnp integers."""
    tail = live % block
    walked = _tail_walked(tail, block)
    return live // block + 1 - walked, tail * walked


def _is_block(k, live, block):
    """Whether the step that starts at a tile's page ``k`` is a whole
    block: :func:`_tile_steps`, asked of one step."""
    return (k + block <= live) | \
        jnp.logical_not(_tail_walked(live % block, block))


def _live_steps(page_table, start, last, cols, tiles, page_size, block,
                window=0):
    """The kernel's grid, from the dispatch's own inputs: the live
    (row, q tile, key block) steps in row-major, tile-major, key-minor
    order.  Tile t of row r holds pages ``_first_page(r, t) ...
    _last_page(r, t)`` (the first is 0 without a ``window``) in
    the steps :func:`_tile_steps` counts, blocks first; ``last`` is 0
    for a padding row, which therefore holds page 0 alone (one step a
    tile: its output block is written like any other).  Returns int32
    lists of the grid's capacity ``cap`` = tiles x the most steps a
    tile can hold -- entry i of ``tile`` is ``r * tiles + t`` of the
    i-th live step, of ``k`` its first page's index in the row's table,
    and entry ``j * cap + i`` of ``pages`` the id in the step's page
    slot j -- and ``n`` int32 [1], how many entries are live: at least
    one a tile.  Slot 0 holds a one-page step's page; a slot the step
    does not read (1.. of a one-page step, the dead end of a masked
    block, any slot past the live entries) repeats the id it last held:
    no DMA, and a page the dispatch holds (page 0 before any).  Nothing
    here depends on a layer, so XLA computes it once a dispatch for all
    of them.  The lists ride in scalar memory."""
    maxp = page_table.shape[1]
    t = jnp.arange(tiles, dtype=jnp.int32)
    live = _last_page(start[:, None], last[:, None], t[None], cols,
                      page_size, maxp).reshape(-1) + 1
    held, most = live, maxp
    if window:
        # a tile under a window holds its last pages alone: at most the
        # window and its own columns, whatever the context
        lo = _first_page(start[:, None], t[None], cols, page_size,
                         window, live.reshape(-1, tiles)).reshape(-1)
        held = live - lo
        most = min(maxp, (window + cols - 2) // page_size + 2)
    blocks, singles = _tile_steps(held, block)
    width = int(sum(_tile_steps(np.arange(1, most + 1), block)).max())
    tile, e, n = _segment_entries((blocks + singles).astype(jnp.int32),
                                  width)
    # entry e of a tile: its blocks, then the walked tail page by page
    k = e + jnp.minimum(e, blocks[tile]) * (block - 1)
    i = jnp.arange(tile.shape[0], dtype=jnp.int32)
    listed = i < n[0]
    # slot j of step i reads page k + j: slot 0 always, the others in a
    # block as far as the tile's pages go; else it keeps the last read
    j = jnp.arange(block, dtype=jnp.int32)[:, None]
    reads = listed & ((j == 0) | (_is_block(k, held[tile], block) &
                                  (k + j < held[tile])))
    if window:
        k = k + lo[tile]          # from here on an index into the table
    src = jax.lax.cummax(jnp.where(reads, i, 0), axis=1)
    first = (tile // tiles) * maxp + k        # into the flattened table
    pages = jnp.where((src > 0) | reads[:, :1],
                      page_table.reshape(-1)[first[src] + j], 0)
    return tile, k, pages.reshape(-1), n


def count_key_blocks(start, count, *, max_pages, page_size, cols, tiles,
                     block):
    """What :func:`_live_steps` lists for a dispatch whose row r holds
    ``count[r]`` columns from position ``start[r]`` (0 columns: a
    padding row), counted on the host (numpy) under the names
    ``ServingMetrics.record_prefill_dispatch`` takes: ``live_pages``
    the (row, tile, page) entries the tiles may see, of ``table_pages``
    = rows x tiles x ``max_pages``; ``key_blocks`` the grid steps that
    walk them and ``block_pages`` the pages those steps compute (==
    ``live_pages`` unless a masked tail block ran; ``key_blocks`` ==
    ``live_pages`` at one page a step)."""
    start, count = np.asarray(start, np.int64), np.asarray(count, np.int64)
    last = np.where(count > 0, start + count - 1, 0)
    live = _last_page(start[:, None], last[:, None], np.arange(tiles)[None],
                      cols, page_size, max_pages, np.minimum) + 1
    blocks, singles = _tile_steps(live, block)
    return dict(live_pages=int(live.sum()), table_pages=live.size * max_pages,
                key_blocks=int((blocks + singles).sum()),
                block_pages=int((blocks * block + singles).sum()))


def _row_and_tile(tile, tiles):
    """(row, q tile) of a :func:`_live_steps` ``tile`` entry; a chunk in
    one q tile, every serving geometry so far, divides nothing."""
    if tiles == 1:
        return tile, 0
    return jax.lax.div(tile, tiles), jax.lax.rem(tile, tiles)


def _paged_prefill_kernel(tile_ref, k_idx_ref, page_ref, start_ref, last_ref,
                          q_ref, *rest, scale, page_size, group, maxp, tiles,
                          block, quantized, value_dim=None, window=0):
    """One grid step: one q tile of one row, ALL kv heads, against one
    KEY BLOCK -- ``block`` consecutive pages of the row, or one page of
    a walked tail; the grid is the dispatch's live steps
    (:func:`_live_steps`), so every step computes.  ``rest`` holds
    ``block`` refs an operand (K, V, their scales), one a page slot.
    Blocks span the pool's trailing (kv_heads, d) dims whole (see
    ``_paged_decode_kernel``); the per-kv-head products are
    leading-batch dots.  ``value_dim`` marks a latent pool: no V refs,
    the value is the leading ``value_dim`` features of the K block (one
    DMA a page), as in the decode kernel, and the q and output tiles
    head-major, [group, cols, d].  ``window`` > 0: a query sees
    the last ``window`` positions alone, and the tile's steps start at
    :func:`_first_page` (``held`` counts its pages from there)."""
    k_refs, rest = rest[:block], rest[block:]
    if value_dim is None:
        v_refs, rest = rest[:block], rest[block:]
    if quantized:
        ks_refs, vs_refs, rest = rest[:block], rest[block:2 * block], \
            rest[2 * block:]
    o_ref, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    ri, ti = _row_and_tile(tile_ref[i], tiles)
    ki = k_idx_ref[i]
    if value_dim is not None:
        cols = q_ref.shape[1]       # a HEAD-MAJOR tile [group, cols, d]
        tq = cols * group
    else:
        tq = q_ref.shape[2]
        cols = tq // group
    live = _last_page(start_ref[ri], last_ref[ri], ti, cols, page_size,
                      maxp) + 1
    lo = _first_page(start_ref[ri], ti, cols, page_size, window, live) \
        if window else 0

    @pl.when(ki == lo)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def side_by_side(refs, w, axis):
        parts = [r[...] if value_dim is not None else r[0]
                 for r in refs[:w]]
        return parts[0] if w == 1 else jnp.concatenate(parts, axis=axis)

    def update(w):
        """The online-softmax update over the step's first ``w`` pages.
        Position 0 is live for every row, so a tile's page 0 is always
        listed and the statistics are finite from the first step on.
        (Under a window a tile's first page may hold no key that its
        LATER columns see: such a row's statistics stay at NEG_INF,
        which is finite, and the first step with a visible key -- its
        own position at the latest -- rescales them away by alpha = 0.)"""
        keys = w * page_size
        if value_dim is not None:
            q = _merge_rows(q_ref[...])[None]             # [1, tq, d]
            k = side_by_side(k_refs, w, 1)                # [1, keys, d]
            v = k[:, :, :value_dim]
        else:
            q = q_ref[0]                                  # [kv_h, tq, d]
            k = side_by_side(k_refs, w, 0)                # [keys, kv_h, d]
            v = side_by_side(v_refs, w, 0)
            if quantized:
                k = (k.astype(jnp.float32) * side_by_side(ks_refs, w, 0)
                     .astype(jnp.float32)).astype(q.dtype)
                v = (v.astype(jnp.float32) * side_by_side(vs_refs, w, 0)
                     .astype(jnp.float32)).astype(q.dtype)
            k = k.transpose(1, 0, 2)                      # [kv_h, keys, d]
            v = v.transpose(1, 0, 2)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [kv_h, tq, keys]
        # tile row r is column r // group: key position p is visible iff
        # p <= start + col, i.e. (p - first column's position) * group
        # <= r — no integer division in-kernel
        k_rel = ki * page_size - start_ref[ri] - ti * cols + \
            jax.lax.broadcasted_iota(jnp.int32, (1, 1, keys), 2)
        if value_dim is not None:
            # head-major: tile row r is column r % cols, of ANY head
            col = _merge_rows(jax.lax.broadcasted_iota(
                jnp.int32, (group, cols, 1), 1))[None]
            seen = k_rel <= col
            if window:
                seen &= k_rel + window > col
        else:
            row = jax.lax.broadcasted_iota(jnp.int32, (1, tq, 1), 1)
            seen = k_rel * group <= row
            if window:
                # ... and p > start + col - window
                seen &= (k_rel + window) * group > row
        s = jnp.where(seen, s, NEG_INF)

        m_prev = m_scr[:, :, :1]                          # [kv_h, tq, 1]
        l_prev = l_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [kv_h, tq, d_v]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if block == 1:
        update(1)
        pages = 1
    else:
        wide = _is_block(ki - lo, live - lo, block) if window \
            else _is_block(ki, live, block)
        pl.when(wide)(lambda: update(block))
        pl.when(jnp.logical_not(wide))(lambda: update(1))
        pages = jnp.where(wide, block, 1)

    # the step that holds the tile's last page
    @pl.when(ki + pages >= live)
    def _finalize():
        out = acc_scr[:] / l_scr[:, :, :1]
        if value_dim is not None:
            # split in float32, whose sublane tile ``cols`` always fills
            o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)
        else:
            o_ref[0] = out.astype(o_ref.dtype)


def _merge_rows(x):
    """[group, cols, d] -> [group * cols, d] inside the kernel.  Leading
    rows merge into the sublane dim for nothing where ``cols`` is whole
    sublane tiles of the dtype (8 rows of 32 bits: 16 of bfloat16); a
    narrower dtype at fewer columns (a short verify) goes through
    float32, whose tile ``cols`` always fills."""
    g, cols, d = x.shape
    if cols % (8 * 4 // x.dtype.itemsize):
        return x.astype(jnp.float32).reshape(g * cols, d).astype(x.dtype)
    return x.reshape(g * cols, d)


def _tile_cols(l, kv_h, group):
    """(columns a q tile, padded chunk length): columns are a multiple
    of 8 (the float32 sublane tile, so ``cols * group`` rows tile for
    any group) and one float32 [kv_h, rows, 128] buffer — a page's
    scores, the accumulator, each statistic — stays within 1 MiB."""
    cap = max(8, (2048 // (kv_h * group)) // 8 * 8)
    cols = min(cap, -(-l // 8) * 8)
    return cols, -(-l // cols) * cols


def _vmem_bytes(block, page_size, rows, kv_h, d, itemsize, pool_itemsize):
    """What a step of ``block`` pages keeps in VMEM, counted from the
    shapes (``rows`` = kv heads x a q tile's rows; a value as wide as a
    key, which it never exceeds here): float32 scores and P, the pages
    double-buffered and once more side by side (in float32 too where
    they are dequantised), the q and output tiles double-buffered, the
    three scratches.  The compiler's own count at the cells' geometries
    is under this (PERF.md section 6, PR 56)."""
    keys = block * page_size
    kv = 2 * kv_h * keys * d
    return (rows * keys * (4 + itemsize)
            + kv * (2 * pool_itemsize + itemsize
                    + 4 * (pool_itemsize != itemsize))
            + 4 * rows * d * itemsize
            + rows * (2 * 128 + d) * 4)


def _block_pages(page_size, rows, kv_h, d, itemsize, pool_itemsize):
    """Pages a grid step, from the shapes: :data:`BLOCK_KEYS` keys (half
    that for a key of :data:`WIDE_KEY` features or more) in at most 8
    pages, halved while :func:`_vmem_bytes` is over
    :data:`VMEM_BUDGET`; 1 where no larger block fits."""
    keys = BLOCK_KEYS // 2 if d >= WIDE_KEY else BLOCK_KEYS
    block = max(1, min(8, keys // page_size))
    while block > 1 and _vmem_bytes(block, page_size, rows, kv_h, d,
                                    itemsize, pool_itemsize) > VMEM_BUDGET:
        block //= 2
    return block


def key_block_plan(chunk, heads, kv_heads, page_size, d, itemsize,
                   pool_itemsize):
    """(columns a q tile, q tiles, pages a key block) of the kernel call
    for a ``[rows, chunk]`` dispatch of ``heads`` query heads over a
    pool of ``kv_heads`` heads (1 for a latent pool) whose keys are
    ``d`` wide: what :func:`paged_prefill` will run, for whoever counts
    its steps (:func:`count_key_blocks`)."""
    group = heads // kv_heads
    cols, l_pad = _tile_cols(chunk, kv_heads, group)
    return cols, l_pad // cols, _block_pages(
        page_size, kv_heads * cols * group, kv_heads, d, itemsize,
        pool_itemsize)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "value_dim", "window"))
def paged_prefill(q, k_pages, v_pages, k_scale, v_scale, page_table, start,
                  count, *, scale, interpret, value_dim=None, window=0):
    """The kernel call, jitted under its own name: every layer of a
    serving program (and every program of one geometry) shares ONE trace
    of the kernel body — a bare ``pallas_call`` re-traces it per call
    site — and the Mosaic instruction is named ``paged_prefill`` in the
    HLO, so readers that find the decode and flash kernels by the
    model's ``attn`` scope never count this one.  ``v_pages=None,
    value_dim=n`` is the shared read of a latent pool (``k_pages`` the
    one leaf [num_pages, page_size, d]; see ``paged_decode_attention``),
    whose kernel operand and result are head-major (module docstring):
    the transposes here are layouts to the compiler, not copies.
    ``window`` > 0 is sliding-window attention: column j sees positions
    ``start + j - window + 1 ... start + j``, and a tile visits the
    pages from its first column's oldest visible position on, so what a
    row costs is its window's pages, not its context's."""
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
    cols, tiles, block = key_block_plan(l, h, kv_h, page_size, d,
                                        q.dtype.itemsize,
                                        k_pages.dtype.itemsize)
    tq, l_pad = cols * group, cols * tiles
    if value_dim is not None:
        # HEAD-MAJOR [h, b, l_pad, d]: what a dot batched over heads (the
        # absorption that makes q, the one that takes the output) writes
        # and reads as it is -- a transpose the compiler turns into the
        # producer's layout, where [b, 1, l_pad * h, d] cost a copy of
        # the whole query in and of the whole output out a call
        q_g = q.transpose(2, 0, 1, 3)
        if l_pad != l:
            q_g = jnp.pad(q_g, ((0, 0), (0, 0), (0, l_pad - l), (0, 0)))
    else:
        # [b, l, h, d] -> [b, kv_h, l_pad * group, d]: head kv*group + g
        # is kv head kv's g-th query head (the _repeat_kv grouping)
        q_g = jnp.pad(q, ((0, 0), (0, l_pad - l), (0, 0), (0, 0))) \
            .reshape(b, l_pad, kv_h, group, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, kv_h, l_pad * group, d)
    start = start.astype(jnp.int32)
    # a padding row (count == 0) sees position 0 alone: finite, unused
    last = jnp.where(count > 0, start + count.astype(jnp.int32) - 1, 0)

    with jax.named_scope("cache"):
        tile, k_idx, pages, n = _live_steps(
            page_table.astype(jnp.int32), start, last, cols, tiles,
            page_size, block, window)
    cap = tile.shape[0]

    def tile_index(i, tile, k_idx, pages, st, ls):
        ri, ti = _row_and_tile(tile[i], tiles)
        return (0, ri, ti, 0) if value_dim is not None else (ri, 0, ti, 0)

    def page_slots(shape):
        """``block`` views of one pool operand, slot j at the id the
        list holds for it."""
        zeros = (0,) * (len(shape) - 1)
        return [pl.BlockSpec(shape, lambda i, tile, k_idx, pages, st, ls,
                             j=j: (pages[j * cap + i],) + zeros)
                for j in range(block)]

    def tile_spec(width):
        """A q or output tile: [1, kv_h, tq, width] of the grouped form,
        [group, cols, width] of a latent pool's head-major one."""
        shape = (group, None, cols, width) if value_dim is not None \
            else (1, kv_h, tq, width)
        return pl.BlockSpec(shape, tile_index)

    in_specs, operands = [tile_spec(d)], [q_g]
    if value_dim is not None:
        pools = [((1, page_size, d), k_pages)]
    else:
        pools = [((1, page_size, kv_h, d), k_pages),
                 ((1, page_size, kv_h, d_v), v_pages)]
    if quantized:
        pools += [((1, page_size, kv_h, 1), k_scale),
                  ((1, page_size, kv_h, 1), v_scale)]
    for shape, pool in pools:
        in_specs += page_slots(shape)
        operands += [pool] * block
    kernel = functools.partial(_paged_prefill_kernel, scale=scale,
                               page_size=page_size, group=group, maxp=maxp,
                               tiles=tiles, block=block, quantized=quantized,
                               value_dim=value_dim, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n[0],),
        in_specs=in_specs,
        out_specs=tile_spec(d_v),
        scratch_shapes=[
            pltpu.VMEM((kv_h, tq, 128), jnp.float32),
            pltpu.VMEM((kv_h, tq, 128), jnp.float32),
            pltpu.VMEM((kv_h, tq, d_v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, name="paged_prefill",
        out_shape=jax.ShapeDtypeStruct(q_g.shape[:3] + (d_v,), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile, k_idx, pages, start, last, *operands)
    if value_dim is not None:
        return out.transpose(1, 2, 0, 3)[:, :l]
    return out.reshape(b, kv_h, l_pad, group, d_v).transpose(0, 2, 1, 3, 4) \
        .reshape(b, l_pad, h, d_v)[:, :l]


def paged_flash_prefill(q, pools, page_table, start, count, *,
                        mesh=None, scale=None, interpret=None,
                        value_dim=None, window=0):
    """Causal attention of ``q`` [rows, l, heads, d] over a PAGED cache:
    column j of row r sits at position ``start[r] + j`` and sees key
    positions <= its own through ``page_table`` [rows, max_pages] (the
    row's slot's table row).  ``pools`` is a layer's pool dict with the
    chunk ALREADY written (``paged_write``); ``count[r]`` columns of row
    r are valid — pages past the last valid column are never read, and
    the outputs of padding columns and padding rows are finite and
    meaningless.  ``window`` > 0 cuts what a column sees to the last
    ``window`` positions, its own included.  ``mesh`` set runs the kernel per shard under
    ``shard_map`` with the decode kernel's axes (kv heads over
    ``model``, rows over ``data`` where they divide).  A latent layer's
    ``pools`` (its one leaf, ``value_dim`` set) runs the shared read;
    under ``shard_map`` the leaf enters whole and only the rows shard."""
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    call = functools.partial(paged_prefill, scale=scale,
                             interpret=interpret, value_dim=value_dim,
                             window=window)
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
