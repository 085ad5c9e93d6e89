"""The paged flash-prefill kernel (ops/attention/paged_prefill.py) held
against the reference path of ``kv_cache._paged_multi``.

Both run through ``_paged_multi`` itself, in interpret mode on the CPU:
``kernel_mode_scope("force")`` takes the kernel, ``"reference"`` the
gather + mask + jnp attention the kernel replaces.  The write is the
same code on both sides, so the pools must agree to the bit; outputs
agree on every VALID column (a padding column sees no page past the
row's last written position in the kernel, every page in the
reference, and nothing reads it).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.ops.attention.decode import (kernel_mode_scope,
                                                paged_kernel_decision)
from deepspeed_tpu.ops.attention import paged_prefill as paged_prefill_module
from deepspeed_tpu.ops.attention.paged_prefill import (VMEM_BUDGET,
                                                       VMEM_LIMIT,
                                                       _live_steps,
                                                       _tile_cols,
                                                       _vmem_bytes,
                                                       count_key_blocks,
                                                       key_block_plan,
                                                       paged_prefill)
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.serving import ServingScheduler

SLOTS, MAXP, PAGES = 4, 6, 40
GQA, MHA = (32, 8, 128), (4, 4, 64)      # heads, kv heads, head dim


def _pools(rng, kv_h, d, ps, dtype, pages=PAGES):
    """One layer's pools, full of history (a float pool: normal draws;
    an int8 pool: payload and per-row scales)."""
    pools = kv_cache.init_paged(1, pages, ps, kv_h, d, dtype)["layers"][0]
    out = {}
    for name, a in pools.items():
        if a.dtype == jnp.int8:
            out[name] = jnp.asarray(rng.integers(-127, 128, a.shape),
                                    jnp.int8)
        elif name.endswith("scale"):
            out[name] = jnp.asarray(rng.uniform(0.005, 0.02, a.shape),
                                    jnp.float32)
        else:
            out[name] = jnp.asarray(rng.standard_normal(a.shape), a.dtype)
    return out


def _step(pools, mode, l, ps, rng):
    """Rows at start 0, a page boundary, mid-page (a prefix-cache hit's
    boundary) and deep into the table; counts full, short, one and — a
    padding row — zero."""
    pt = jnp.asarray(rng.permutation(PAGES - 1)[:SLOTS * MAXP]
                     .reshape(SLOTS, MAXP) + 1, jnp.int32)
    lengths = jnp.asarray([0, 2 * ps, ps + 5, 2 * ps + 5], jnp.int32)
    if mode == "verify":        # row r IS slot r
        count = jnp.asarray([l, 0, l - 2, 1], jnp.int32)
        return kv_cache.verify_step(pools, pt, lengths, count)
    rows = jnp.asarray([2, 0, 3, 1, 0], jnp.int32)
    count = jnp.asarray([l, l - 3, 1, l, 0], jnp.int32)
    return kv_cache.prefill_step(pools, pt, lengths, rows, count)


def _both_paths(step, h, kv_h, d, l, dtype, rng):
    b = SLOTS if step.rows is None else step.rows.shape[0]
    q, k, v = (jnp.asarray(rng.standard_normal((b, l, n, d)), dtype)
               for n in (h, kv_h, kv_h))
    pos = kv_cache.positions(step, b, l)
    out = {}
    for mode in ("reference", "force"):
        with kernel_mode_scope(mode):
            out[mode] = jax.jit(lambda q, k, v: kv_cache._paged_multi(
                q, k, v, pos, step, None))(q, k, v)
    valid = np.arange(l)[None, :] < np.asarray(step.count)[:, None]
    return out["reference"], out["force"], valid


def _assert_same(ref, got, valid, dtype):
    (o_ref, p_ref), (o_got, p_got) = ref, got
    for name in p_ref:
        assert np.array_equal(np.asarray(p_ref[name]),
                              np.asarray(p_got[name])), name
    o_ref, o_got = (np.asarray(o, np.float32) for o in (o_ref, o_got))
    assert np.isfinite(o_got).all()      # padding rows and columns too
    # float32: test_batched_prefill's 1e-5; bf16: two ulps of the
    # largest output (each side rounds its output once)
    tol = 1e-5 if dtype == jnp.float32 else \
        2 * 2.0 ** -8 * np.abs(o_ref[valid]).max()
    assert np.abs(o_ref - o_got)[valid].max() <= tol


@pytest.mark.parametrize("mode", ["prefill", "verify"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("geometry", [GQA, MHA], ids=["gqa32x8", "mha4"])
def test_kernel_matches_the_reference_path(geometry, dtype, mode):
    h, kv_h, d = geometry
    rng = np.random.default_rng(0)
    l = 9 if mode == "verify" else 8        # K + 1 pads to a tile
    step = _step(_pools(rng, kv_h, d, 16, dtype), mode, l, 16, rng)
    ref, got, valid = _both_paths(step, h, kv_h, d, l, dtype, rng)
    _assert_same(ref, got, valid, dtype)


@pytest.mark.parametrize("mode", ["prefill", "verify"])
def test_int8_pools_dequantize_in_the_kernel(mode):
    h, kv_h, d = GQA
    rng = np.random.default_rng(1)
    step = _step(_pools(rng, kv_h, d, 16, "int8"), mode, 8, 16, rng)
    assert "k_scale" in step.layers
    ref, got, valid = _both_paths(step, h, kv_h, d, 8, jnp.bfloat16, rng)
    _assert_same(ref, got, valid, jnp.bfloat16)


@pytest.mark.parametrize("case", ["pages_of_128", "chunk_in_two_q_tiles"])
def test_kernel_at_other_tilings(case):
    """The chip's page size, and a head count at which one chunk's
    scores would outgrow a tile's budget, so the chunk splits over the
    q-tile grid axis (each tile stops at its own last column)."""
    h, kv_h, d, ps, l = (32, 8, 128, 128, 32) if case == "pages_of_128" \
        else (64, 64, 16, 16, 40)
    assert _tile_cols(l, kv_h, h // kv_h) == \
        ((32, 32) if case == "pages_of_128" else (32, 64))
    rng = np.random.default_rng(2)
    step = _step(_pools(rng, kv_h, d, ps, jnp.float32), "prefill", l, ps,
                 rng)
    ref, got, valid = _both_paths(step, h, kv_h, d, l, jnp.float32, rng)
    _assert_same(ref, got, valid, jnp.float32)


@pytest.mark.parametrize("mode", ["prefill", "verify"])
def test_dead_pages_are_never_read(mode):
    """Every page past a row's last WRITTEN position poisoned with NaN:
    the kernel's valid outputs are what they were, to the bit, and
    finite everywhere; the reference, which reads capacity, is not."""
    h, kv_h, d = MHA
    ps, l = 16, 8
    rng = np.random.default_rng(3)
    pools = _pools(rng, kv_h, d, ps, jnp.float32)
    step = _step(pools, mode, l, ps, np.random.default_rng(4))
    clean = _both_paths(step, h, kv_h, d, l, jnp.float32,
                        np.random.default_rng(5))
    slots = np.arange(SLOTS) if step.rows is None else np.asarray(step.rows)
    last = np.asarray(step.lengths)[slots] + np.asarray(step.count) - 1
    live = {0}                                   # the table's null page
    for s, n, c in zip(slots, last, np.asarray(step.count)):
        live |= set(np.asarray(step.page_table)[s, :(n if c else 0) // ps
                                                + 1].tolist())
    dead = np.asarray(sorted(set(range(PAGES)) - live))
    assert dead.size > PAGES // 2
    poisoned = {n: a.at[dead].set(jnp.nan) for n, a in pools.items()}
    ref, got, valid = _both_paths(
        dataclasses.replace(step, layers=poisoned), h, kv_h, d, l,
        jnp.float32, np.random.default_rng(5))
    assert np.isfinite(np.asarray(got[0])).all()
    assert np.array_equal(np.asarray(got[0])[valid],
                          np.asarray(clean[1][0])[valid])
    assert not np.isfinite(np.asarray(ref[0])[valid]).all()


# ------------------------------------------------------ the work list

# (start, count) a row: mid-prompt rows, one-token riders with long
# histories, padding rows (count 0), a row at capacity (its chunk ends
# on the table's last position) and a row that begins at 0
def _dispatch_rows(ps, maxp, l):
    cap = maxp * ps
    return [(3 * ps, l), (ps + 5, l - 3), (cap - ps - 7, 1), (0, 0),
            (cap - l, l), (0, l), (2 * ps - 1, 1), (4 * ps + 9, 0)]


def _brute_force_steps(rows, ps, maxp, cols, tiles, block):
    """Every (row, tile) in grid order with its live pages -- those
    whose first position is at or under the last position the tile may
    see -- cut into the kernel's steps: whole blocks of ``block`` pages,
    then the pages left over one a step where they are one page or
    under half a block, else as one more block.  A step is (row, tile, first page,
    pages it reads, pages it computes)."""
    out = []
    for r, (start, count) in enumerate(rows):
        last = start + count - 1 if count else 0
        for t in range(tiles):
            tile_last = min(last, start + (t + 1) * cols - 1)
            live = sum(k * ps <= tile_last for k in range(maxp))
            k = 0
            while live - k >= block:
                out.append((r, t, k, block, block))
                k += block
            if 2 * (live - k) >= max(block, 3):
                out.append((r, t, k, live - k, block))
            else:
                out += [(r, t, j, 1, 1) for j in range(k, live)]
    return out


@pytest.mark.parametrize("block", [1, 2, 4, 8])
@pytest.mark.parametrize("ps", [16, 128])
@pytest.mark.parametrize("l,kv_h,group", [(8, 8, 4), (32, 1, 32),
                                          (40, 64, 1)],
                         ids=["one_tile", "latent_group32", "two_tiles"])
def test_the_work_list_is_the_live_key_blocks_in_grid_order(l, kv_h, group,
                                                            ps, block):
    """Every live page of every (row, tile) once, in order, in the slot
    of the step the kernel reads it in; a slot a step does not read
    repeats what it last held."""
    maxp = 19
    cols, l_pad = _tile_cols(l, kv_h, group)
    tiles = l_pad // cols
    assert tiles == (2 if l == 40 else 1)
    rows = _dispatch_rows(ps, maxp, l) + [(9 * ps + 3, l), (11 * ps, 1)]
    start, count = (jnp.asarray(c, jnp.int32) for c in zip(*rows))
    last = jnp.where(count > 0, start + count - 1, 0)
    table = np.arange(len(rows) * maxp, dtype=np.int32) \
        .reshape(len(rows), maxp)[:, ::-1] + 3
    tile, k_idx, pages, n = jax.jit(_live_steps, static_argnums=(3, 4, 5, 6))(
        jnp.asarray(table), start, last, cols, tiles, ps, block)
    want = _brute_force_steps(rows, ps, maxp, cols, tiles, block)
    tile, k_idx, pages = (np.asarray(a) for a in (tile, k_idx, pages))
    cap = tile.shape[0]
    assert int(n[0]) == len(want) <= cap < len(rows) * tiles * maxp or \
        block == 1 and cap == len(rows) * tiles * maxp
    assert k_idx.shape == (cap,) and pages.shape == (block * cap,)
    assert list(zip(tile[:len(want)] // tiles, tile[:len(want)] % tiles,
                    k_idx[:len(want)])) == [s[:3] for s in want]
    slots = pages.reshape(block, cap)
    held, read = np.zeros(block, np.int64), []
    for i, (r, t, k, reads, _) in enumerate(want):
        for j in range(block):
            if j < reads:
                held[j] = table[r, k + j]
                read.append((r, t, k + j))
        assert slots[:, i].tolist() == held.tolist(), (i, want[i])
    # ... which is each (row, tile)'s live pages, each once, in order
    assert read == [(r, t, k) for r, t, k in _brute_force_pages(
        rows, ps, maxp, cols, tiles)]
    assert sum(s[3] for s in want) == len(read)
    # a padding row holds page 0 alone, a tile, whatever the block; the
    # row at capacity the whole of its table row
    for r in (3, 7):
        assert [s for s in want if s[0] == r] == \
            [(r, t, 0, 1, 1) for t in range(tiles)]
    assert sum(s[3] for s in want if s[:2] == (4, tiles - 1)) == maxp
    # past the live entries nothing is read, and every list still names
    # entries of the table (the slots what they last held)
    assert ((0 <= tile) & (tile < len(rows) * tiles)).all()
    assert (0 <= k_idx).all()
    assert (slots[:, len(want):] == held[:, None]).all()
    # the host's count of the same dispatch (the scheduler's counter)
    assert count_key_blocks(
        [s for s, _ in rows], [c for _, c in rows], max_pages=maxp,
        page_size=ps, cols=cols, tiles=tiles, block=block) == dict(
            live_pages=len(read), table_pages=len(rows) * tiles * maxp,
            key_blocks=len(want), block_pages=sum(s[4] for s in want))


def _brute_force_pages(rows, ps, maxp, cols, tiles):
    """Every (row, tile, page) with the page's first position at or
    under the last position the tile may see, in grid order: PR 55's
    list, and what one page a step still walks."""
    out = []
    for r, (start, count) in enumerate(rows):
        last = start + count - 1 if count else 0
        for t in range(tiles):
            tile_last = min(last, start + (t + 1) * cols - 1)
            out += [(r, t, k) for k in range(maxp) if k * ps <= tile_last]
    return out


def test_one_page_a_step_lists_every_live_page():
    """block == 1 is the list of PR 55: a step a live page."""
    ps, maxp, l = 16, 7, 8
    rows = _dispatch_rows(ps, maxp, l)
    assert [s[:3] for s in _brute_force_steps(rows, ps, maxp, 8, 1, 1)] == \
        _brute_force_pages(rows, ps, maxp, 8, 1)


@pytest.mark.parametrize("block", [1, 4])
def test_rows_at_capacity_list_the_whole_grid(block):
    ps, maxp, l = 16, 5, 8
    start = jnp.full(3, maxp * ps - l, jnp.int32)
    table = jnp.arange(3 * maxp, dtype=jnp.int32).reshape(3, maxp)
    tile, k_idx, pages, n = _live_steps(table, start, start + l - 1, 8, 1,
                                        ps, block)
    if block == 1:
        assert int(n[0]) == 3 * maxp
        assert np.asarray(tile * maxp + k_idx).tolist() == \
            np.asarray(pages).tolist() == list(range(3 * maxp))
    else:       # five pages: a block of four and one page walked
        assert int(n[0]) == tile.shape[0] == 3 * 2
        assert np.asarray(k_idx).tolist() == [0, 4] * 3
        assert np.asarray(pages).reshape(4, 6)[0].tolist() == \
            [0, 4, 5, 9, 10, 14]


@pytest.mark.parametrize("name,args,block", [
    # chunk 32: heads, kv heads, page size, key width, q and pool bytes
    ("kanana2-30b.longdoc-batch", (32, 32, 1, 128, 640, 2, 2), 4),
    ("mistral7b.longprompt-batch", (32, 32, 8, 128, 128, 2, 2), 8),
    ("mimo-v2-flash.longctx-batch", (32, 64, 4, 128, 256, 2, 2), 8),
    ("mistral_int8_pool", (32, 32, 8, 128, 128, 2, 1), 8),
    ("mistral_float32", (32, 32, 8, 128, 128, 4, 4), 4),
    ("pages_of_16", (8, 32, 8, 16, 128, 2, 2), 8),
    ("pages_of_512", (32, 32, 8, 512, 128, 2, 2), 2),
    ("wide_heads_float32", (32, 64, 64, 128, 512, 4, 4), 1),
])
def test_the_block_comes_from_the_shapes(name, args, block):
    """The three closed-loop cells' geometries take what the sweep on
    the chip found best (PERF.md section 6, PR 56 a); a geometry whose
    two-page block would outgrow the budget runs a page a step."""
    cols, tiles, got = key_block_plan(*args)
    assert (cols, tiles, got) == (32 if args[0] == 32 else 8, 1, block)
    chunk, heads, kv_h, ps, d, size, pool_size = args
    fits = [b for b in (1, 2, 4, 8) if _vmem_bytes(
        b, ps, kv_h * cols * (heads // kv_h), kv_h, d, size, pool_size)
        <= VMEM_BUDGET]
    assert block == 1 or block in fits and VMEM_BUDGET < VMEM_LIMIT


def _grid_form(q, k_pages, v_pages, k_scale, v_scale, page_table, start,
               count, *, scale, value_dim=None):
    """The kernel as PR 34 wrote it, kept here as the yardstick: grid
    (rows, q tiles, max_pages), the same body under ``pl.when`` on the
    steps past a tile's last visible page, the index map clamped to the
    last live page.  Interpret mode only."""
    import functools
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from deepspeed_tpu.ops.attention.flash import NEG_INF

    b, l, h, d = q.shape
    ps = k_pages.shape[1]
    latent = value_dim is not None
    kv_h, d_v = (1, value_dim) if latent else \
        (k_pages.shape[2], v_pages.shape[3])
    maxp, group, quantized = page_table.shape[1], h // kv_h, \
        k_scale is not None
    cols, l_pad = _tile_cols(l, kv_h, group)
    tq = cols * group
    q_g = jnp.pad(q, ((0, 0), (0, l_pad - l), (0, 0), (0, 0))) \
        .reshape(b, l_pad, kv_h, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kv_h, l_pad * group, d)
    start = start.astype(jnp.int32)
    last = jnp.where(count > 0, start + count.astype(jnp.int32) - 1, 0)

    def tile_last(ri, ti, st, ls):
        return jnp.minimum(ls[ri], st[ri] + (ti + 1) * cols - 1)

    def kernel(pt_ref, start_ref, last_ref, q_ref, k_ref, *rest):
        if not latent:
            v_ref, rest = rest[0], rest[1:]
        if quantized:
            ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
        else:
            o_ref, m_scr, l_scr, acc_scr = rest
        ri, ti, ki = (pl.program_id(a) for a in range(3))

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
            l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

        @pl.when(ki * ps <= tile_last(ri, ti, start_ref, last_ref))
        def _compute():
            q = q_ref[0]
            if latent:
                k = k_ref[...]
                v = k[:, :, :value_dim]
            else:
                k, v = k_ref[0], v_ref[0]
                if quantized:
                    k = (k.astype(jnp.float32) *
                         ks_ref[0].astype(jnp.float32)).astype(q.dtype)
                    v = (v.astype(jnp.float32) *
                         vs_ref[0].astype(jnp.float32)).astype(q.dtype)
                k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            k_rel = ki * ps - start_ref[ri] - ti * cols + \
                jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
            row = jax.lax.broadcasted_iota(jnp.int32, (1, tq, 1), 1)
            s = jnp.where(k_rel * group <= row, s, NEG_INF)
            m_prev, l_prev = m_scr[:, :, :1], l_scr[:, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            acc_scr[:] = acc_scr[:] * alpha + pv
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(ki == maxp - 1)
        def _finalize():
            o_ref[0] = (acc_scr[:] / l_scr[:, :, :1]).astype(o_ref.dtype)

    def page_index(ri, ti, ki, pt, st, ls):
        live = tile_last(ri, ti, st, ls) // ps
        return (pt[ri, jnp.minimum(ki, live)], 0, 0, 0)

    def tile_index(ri, ti, ki, pt, st, ls):
        return (ri, 0, ti, 0)

    q_spec = pl.BlockSpec((1, kv_h, tq, d), tile_index)
    if latent:
        in_specs = [q_spec, pl.BlockSpec(
            (1, ps, d), lambda *a: page_index(*a)[:3])]
        operands = [q_g, k_pages]
    else:
        in_specs = [q_spec, pl.BlockSpec((1, ps, kv_h, d), page_index),
                    pl.BlockSpec((1, ps, kv_h, d_v), page_index)]
        operands = [q_g, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, ps, kv_h, 1), page_index)] * 2
        operands += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel, interpret=True,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, l_pad // cols, maxp),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kv_h, tq, d_v), tile_index),
            scratch_shapes=[pltpu.VMEM((kv_h, tq, 128), jnp.float32),
                            pltpu.VMEM((kv_h, tq, 128), jnp.float32),
                            pltpu.VMEM((kv_h, tq, d_v), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q_g.shape[:3] + (d_v,), q.dtype),
    )(page_table.astype(jnp.int32), start, last, *operands)
    return out.reshape(b, kv_h, l_pad, group, d_v) \
        .transpose(0, 2, 1, 3, 4).reshape(b, l_pad, h, d_v)[:, :l]


FORMS = {      # heads, kv heads, key dim, value dim, chunk, pool, q dtype
    "f32": (8, 2, 32, 32, 8, jnp.float32, jnp.float32),
    "bf16_gqa": (32, 8, 128, 128, 8, jnp.bfloat16, jnp.bfloat16),
    "int8_pool": (8, 2, 32, 32, 8, "int8", jnp.bfloat16),
    "key_wider_than_value": (8, 2, 64, 32, 8, jnp.bfloat16, jnp.bfloat16),
    "latent": (8, 1, 48, 32, 8, jnp.bfloat16, jnp.bfloat16),
    "two_q_tiles": (64, 64, 16, 16, 40, jnp.float32, jnp.float32),
}


def _against_the_grid_form(form, maxp=7):
    """(kernel, grid form, valid mask, mask of the tiles that hold one
    page, block) on prompt rows, riders, padding rows and a row at
    capacity."""
    h, kv_h, d_k, d_v, l, pool_dtype, q_dtype = FORMS[form]
    ps = 16
    rng = np.random.default_rng(8)
    rows = _dispatch_rows(ps, maxp, l)
    start, count = (jnp.asarray(c, jnp.int32) for c in zip(*rows))
    table = jnp.asarray(rng.permutation(len(rows) * maxp)
                        .reshape(len(rows), maxp), jnp.int32)
    num_pages = len(rows) * maxp
    q = jnp.asarray(rng.standard_normal((len(rows), l, h, d_k)), q_dtype)
    if form == "latent":
        args = (jnp.asarray(rng.standard_normal((num_pages, ps, d_k)),
                            pool_dtype), None, None, None)
        kw = dict(value_dim=d_v)
    else:
        pools = _pools(rng, kv_h, d_k, ps, pool_dtype, num_pages)
        args = (pools["k_pages"], pools["v_pages"][..., :d_v],
                pools.get("k_scale"), pools.get("v_scale"))
        kw = {}
    # un-jitted, so that a patched rule is the rule this call traces
    got = paged_prefill.__wrapped__(q, *args, table, start, count,
                                    scale=d_k ** -0.5, interpret=True, **kw)
    want = _grid_form(q, *args, table, start, count, scale=d_k ** -0.5,
                      **kw)
    assert got.shape == want.shape == (len(rows), l, h, d_v)
    assert got.dtype == want.dtype == q_dtype
    cols, tiles, block = key_block_plan(
        l, h, kv_h, ps, d_k, jnp.dtype(q_dtype).itemsize,
        args[0].dtype.itemsize)
    pages = {(r, t): 0 for r in range(len(rows)) for t in range(tiles)}
    for r, t, _, reads, _ in _brute_force_steps(rows, ps, maxp, cols, tiles,
                                                block):
        pages[r, t] += reads
    col = np.arange(l)
    single = np.asarray([[pages[r, c // cols] == 1 for c in col]
                         for r in range(len(rows))])
    valid = col[None] < np.asarray(count)[:, None]
    got, want = (np.asarray(o, np.float32) for o in (got, want))
    assert valid.sum() and np.isfinite(got).all()
    return got, want, valid, single, block


def _assert_reassociated(form, got, want, valid, single):
    """Valid outputs within the stated tolerance of the grid form's, and
    the same bits in the tiles that hold one page."""
    scale = np.abs(want[valid]).max()
    tol = 1e-5 * scale if FORMS[form][6] == jnp.float32 else \
        2 * 2.0 ** -8 * scale
    assert np.abs(got - want)[valid].max() <= tol
    assert np.array_equal(got[single], want[single])


@pytest.mark.parametrize("form", sorted(FORMS))
def test_valid_outputs_equal_the_grid_form_to_the_bit(form):
    """Prompt rows, riders, padding rows and a row at capacity through
    the key-block kernel and through the (rows, tiles, max_pages) form
    of one page a step.  A block takes its running maximum once for
    all its pages, so a tile of several steps agrees to float32
    reassociation and not to the bit: 1e-5 of the largest output in
    float32 (sums of ~100 products of magnitude one, reordered), two
    ulps of the largest output where q is bfloat16 (P is rounded to
    bfloat16 against another maximum, then each side rounds its output
    once).  A tile that holds ONE page -- a padding row's, a first
    chunk's -- is the same arithmetic on the same page: the same bits."""
    got, want, valid, single, block = _against_the_grid_form(form)
    assert block == 8 and single.any() and not single.all()
    _assert_reassociated(form, got, want, valid, single)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_one_page_a_step_is_the_grid_form_to_the_bit(form, monkeypatch):
    """Where no larger block fits the budget the kernel runs as PR 55
    left it: every output, padding rows and columns too, the bits of
    the grid form."""
    monkeypatch.setattr(paged_prefill_module, "VMEM_BUDGET", 0)
    got, want, valid, single, block = _against_the_grid_form(form)
    assert block == 1 and np.array_equal(got, want)


@pytest.mark.parametrize("keys,block", [(32, 2), (64, 4)])
@pytest.mark.parametrize("form", ["f32", "int8_pool", "latent",
                                  "two_q_tiles"])
def test_smaller_blocks_agree_with_the_grid_form(form, keys, block,
                                                 monkeypatch):
    """Blocks of two and four pages over tables of eleven: whole blocks
    followed by a walked page, by a masked block, by nothing."""
    monkeypatch.setattr(paged_prefill_module, "BLOCK_KEYS", keys)
    got, want, valid, single, got_block = _against_the_grid_form(form, 11)
    assert got_block == block
    _assert_reassociated(form, got, want, valid, single)


def test_the_grid_is_the_live_steps_of_the_dispatch():
    """3 live rows in a 16-row bucket over slots of 12 pages: the
    kernel's one grid axis is bound by the list's count -- the live
    rows' key blocks and one page a padding row -- where the (rows,
    tiles, max_pages) form walked 192 steps and a page a step 31."""
    ps, maxp, l, h, d = 16, 12, 8, 4, 16
    start = jnp.asarray([5 * ps, ps + 3, 9 * ps + 8] + [0] * 13, jnp.int32)
    count = jnp.asarray([l, l, 1] + [0] * 13, jnp.int32)
    table = jnp.arange(16 * maxp, dtype=jnp.int32).reshape(16, maxp)
    q = jnp.ones((16, l, h, d), jnp.float32)
    pool = jnp.ones((16 * maxp, ps, h, d), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *a: paged_prefill(
        *a, scale=1.0, interpret=True))(q, pool, pool, None, None, table,
                                        start, count)
    calls = [e for e in jaxpr.jaxpr.eqns[-1].params["jaxpr"].eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    grid = calls[0].params["grid_mapping"].grid
    assert len(grid) == 1 and not isinstance(grid[0], int)   # dynamic
    cols, tiles, block = key_block_plan(l, h, h, ps, d, 4, 4)
    assert (cols, tiles, block) == (8, 1, 8)
    last = jnp.where(count > 0, start + count - 1, 0)
    *_, n = _live_steps(table, start, last, cols, tiles, ps, block)
    # six pages: one masked block; two: walked; ten: a block and two
    assert int(n[0]) == (1 + 2 + 3) + 13
    assert count_key_blocks(start, count, max_pages=maxp, page_size=ps,
                            cols=cols, tiles=tiles, block=block) == dict(
        live_pages=(6 + 2 + 10) + 13, table_pages=16 * maxp, key_blocks=19,
        block_pages=(8 + 2 + 10) + 13)


# ------------------------------------------------------- the decision

def _mesh_2x4():
    return make_mesh(MeshConfig(data=4, model=2))


@pytest.mark.parametrize("facts,path,dispatch,reason", [
    (dict(page_size=128, backend="tpu"), "kernel", "direct",
     "paged_prefill kernel over each row's live pages"),
    (dict(page_size=128, backend="tpu", mesh=_mesh_2x4), "kernel",
     "shard_map", "shard_mapped over the mesh"),
    (dict(page_size=128, backend="tpu", has_bias=True), "reference", None,
     "ALiBi"),
    (dict(page_size=64, backend="tpu"), "reference", None, "page_size=64"),
    (dict(page_size=128, backend="tpu", mode="reference"), "reference",
     None, "paged_kernel='reference'"),
    (dict(page_size=128, backend="cpu"), "reference", None,
     "off-TPU backend 'cpu'"),
    (dict(page_size=16, backend="cpu", mode="force"), "kernel", "direct",
     "paged_kernel='force'"),
    (dict(page_size=128, backend="tpu", num_heads=6, num_kv_heads=4),
     "reference", None, "not a multiple"),
], ids=["tpu", "mesh", "alibi", "page64", "mode_reference", "cpu", "force",
        "ragged_groups"])
def test_the_decision_answers_for_the_multi_token_path(facts, path,
                                                       dispatch, reason):
    facts = dict(dict(num_heads=32, num_kv_heads=8), **facts)
    if "mesh" in facts:
        facts["mesh"] = facts["mesh"]()
    multi = paged_kernel_decision(multi_token=True, **facts)
    assert (multi["path"], multi["dispatch"]) == (path, dispatch)
    assert reason in multi["reason"]
    # one rule: the same facts give decode the same path, and only the
    # multi-token answer names the prefill kernel or its fallback
    decode = paged_kernel_decision(**facts)
    assert (decode["path"], decode["dispatch"]) == (path, dispatch)
    assert "prefill and verify" in multi["reason"]
    assert "prefill and verify" not in decode["reason"]
    assert multi.get("blocker") == decode.get("blocker")


@pytest.mark.parametrize("mode,path", [("force", "kernel"),
                                       ("reference", "reference"),
                                       ("auto", "reference")])
def test_served_prefill_takes_the_path_health_reports(mode, path):
    """An engine serves token-exactly against its own ``generate()`` on
    either path; ``health()`` says which, beside decode's, and the
    prefill program holds the kernel's own name exactly when it does."""
    engine = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1}, paged_kernel=mode)
    engine.init_params()
    sched = ServingScheduler(engine, num_slots=2, num_pages=12,
                             page_size=16, max_pages_per_slot=6,
                             prefill_chunk=8, comm_telemetry=True)
    pa = sched.health()["paged_attention"]
    assert pa["path"] == pa["multi_token"]["path"] == path
    assert set(pa["multi_token"]) >= {"path", "dispatch", "reason"}
    assert engine.paged_kernel_decision(page_size=16)["multi_token"] == \
        pa["multi_token"]
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 200, n).astype(np.int32) for n in (19, 5)]
    reqs = [sched.submit(p, max_new_tokens=4) for p in prompts]
    got = sched.run()
    for p, r in zip(prompts, reqs):
        want = engine.generate(p[None], max_new_tokens=4, do_sample=False)
        assert list(got[r.rid]) == list(np.asarray(want)[0, len(p):])
    texts = _prefill_program_texts(engine)
    assert texts and all(("paged_prefill" in t) == (path == "kernel")
                         for t in texts)


def _prefill_program_texts(engine, compiled=False):
    """The lowered (or compiled) text of every prefill signature the
    engine dispatched, from its comm-ledger capture."""
    out = []
    for (name, _, _), (fn, specs, statics) in engine._comm_capture.items():
        if name == "prefill":
            with engine._serving_scope():
                low = getattr(engine, fn).lower(*specs, *statics)
            out.append((low.compile() if compiled else low).as_text())
    return out


def test_a_prefill_program_computes_the_work_list_once_for_its_layers():
    """Nothing in the list depends on a layer, so the compiled prefill
    program of a two-layer model holds the list's search as often as a
    one-layer model's: once, whatever the number of kernel calls."""
    mentions = {}
    for layers in (1, 2):
        engine = deepspeed_tpu.init_inference(
            model=GPT2(dataclasses.replace(gpt2_tiny(), num_layers=layers)),
            dtype="float32", kv_cache_dtype="float32",
            mesh={"data": 1, "model": 1}, paged_kernel="force")
        engine.init_params()
        sched = ServingScheduler(engine, num_slots=2, num_pages=12,
                                 page_size=16, max_pages_per_slot=6,
                                 prefill_chunk=8, comm_telemetry=True)
        sched.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=2)
        sched.run()
        low, = _prefill_program_texts(engine)
        assert low.count("call @paged_prefill") == layers
        text, = _prefill_program_texts(engine, compiled=True)
        mentions[layers] = text.count("searchsorted")
    assert mentions[1] == mentions[2] > 0
