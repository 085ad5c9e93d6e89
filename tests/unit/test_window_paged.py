"""The two paged kernels given ``window=`` (ops/attention/paged_prefill.py,
decode.py; Pallas interpret mode on the CPU) and the ring that they read
as a page pool (ops/attention/window.py ``page_view``), held against
``window.masked_attention`` over the whole history under the plain
positional mask.

* a ring of ``window + 2 pages`` rows through ``kv_cache.attend``:
  chunked prefill of several slots a dispatch (a chunk that straddles a
  page, a padding row, a slot reused from position 0), then decode,
  long enough to wrap the ring, on the kernel path and on the gather
  reference;
* the kernels alone over an ordinary page table (more pages than any
  window needs, absolute positions, two q tiles): the first page a tile
  visits and the mask's lower edge;
* the two pages: a tile visits ``window / page + 2`` pages at most while
  its chunk is ``page + 2`` columns or fewer, and does visit as many --
  counted by brute force against the bound ``page_view`` checks;
* ``window=0`` lowers to the programs of the commit before the argument
  existed, for one grouped-query decode and one prefill geometry of the
  benchmark's cells.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import kv_cache, window as window_ops
from deepspeed_tpu.ops.attention.decode import (_paged_decode_pallas,
                                                kernel_mode_scope)
from deepspeed_tpu.ops.attention.paged_prefill import (key_block_plan,
                                                       paged_prefill)

WINDOW, PAGE, KV, GROUP, D = 32, 16, 2, 2, 16
SLOTS = 3
# float32 throughout: the kernel's online softmax against one softmax
TOL = 2e-6


class History:
    """What every slot has been fed, for the plain mask."""

    def __init__(self):
        self.k = [np.zeros((0, KV, D), np.float32) for _ in range(SLOTS)]
        self.v = [np.zeros((0, KV, D), np.float32) for _ in range(SLOTS)]

    def reset(self, slot):
        self.k[slot], self.v[slot] = self.k[slot][:0], self.v[slot][:0]

    def feed(self, slot, k, v):
        self.k[slot] = np.concatenate([self.k[slot], k])
        self.v[slot] = np.concatenate([self.v[slot], v])

    def want(self, slot, q, first):
        """q [l, h, D] at positions first .. first + l - 1."""
        return plain_attention(q, self.k[slot], self.v[slot],
                               first + np.arange(len(q)))


def plain_attention(q, k, v, pos):
    """``window.masked_attention`` under ``window.visible``, in numpy
    (float64): q [l, h, d] at positions ``pos`` over k, v [n, kv, d] at
    positions 0 .. n - 1; a history that grows by a token a step would
    compile the jnp form anew at every length."""
    l, h, d = q.shape
    g = h // k.shape[1]
    k_pos = np.arange(len(k))
    seen = (k_pos[None] <= pos[:, None]) & (k_pos[None] > pos[:, None] - WINDOW)
    s = np.einsum("qkgd,nkd->kgqn", q.reshape(l, -1, g, d).astype(float),
                  k.astype(float)) / np.sqrt(d)
    s = np.where(seen[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("kgqn,nkd->qkgd", p, v.astype(float)).reshape(l, h, -1)


def draw(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


def serve(mode, chunk, steps):
    """Prefill slots 2 and 0 side by side (and a padding row) in chunks
    of ``chunk`` until each holds ~90 positions, hand slot 2 to a new
    tenant from position 0, then ``steps`` decode steps of slots 0 and 2
    (slot 1 idle); every output against the plain mask."""
    rng = np.random.default_rng(chunk)
    entry = window_ops.init_paged_ring(SLOTS, WINDOW, PAGE, KV, D, D,
                                       jnp.float32)
    # last tenants' leftovers everywhere: nothing is ever cleared
    entry = {n: jnp.asarray(draw(rng, *a.shape)) for n, a in entry.items()}
    table = jnp.zeros((SLOTS, 8), jnp.int32)     # the page pool's: unused
    lengths = np.zeros(SLOTS, np.int32)
    hist = History()
    h = KV * GROUP

    @jax.jit
    def prefill(entry, q, k, v, lengths, rows, count):
        step = kv_cache.layer_view(kv_cache.prefill_step(
            [entry], table, lengths, rows, count), 0)
        with kernel_mode_scope(mode):
            return kv_cache.attend(
                q, k, v, kv_cache.positions(step, *q.shape[:2]), step,
                window=WINDOW)

    @jax.jit
    def decode(entry, q, k, v, lengths, active):
        step = kv_cache.layer_view(kv_cache.decode_step(
            [entry], table, lengths, active), 0)
        with kernel_mode_scope(mode):
            return kv_cache.attend(
                q, k, v, kv_cache.positions(step, SLOTS, 1), step,
                window=WINDOW)

    def dispatch(slots, counts):
        nonlocal entry
        rows = np.array(slots + [slots[0]], np.int32)     # + a padding row
        count = np.array(counts + [0], np.int32)
        q, k, v = (draw(rng, len(rows), chunk, n, D) for n in (h, KV, KV))
        out, entry = prefill(entry, q, k, v, jnp.asarray(lengths),
                             jnp.asarray(rows), jnp.asarray(count))
        out = np.asarray(out)
        assert np.isfinite(out).all()
        for r, (slot, n) in enumerate(zip(slots, counts)):
            hist.feed(slot, k[r, :n], v[r, :n])
            np.testing.assert_allclose(
                out[r, :n], hist.want(slot, q[r, :n], lengths[slot]),
                atol=TOL, rtol=0)
            lengths[slot] += n

    while lengths[2] < 90:
        # slot 0 runs short chunks beside slot 2's full ones
        dispatch([2, 0], [chunk, max(1, chunk - 3)])
    hist.reset(2)
    lengths[2] = 0
    dispatch([2], [min(chunk, 11)])      # a shorter tenant, from zero
    for _ in range(steps):
        q, k, v = (draw(rng, SLOTS, 1, n, D) for n in (h, KV, KV))
        before = np.asarray(entry["k_ring"][1])
        out, entry = decode(entry, q, k, v, jnp.asarray(lengths),
                            jnp.asarray([True, False, True]))
        assert np.array_equal(before, np.asarray(entry["k_ring"][1]))
        for slot in (0, 2):
            hist.feed(slot, k[slot], v[slot])
            np.testing.assert_allclose(
                np.asarray(out)[slot], hist.want(slot, q[slot],
                                                 lengths[slot]),
                atol=TOL, rtol=0)
            lengths[slot] += 1


@pytest.mark.parametrize("mode,chunk,steps", [
    ("force", 8, 3), ("force", PAGE + 2, 2), ("reference", 8, 40),
    ("reference", PAGE + 2, 3)])
def test_a_ring_of_two_pages_more_serves_its_window(mode, chunk, steps):
    """``PAGE + 2`` columns is the longest chunk the ring takes, and
    every one of them straddles a page; 90 positions wrap the ring
    of 64 rows; the gather reference decodes on until slot 0 wraps
    again."""
    serve(mode, chunk, steps)


def test_the_form_is_the_rings_shape():
    entry = window_ops.init_ring(2, WINDOW, KV, D, D, jnp.float32)
    assert window_ops.ring_page_size(entry, WINDOW) == 0
    paged = window_ops.init_paged_ring(2, WINDOW, PAGE, KV, D, D,
                                       jnp.float32)
    assert paged["k_ring"].shape == (2, WINDOW + 2 * PAGE, KV, D)
    assert window_ops.ring_page_size(paged, WINDOW) == PAGE
    for rows in (WINDOW - 1, WINDOW + 2 * PAGE + 1, WINDOW + 2 * 5):
        odd = window_ops.init_ring(2, rows, KV, D, D, jnp.float32)
        with pytest.raises(ValueError, match="cannot serve a window"):
            window_ops.ring_page_size(odd, WINDOW)
    with pytest.raises(ValueError, match="a ring a slot, not pages"):
        window_ops.ring_page_size({"k_pages": None}, WINDOW)
    with pytest.raises(ValueError, match="whole pages"):
        window_ops.init_paged_ring(2, WINDOW + 1, PAGE, KV, D, D,
                                   jnp.float32)
    # a verify step, and a chunk too long for the ring, say so
    step = kv_cache.layer_view(kv_cache.verify_step(
        [paged], jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32),
        jnp.ones(2, jnp.int32)), 0)
    x = jnp.zeros((2, 2, KV, D))
    with pytest.raises(NotImplementedError, match="window-ring"):
        kv_cache.attend(x, x, x, jnp.zeros((2, 2), jnp.int32), step,
                        window=WINDOW)
    long = jnp.zeros((2, PAGE + 3, KV, D))
    step = kv_cache.layer_view(kv_cache.prefill_step(
        [paged], jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32),
        jnp.arange(2), jnp.ones(2, jnp.int32)), 0)
    with pytest.raises(ValueError, match=f"at most {PAGE + 2} columns"):
        kv_cache.attend(long, long, long,
                        jnp.zeros((2, PAGE + 3), jnp.int32), step,
                        window=WINDOW)


def pages_a_tile_visits(start, l):
    """Distinct pages holding a position that some query of a chunk of
    ``l`` columns from ``start`` sees, or that the chunk writes."""
    first = max(start - WINDOW + 1, 0)
    return (start + l - 1) // PAGE - first // PAGE + 1


@pytest.mark.parametrize("l", [1, 2, 3, 8, PAGE + 2, PAGE + 3])
def test_two_pages_more_are_needed_and_enough(l):
    """By brute force over every start: a chunk of 3 .. PAGE + 2
    columns visits WINDOW / PAGE + 2 pages at some start (its window's
    oldest position the last of a page, its own last column the first
    of another) and never more, so a ring of one page fewer has no page
    of its own for each; one column more and there is a start that
    needs a third.  ``page_view``'s bound is that count."""
    most = max(pages_a_tile_visits(s, l) for s in range(4 * WINDOW))
    assert most == (WINDOW + l - 3) // PAGE + 2        # page_view's
    ring = WINDOW // PAGE + 2
    assert (most <= ring) == (l <= PAGE + 2)
    if 3 <= l <= PAGE + 2:
        assert most == ring


# ------------------------------------- the kernels over a real page table

def test_the_kernels_walk_a_page_table_from_the_windows_first_page():
    """128 query heads on 4 KV heads make two q tiles of a 24-column
    chunk; rows deep in a table of 12 pages, at its start, mid-page, and
    a padding row.  The prefill kernel then the decode kernel, each
    against the plain mask over the gathered pages."""
    h, kv, d, maxp, l = 128, 4, 16, 12, 24
    rng = np.random.default_rng(3)
    cols, tiles, _ = key_block_plan(l, h, kv, PAGE, d, 4, 4)
    assert tiles == 2 and cols < l
    k_pages, v_pages = (jnp.asarray(draw(rng, 40, PAGE, kv, d))
                        for _ in range(2))
    table = jnp.asarray(rng.permutation(39)[:3 * maxp].reshape(3, maxp) + 1,
                        jnp.int32)
    start = np.array([150, 0, 37], np.int32)
    count = np.array([l, l - 5, 0], np.int32)
    q = draw(rng, 3, l, h, d)
    out = paged_prefill(jnp.asarray(q), k_pages, v_pages, None, None, table,
                        jnp.asarray(start), jnp.asarray(count),
                        scale=d ** -0.5, interpret=True, window=WINDOW)
    k_all, v_all = (np.asarray(p)[np.asarray(table)].reshape(
        3, maxp * PAGE, kv, d) for p in (k_pages, v_pages))

    def want(r, q_rows, pos):
        return plain_attention(q_rows, k_all[r], v_all[r], pos)
    assert np.isfinite(np.asarray(out)).all()
    for r in range(2):
        n = count[r]
        np.testing.assert_allclose(
            np.asarray(out)[r, :n],
            want(r, q[r, :n], start[r] + np.arange(n)), atol=TOL, rtol=0)
    pos = np.array([150, 5, 37], np.int32)
    q1 = draw(rng, 3, 1, h, d)
    out = _paged_decode_pallas(
        jnp.asarray(q1), k_pages, v_pages, table, jnp.asarray(pos),
        scale=d ** -0.5, interpret=True,
        active=jnp.asarray([True, True, False]), window=WINDOW)
    for r in range(2):
        np.testing.assert_allclose(np.asarray(out)[r],
                                   want(r, q1[r], pos[r:r + 1]),
                                   atol=TOL, rtol=0)
    assert not np.asarray(out)[2].any()          # an idle slot reads zeros


# --------------------------------------------- window=0: the old programs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# sha256 of str(jax.make_jaxpr(kernel call)) -- the XLA ops around the
# kernel and the kernel's own body, no source locations -- at commit
# 4c308f1, the last without a ``window`` argument, for the ahead-of-time
# case of that name (tests/chip_bench/aot/): Mistral's grouped-query
# decode and MiMo's grouped prefill (a key wider than a value).  The
# Mosaic payload of the lowered text carries file names and line
# numbers, so it is the jaxpr that is held.  Kanana's latent prefill is
# held as PR 63 left it (its q and output tiles head-major; 23dade11...
# at 4c308f1): the grouped form beside it shows that only the latent
# branch moved.
BEFORE_WINDOW = {
    "paged_decode.mistral-longprompt":
        "3581d441378a68c7e6b664abb02c48818fcd69b820e3bd69e80c082440cf37d0",
    "paged_prefill.mimo-longctx":
        "f3b1ef59e799a9375656b53f66524a974a67bd83a314592232e8a4d6070f9e65",
    "paged_prefill.kanana2-longdoc":
        "c6d9d0290df4e25f5aa1ccd3a953027e029f90f47f57779a1c6e541b08b7aa2d",
}


def _case_jaxpr(case, **window):
    with open(os.path.join(REPO, "tests", "chip_bench", "aot",
                           case + ".json")) as f:
        c = json.load(f)
    dt = jnp.dtype(c["dtype"])

    def spec(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)
    ints = dict(dtype=jnp.int32)
    if case.startswith("paged_decode"):
        pool = spec(c["pages"], c["page_size"], c["kv_heads"], c["head_dim"])
        return str(jax.make_jaxpr(
            lambda q, k, v, table, pos: _paged_decode_pallas(
                q, k, v, table, pos, scale=c["head_dim"] ** -0.5,
                interpret=False, k_scale=None, v_scale=None, **window))(
            spec(c["slots"], 1, c["heads"], c["head_dim"]), pool, pool,
            spec(c["slots"], c["max_pages"], **ints),
            spec(c["slots"], **ints)))
    rows = (spec(c["rows"], c["max_pages"], **ints), spec(c["rows"], **ints),
            spec(c["rows"], **ints))
    if "kv_heads" in c:         # K and V pages, a key wider than a value
        return str(jax.make_jaxpr(
            lambda q, k, v, table, start, count: paged_prefill(
                q, k, v, None, None, table, start, count,
                scale=c["scale_dim"] ** -0.5, interpret=False, **window))(
            spec(c["rows"], c["chunk"], c["heads"], c["k_dim"]),
            spec(c["pages"], c["page_size"], c["kv_heads"], c["k_dim"]),
            spec(c["pages"], c["page_size"], c["kv_heads"], c["v_dim"]),
            *rows))
    return str(jax.make_jaxpr(
        lambda q, pool, table, start, count: paged_prefill(
            q, pool, None, None, None, table, start, count,
            scale=c["scale_dim"] ** -0.5, interpret=False,
            value_dim=c["value_dim"], **window))(
        spec(c["rows"], c["chunk"], c["heads"], c["stored_dim"]),
        spec(c["pages"], c["page_size"], c["stored_dim"]), *rows))


@pytest.mark.parametrize("case", sorted(BEFORE_WINDOW))
def test_without_a_window_the_kernels_are_the_programs_they_were(case):
    def digest(text):
        return hashlib.sha256(text.encode()).hexdigest()
    assert digest(_case_jaxpr(case)) == BEFORE_WINDOW[case]
    assert digest(_case_jaxpr(case, window=0)) == BEFORE_WINDOW[case]
    assert digest(_case_jaxpr(case, window=4096)) != BEFORE_WINDOW[case]
