"""The window-ring contract: what a sliding-window attention layer keeps
between serving dispatches, beside the page pool.

A layer whose queries see the last ``window`` positions alone (their own
included) needs ``window`` positions a slot whatever the context, so its
entry in the pools pytree holds no pages but a ring a slot::

    {"k_ring": [slots, window, kv_heads, k_dim]   the cache dtype
     "v_ring": [slots, window, kv_heads, v_dim]}

with position ``p`` at row ``p % window``.  Like a recurrent layer's
state (ops/ssm/state.py, whose rules these are) a ring is sized by the
slot count and cannot be shared by pages.  The layer reads a
``kv_cache.PagedStep`` — ``mode``, ``rows``, ``lengths``, ``count`` —
and nothing else:

* prefill: batch row r is slot ``rows[r]`` and its chunk starts at
  position ``lengths[rows[r]]``.  The queries see the ring's live rows
  (row j holds the newest position <= start - 1 congruent to j, live if
  that is >= 0) and the chunk's own keys, masked by absolute position;
  then the chunk's last <= ``window`` valid positions are written.  A row
  that starts at position 0 sees no ring row, whatever the slot's last
  tenant left there: that IS the slot reset, and no host-side clear
  exists.  A padding row (``count[r] == 0``) writes nothing.  Any chunk
  length works, one longer than the window included.
* decode: batch row r is slot r; the token's K/V is written at row
  ``pos % window`` first and the query then sees every row that holds a
  position >= 0.  An inactive slot (``count[r]`` false) writes nothing,
  so its ring stays bit-identical.
* verify (speculative decode) would have to take rejected positions out
  of a ring that has already overwritten what they replaced: it raises.

``sink`` is one learned logit a query head that joins the softmax and
whose column is dropped: ``p_ij = exp(s_ij) / (exp(sink_h) + sum_j
exp(s_ij))``.  Keys and values may differ in width.

Two forms read a ring, and the ring's SHAPE says which (never a model's
name, an option or a flag):

* ``rows == window`` (:func:`init_ring`): XLA ops over the whole ring,
  :func:`attend_ring` -- a score tensor [rows, heads, chunk, window +
  chunk] in HBM, which is right at 128 keys a slot and takes a sink and
  two widths.
* ``rows == window + 2 pages`` (:func:`init_paged_ring`): the two paged
  kernels (paged_prefill.py, decode.py, each given ``window=``) read
  the leaf as a page pool of its own.  Position ``p`` still sits at row
  ``p % rows``; reshaped -- free, the leaf is contiguous -- it is
  ``[slots x rows / page, page, kv_heads, dim]``, and :func:`page_view`
  derives a table a dispatch row: with ``lo`` the page of the oldest
  position the row's first query sees, table entry ``l`` is page
  ``slot x pages + (lo + l) % pages`` and the row's positions are
  counted from ``lo``'s first (the masks are differences of positions,
  so nothing else changes, and the table is ``rows / page`` wide
  whatever the context).  The chunk is written through that table
  FIRST and read after, as a page pool's is, so the ring must hold the
  window of the chunk's first query beside the chunk itself, each
  page they touch a page of its own: ``window + l - 1`` positions on
  end lie in ``window / page + 2`` pages where the chunk straddles a
  page boundary, for any ``l <= page + 2`` -- hence the two pages more,
  and one fewer leaves some start without a page for its last
  (tests/unit/test_window_paged.py counts both).  No score tensor
  wider than a key block reaches HBM, and a row costs its window's
  pages.  A sink has no kernel here and raises.  Off the TPU (and
  under ``paged_kernel="reference"``) the same view runs the gather
  reference, as a page pool's does.

The XLA form is 128 keys a slot; the pool is donated to
every dispatch and updated in place by the scatters below.
:func:`masked_attention` is also what a model with a sink or with
``k_dim != v_dim`` runs where no serving cache is involved (training,
the full forward, ``generate()``'s dense cache): the flash and
contiguous-decode kernels take neither.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
RING_LEAVES = ("k_ring", "v_ring")


def init_ring(slots, window, kv_heads, k_dim, v_dim, dtype):
    return {"k_ring": jnp.zeros((slots, window, kv_heads, k_dim), dtype),
            "v_ring": jnp.zeros((slots, window, kv_heads, v_dim), dtype)}


def paged_ring_rows(window, page_size):
    """Rows of a ring the paged kernels read: the window and two pages
    (see the module's docstring for why two)."""
    if window % page_size:
        raise ValueError(
            f"a paged ring holds whole pages: window {window} is no "
            f"multiple of the page size {page_size}")
    return window + 2 * page_size


def init_paged_ring(slots, window, page_size, kv_heads, k_dim, v_dim, dtype):
    """The ring of a window too long for XLA ops over all of it."""
    return init_ring(slots, paged_ring_rows(window, page_size), kv_heads,
                     k_dim, v_dim, dtype)


def bytes_per_slot(window, kv_heads, k_dim, v_dim, dtype):
    """Exact bytes one slot's ring of ``window`` rows costs in ONE
    window layer."""
    return window * kv_heads * (k_dim + v_dim) * jnp.dtype(dtype).itemsize


def ring_page_size(entry, window):
    """The page size the paged kernels read ``entry``'s ring at, from
    its shape: 0 for a ring of ``window`` rows (the XLA form)."""
    if "k_ring" not in entry:
        raise ValueError(
            "a window layer on the paged serving path keeps a ring a "
            "slot, not pages: the model's init_paged_kv_cache has to "
            "build its entry with ops/attention/window.init_ring or "
            f"init_paged_ring (this entry holds {sorted(entry)})")
    rows = entry["k_ring"].shape[1]
    if rows == window:
        return 0
    page = (rows - window) // 2
    if rows < window or rows != window + 2 * page or window % page:
        raise ValueError(f"a ring of {rows} rows cannot serve a window of "
                         f"{window}: {window} rows, or {window} and two "
                         "pages that divide it")
    return page


def page_view(step, positions, window, page_size):
    """(``step`` with the layer's ring as a page pool and the table
    derived for it, ``positions`` counted from each row's first table
    entry): what ``kv_cache.attend`` hands the paged write and the
    paged kernels for a ring of ``window + 2 x page_size`` rows.  Row
    r's table starts at ``lo``, the page of the oldest position its
    first query sees, so it is ``rows / page_size`` wide at any
    context.  The view names no slot (``rows`` None): its table is a
    dispatch row's already."""
    if step.mode not in ("prefill", "decode"):
        raise NotImplementedError(
            f"a window-ring layer cannot run a {step.mode!r} step: "
            "rejected tokens would have to be taken out of a ring that "
            "has overwritten what they replaced")
    k_ring, v_ring = (step.layers[name] for name in RING_LEAVES)
    slots, rows = k_ring.shape[:2]
    pages = rows // page_size
    b, l = positions.shape
    # the window of the first query and the chunk are window + l - 1
    # positions on end, which lie in this many pages at most
    if (window + l - 3) // page_size + 2 > pages:
        raise ValueError(
            f"a chunk of {l} columns does not fit a ring of {rows} rows "
            f"beside its first query's window of {window}: at most "
            f"{rows - window - page_size + 2} columns a dispatch")
    with jax.named_scope("cache"):
        slot = jnp.arange(b) if step.rows is None else step.rows
        lo = jnp.maximum(positions[:, 0] - window + 1, 0) // page_size
        table = slot[:, None] * pages + \
            (lo[:, None] + jnp.arange(pages)[None, :]) % pages
        positions = positions - (lo * page_size)[:, None]

    def as_pool(ring):
        return ring.reshape((slots * pages, page_size) + ring.shape[2:])
    view = dataclasses.replace(
        step, layers={"k_pages": as_pool(k_ring), "v_pages": as_pool(v_ring)},
        page_table=table.astype(jnp.int32), rows=None)
    return view, positions


def ring_entry(pools, like):
    """A :func:`page_view`'s updated pools as the ring entry ``like``."""
    return {name: pools[leaf].reshape(like[name].shape)
            for name, leaf in zip(RING_LEAVES, ("k_pages", "v_pages"))}


def masked_attention(q, k, v, mask, sink=None):
    """Grouped-query attention of q [b, l, h, k_dim] over k [b, n, kv_h,
    k_dim] / v [b, n, kv_h, v_dim] where ``mask`` (bool, broadcastable
    to [b, l, n]) allows; query head ``kv * g + i`` reads kv head ``kv``.
    Scores and statistics in float32, P cast to v's dtype before P.V
    (the arithmetic of ``decode._gqa_reference``).  ``sink`` [h] adds
    ``exp(sink_h)`` to each row's denominator.  A row that sees no key
    returns a finite, meaningless mean (zeros with a sink)."""
    b, l, h, d = q.shape
    kv_h = k.shape[2]
    g = h // kv_h
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(b, l, kv_h, g, d), k,
                   preferred_element_type=F32) * (1.0 / d ** 0.5)
    mask = jnp.broadcast_to(mask, (b, l, k.shape[1]))
    s = jnp.where(mask[:, None, None], s, jnp.finfo(F32).min)
    m = s.max(axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(F32).reshape(1, kv_h, g, 1, 1)
        m = jnp.maximum(m, sk)
    p = jnp.exp(s - m)
    denom = p.sum(axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(sk - m)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", (p / denom).astype(v.dtype), v)
    return out.reshape(b, l, h, v.shape[-1]).astype(q.dtype)


def visible(q_pos, k_pos, window=0):
    """[b, l, n] bool: key position visible to query position (causal;
    the last ``window`` positions, the query's own included, if > 0)."""
    mask = k_pos[:, None, :] <= q_pos[:, :, None]
    if window > 0:
        mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
    return mask


def attend_fresh(q, k, v, *, window=0, sink=None):
    """No cache: every position of the call against the call's own."""
    pos = jnp.arange(q.shape[1])[None]
    return masked_attention(q, k, v, visible(pos, pos, window), sink)


def attend_dense(q, k, v, positions, cache, *, window=0, sink=None):
    """``generate()``'s dense cache ``{"k", "v", "index"}``: append at
    ``index`` and attend over the whole buffer under the positional
    mask (``kv_cache._attend_dense`` with a sink and two widths)."""
    with jax.named_scope("cache"):
        at = (0, cache["index"], 0, 0)
        k_cache = lax.dynamic_update_slice(cache["k"],
                                           k.astype(cache["k"].dtype), at)
        v_cache = lax.dynamic_update_slice(cache["v"],
                                           v.astype(cache["v"].dtype), at)
    k_pos = jnp.arange(k_cache.shape[1])[None]
    out = masked_attention(q, k_cache, v_cache,
                           visible(positions, k_pos, window), sink)
    return out, {"k": k_cache, "v": v_cache,
                 "index": cache["index"] + q.shape[1]}


def _held(last, window):
    """[b, window]: the position ring row j holds once position
    ``last[b]`` is the newest written — the largest p <= last with
    p % window == j — negative where no such position exists yet."""
    j = jnp.arange(window)[None, :]
    return last[:, None] - (last[:, None] - j) % window


def attend_ring(q, k, v, positions, step, *, window, sink=None):
    """One serving dispatch of a window layer (see the module's
    docstring).  ``step`` is the layer's view of a ``PagedStep`` whose
    ``layers`` is the ring entry.  Returns (out [b, l, h, v_dim], the
    updated entry)."""
    entry = step.layers
    k_ring, v_ring = entry["k_ring"], entry["v_ring"]
    slots, size = k_ring.shape[:2]
    if size != window:
        raise ValueError(f"a ring of {size} rows cannot serve a window "
                         f"of {window} as XLA ops")
    b, l = positions.shape
    k, v = k.astype(k_ring.dtype), v.astype(v_ring.dtype)
    if step.mode == "decode":
        pos = positions[:, 0]
        # an out-of-range slot id drops an inactive slot's write
        with jax.named_scope("cache"):
            at = jnp.where(step.count.astype(bool), jnp.arange(b), slots)
            k_ring = k_ring.at[at, pos % window].set(k[:, 0], mode="drop")
            v_ring = v_ring.at[at, pos % window].set(v[:, 0], mode="drop")
        out = masked_attention(q, k_ring, v_ring,
                               (_held(pos, window) >= 0)[:, None, :], sink)
        return out, {"k_ring": k_ring, "v_ring": v_ring}
    if step.mode != "prefill":
        raise NotImplementedError(
            f"a window-ring layer cannot run a {step.mode!r} step: "
            "rejected tokens would have to be taken out of a ring that "
            "has overwritten what they replaced")
    start = positions[:, 0]
    held = _held(start - 1, window)           # all < 0 where start == 0
    cols = jnp.arange(l)[None, :]
    valid = cols < step.count[:, None]
    k_pos = jnp.concatenate([held, positions], axis=1)
    live = jnp.concatenate([held >= 0, valid], axis=1)
    out = masked_attention(
        q, jnp.concatenate([k_ring[step.rows], k], axis=1),
        jnp.concatenate([v_ring[step.rows], v], axis=1),
        visible(positions, k_pos, window) & live[:, None, :], sink)
    # the chunk's last <= window valid columns, each to its own row
    with jax.named_scope("cache"):
        keep = valid & (cols >= step.count[:, None] - window)
        at = jnp.where(keep, step.rows[:, None], slots)
        return out, {
            "k_ring": k_ring.at[at, positions % window].set(k, mode="drop"),
            "v_ring": v_ring.at[at, positions % window].set(v, mode="drop")}
