"""Mamba-2 (state-space duality) mixer mathematics, XLA ops only.

One recurrence, per head h with a scalar decay (Dao & Gu 2024; the
``nemotron_h`` family's ``M`` layers)::

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (x) B_t     [P, N]
    y_t = h_t . C_t + D * x_t                             [P]

with ``x`` [heads, P], ``B``/``C`` [groups, N] (head h reads group
h // (heads / groups)), ``dt = softplus(dt_raw + dt_bias)`` and
``A = -exp(A_log)``.  Around it: a causal depthwise conv + SiLU over
``[x | B | C]`` before, and ``RMSNorm_grouped(y * silu(z))`` after.

Three entries share it (:func:`mixer_sequence` is the first two):

* a whole sequence from a zero state (``generate()``'s oracle path,
  training): the published chunked scan, blocks of ``chunk`` tokens;
* one chunk per row continuing from a carried state (chunked prefill):
  the same scan, ``n_valid`` columns live — a padding column has
  ``dt = 0``, which leaves the state exactly alone;
* one token per slot (:func:`mixer_token`, decode): the recurrence
  itself.

``dt``, ``A``, the decays, the state and the gated norm are float32, as
in the published implementation; the contractions of the scan run at
``"highest"`` (they are a hundredth of the layer's projections' FLOPs)
so that a chunked result does not depend on the block size beyond
float32 rounding.
"""

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def causal_conv(xbc, tail, weight, bias, n_valid=None):
    """Depthwise causal conv1d + SiLU.  ``xbc`` [b, l, c], ``tail``
    [b, k-1, c] (the k-1 inputs before column 0), ``weight`` [k, c],
    ``bias`` [c].  Returns (silu(conv) [b, l, c] float32, the new tail:
    the k-1 inputs before column ``n_valid[b]`` — ``l`` where None)."""
    k = weight.shape[0]
    l = xbc.shape[1]
    seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = weight.astype(F32)
    out = sum(seq[:, j:j + l].astype(F32) * w[j] for j in range(k))
    out = jax.nn.silu(out + bias.astype(F32))
    if n_valid is None:
        new_tail = seq[:, l:]
    else:
        idx = n_valid[:, None] + jnp.arange(k - 1)[None, :]
        new_tail = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
    return out, new_tail


def _chunk(x, dt, a_head, b_mat, c_mat, h):
    """One block of the scan.  x [b, q, g, r, p], dt [b, q, g, r],
    a_head [g, r], b_mat/c_mat [b, q, g, n], h [b, g, r, p, n]; all
    float32.  Returns (y [b, q, g, r, p], the state after column q)."""
    q = x.shape[1]
    cs = jnp.cumsum(dt * a_head, axis=1)                  # [b, q, g, r] <= 0
    # within the block: y_t += sum_{s<=t} exp(cs_t - cs_s) (C_t.B_s) dt_s x_s
    cb = jnp.einsum("bqgn,bsgn->bgqs", c_mat, b_mat, precision=HIGHEST)
    diff = cs[:, :, None] - cs[:, None, :]                # [b, q, s, g, r]
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    decay = jnp.exp(jnp.where(causal[None, :, :, None, None], diff,
                              -jnp.inf))
    w = decay * dt[:, None] * jnp.moveaxis(cb, 1, -1)[..., None]
    y = jnp.einsum("bqsgr,bsgrp->bqgrp", w, x, precision=HIGHEST)
    # from the carried state: y_t += exp(cs_t) C_t . h
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bqgn,bgrpn->bqgrp", c_mat, h, precision=HIGHEST)
    # the state after the block
    to_end = jnp.exp(cs[:, -1:] - cs) * dt                # [b, q, g, r]
    h = jnp.exp(cs[:, -1])[..., None, None] * h + jnp.einsum(
        "bsgrp,bsgn->bgrpn", x * to_end[..., None], b_mat,
        precision=HIGHEST)
    return y, h


def ssd_scan(x, dt, a_head, b_mat, c_mat, h0, chunk):
    """The recurrence over a sequence, ``chunk`` tokens a block.
    x [b, l, heads, p], dt [b, l, heads] (0 at a padding column),
    a_head [heads] (< 0), b_mat/c_mat [b, l, groups, n], h0 [b, heads,
    p, n].  Returns (y [b, l, heads, p] WITHOUT the D skip, h after the
    last column); float32 throughout."""
    b, l, heads, p = x.shape
    g, n = b_mat.shape[2:]
    r = heads // g
    q = min(chunk, l)
    pad = -l % q
    x, dt, b_mat, c_mat = (
        jnp.pad(t.astype(F32), [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        for t in (x, dt, b_mat, c_mat))
    nc = (l + pad) // q

    def blocks(t):       # [b, l, ...] -> [nc, b, q, ...]
        return jnp.moveaxis(t.reshape(b, nc, q, *t.shape[2:]), 1, 0)
    xs = (blocks(x.reshape(b, l + pad, g, r, p)),
          blocks(dt.reshape(b, l + pad, g, r)), blocks(b_mat), blocks(c_mat))
    a_head = a_head.astype(F32).reshape(g, r)
    h = h0.astype(F32).reshape(b, g, r, p, n)
    if nc == 1:
        y, h = _chunk(xs[0][0], xs[1][0], a_head, xs[2][0], xs[3][0], h)
        y = y[None]
    else:
        def body(h, blk):
            y, h = _chunk(blk[0], blk[1], a_head, blk[2], blk[3], h)
            return h, y
        h, y = lax.scan(body, h, xs)
    y = jnp.moveaxis(y, 0, 1).reshape(b, l + pad, heads, p)[:, :l]
    return y, h.reshape(b, heads, p, n)


def ssm_token(x, dt, a_head, b_vec, c_vec, h):
    """The recurrence for one token.  x [b, heads, p], dt [b, heads],
    b_vec/c_vec [b, groups, n], h [b, heads, p, n] float32.  Returns
    (y [b, heads, p] without the D skip, the new state)."""
    r = x.shape[1] // b_vec.shape[1]
    b_h = jnp.repeat(b_vec.astype(F32), r, axis=1)        # [b, heads, n]
    c_h = jnp.repeat(c_vec.astype(F32), r, axis=1)
    dt = dt.astype(F32)
    decay = jnp.exp(dt * a_head.astype(F32))
    h = h * decay[..., None, None] + \
        (dt[..., None] * x.astype(F32))[..., None] * b_h[:, :, None, :]
    return jnp.sum(h * c_h[:, :, None, :], axis=-1), h


def gated_group_norm(y, z, scale, groups, eps):
    """``RMSNorm(y * silu(z))`` over ``groups`` equal groups of the last
    axis with a learned scale, float32."""
    y = y.astype(F32) * jax.nn.silu(z.astype(F32))
    shape = y.shape
    y = y.reshape(*shape[:-1], groups, shape[-1] // groups)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return y.reshape(shape) * scale.astype(F32)


def _split(zxbcdt, dims):
    inner, conv_dim = dims["inner"], dims["conv_dim"]
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv_dim],
            zxbcdt[..., inner + conv_dim:])


def _heads(act, dims):
    """Post-conv activations [..., conv_dim] -> x [..., heads, p],
    B and C [..., groups, n]."""
    inner, gn = dims["inner"], dims["groups"] * dims["state"]
    lead = act.shape[:-1]
    return (act[..., :inner].reshape(*lead, dims["heads"], dims["head_dim"]),
            act[..., inner:inner + gn].reshape(*lead, dims["groups"],
                                               dims["state"]),
            act[..., inner + gn:].reshape(*lead, dims["groups"],
                                          dims["state"]))


def mixer_sequence(zxbcdt, w, dims, tail, h0, n_valid=None):
    """Everything between ``in_proj`` and ``out_proj`` for a sequence.
    ``zxbcdt`` [b, l, inner + conv_dim + heads]; ``w`` holds ``conv_w``
    [k, conv_dim], ``conv_b``, ``dt_bias``, ``A_log``, ``D`` [heads],
    ``norm`` [inner]; ``dims`` the static sizes (heads, head_dim,
    groups, state, inner, conv_dim, chunk, eps).  ``tail``/``h0`` are
    the carried conv inputs and state; columns at or past ``n_valid[b]``
    are padding and leave both alone.  Returns (y [b, l, inner] float32,
    new tail, new state)."""
    z, xbc, dt = _split(zxbcdt, dims)
    l = zxbcdt.shape[1]
    with jax.named_scope("ssm"):
        act, tail = causal_conv(xbc, tail, w["conv_w"], w["conv_b"], n_valid)
        x, b_mat, c_mat = _heads(act, dims)
        dt = jax.nn.softplus(dt.astype(F32) + w["dt_bias"].astype(F32))
        if n_valid is not None:
            dt = jnp.where(jnp.arange(l)[None, :, None] <
                           n_valid[:, None, None], dt, 0.0)
        y, h = ssd_scan(x, dt, -jnp.exp(w["A_log"].astype(F32)), b_mat,
                        c_mat, h0, dims["chunk"])
        y = y + w["D"].astype(F32)[:, None] * x
    y = gated_group_norm(y.reshape(*y.shape[:2], dims["inner"]), z,
                         w["norm"], dims["groups"], dims["eps"])
    return y, tail, h


def mixer_token(zxbcdt, w, dims, tail, h):
    """:func:`mixer_sequence` for one token a row: ``zxbcdt`` [b, 1,
    ...].  Returns (y [b, 1, inner] float32, new tail, new state)."""
    z, xbc, dt = _split(zxbcdt, dims)
    with jax.named_scope("ssm"):
        act, tail = causal_conv(xbc, tail, w["conv_w"], w["conv_b"])
        x, b_vec, c_vec = _heads(act[:, 0], dims)
        dt = jax.nn.softplus(dt[:, 0].astype(F32) + w["dt_bias"].astype(F32))
        y, h = ssm_token(x, dt, -jnp.exp(w["A_log"].astype(F32)), b_vec,
                         c_vec, h)
        y = y + w["D"].astype(F32)[:, None] * x
    y = gated_group_norm(y.reshape(y.shape[0], 1, dims["inner"]), z,
                         w["norm"], dims["groups"], dims["eps"])
    return y, tail, h
