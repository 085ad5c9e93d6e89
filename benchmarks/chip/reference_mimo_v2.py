"""Plain reference of the ``mimo_v2_flash`` family (XiaomiMiMo/MiMo-V2-
Flash): the forward pass in straightforward float32 ``jax.numpy`` — no
kernels, no cache, no ring, no grouped matmul, nothing imported from the
program under test.  The callers run it under
``jax.default_matmul_precision("highest")``; the weights are the
program's own, upcast one block at a time.

Pre-norm residual: ``x <- x + Attn_i(RMSNorm(x))``, ``x <- x +
FFN_i(RMSNorm(x))`` (eps ``eps``); then the final RMSNorm and the untied
head.

Attention, by ``layer_pattern[i]`` (0 full, 1 window): ``q`` as
``heads`` x ``head_dim``, ``k`` as kv x ``head_dim``, ``v`` as kv x
``v_head_dim`` with kv = ``kv_heads`` (full) or ``swa_kv_heads``
(window).  Rotary on the first ``floor(rotary_factor x head_dim)``
features (made even) of q and k in the half-rotation convention, base
``theta`` (full) or ``swa_theta`` (window); the rest pass through.
``v <- value_scale x v``.  Scores ``q_i . k_j / sqrt(head_dim)`` for
``j <= i`` and, in a window layer, ``j > i - window``.  Where the kind's
sink flag is set, ``p_ij = exp(s_ij) / (exp(b_h) + sum_j exp(s_ij))``
with ``b_h`` one learned scalar a query head (a logit that joins the
softmax and whose column is dropped); else the plain softmax.  ``o_i =
sum_j p_ij v_j``, output ``concat_h(o) W_o``.  Scores are computed for a
block of query rows at a time.

Feed-forward, by ``moe_pattern[i]``: 0 is ``W_down(silu(W_gate u) *
W_up u)``; 1 is routed: ``s = sigmoid(u W_r)`` over all the router's
experts, the ``per_token`` largest of ``s + bias`` chosen, weights
``s[chosen] / (sum + 1e-20) x scaling`` (a published ``null`` scaling is
1), each chosen expert the same SwiGLU (its gate and up matrices side by
side in ``w_up``), as a loop over the experts HELD here (``first_held ..
+ held``) with a mask; what the absent experts would add is left out,
and there is no shared expert.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOKEN_BLOCK = 2048      # feed-forward rows at a time
QUERY_BLOCK = 128       # attention query rows at a time


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def in_blocks(fn, x, block):
    """``fn`` over the rows of x [t, ...] a block at a time."""
    t = x.shape[0]
    n = -(-t // block)
    padded = jnp.pad(x, ((0, n * block - t),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, padded.reshape((n, block) + x.shape[1:]))
    return out.reshape((n * block,) + out.shape[2:])[:t]


def rotary(x, pos, dim, theta):
    """Half-rotation rotary on the first ``dim`` features of x [t, h,
    d]: feature i pairs with feature i + dim / 2."""
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(q, k, v, *, window, sink, drop=()):
    """q [t, h, d], k [t, kv, d], v [t, kv, dv] -> [t, h, dv]; query
    head i reads kv head i // (h / kv).  ``window`` 0 is full causal
    attention.  ``drop`` names terms to leave out (the unit tests show
    that the comparison sees each)."""
    t, h, d = q.shape
    kv, dv = k.shape[1], v.shape[2]
    g = h // kv
    k_pos = jnp.arange(t)

    def block(args):
        qb, q_pos = args                          # [blk, h, d], [blk]
        s = jnp.einsum("qkgd,tkd->kgqt", qb.reshape(-1, kv, g, d), k) \
            / jnp.sqrt(F32(d))
        mask = k_pos[None, :] <= q_pos[:, None]
        if window:
            edge = window + 1 if "window_edge" in drop else window
            mask &= k_pos[None, :] > q_pos[:, None] - edge
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m = jnp.max(s, -1, keepdims=True)
        if sink is not None:
            b_h = sink.reshape(kv, g, 1, 1)
            m = jnp.maximum(m, b_h)
        e = jnp.exp(s - m)
        denom = jnp.sum(e, -1, keepdims=True)
        if sink is not None:
            denom = denom + jnp.exp(b_h - m)
        o = jnp.einsum("kgqt,tkd->qkgd", e / denom, v)
        return o.reshape(-1, h, dv)
    n = -(-t // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - t
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n, QUERY_BLOCK, h, d)
    # a padding query sits at the last position: it sees keys, is unused
    pos = jnp.minimum(jnp.arange(n * QUERY_BLOCK), t - 1) \
        .reshape(n, QUERY_BLOCK)
    return jax.lax.map(block, (qp, pos)).reshape(-1, h, dv)[:t]


def attention_layer(u, w, kind, *, heads, kv_heads, swa_kv_heads, head_dim,
                    v_head_dim, rotary_factor, theta, swa_theta, window,
                    value_scale, swa_sink, full_sink, drop=(), **_):
    t = u.shape[0]
    kv = swa_kv_heads if kind else kv_heads
    q = (u @ w["wq"]).reshape(t, heads, head_dim)
    k = (u @ w["wk"]).reshape(t, kv, head_dim)
    v = (u @ w["wv"]).reshape(t, kv, v_head_dim)
    if "partial_rotary" in drop:
        rot = head_dim
    else:
        rot = int(rotary_factor * head_dim) // 2 * 2
    pos = jnp.arange(t)
    base = F32(swa_theta if kind else theta)
    q, k = rotary(q, pos, rot, base), rotary(k, pos, rot, base)
    if "value_scale" not in drop:
        v = v * value_scale
    has_sink = (swa_sink if kind else full_sink) and "sink" not in drop
    o = attention(q, k, v, window=window if kind else 0,
                  sink=w["sink"] if has_sink else None, drop=drop)
    return o.reshape(t, heads * v_head_dim) @ w["wo"]


def swiglu(u, w_gate_up, w_down):
    inter = w_down.shape[0]
    h = u @ w_gate_up
    return (jax.nn.silu(h[:, :inter]) * h[:, inter:]) @ w_down


def dense_ffn(u, w, **_):
    return in_blocks(
        lambda r: (jax.nn.silu(r @ w["w_gate"]) * (r @ w["w_up"]))
        @ w["w_down"], u, TOKEN_BLOCK)


def routed_ffn(u, w, *, per_token, scaling, first_held, drop=(), **_):
    scaling = 1.0 if scaling is None else scaling

    def rows(r):
        s = jax.nn.sigmoid(r @ w["router"])
        choose = s if "score_bias" in drop else s + w["bias"]
        _, chosen = jax.lax.top_k(choose, per_token)
        wts = jnp.take_along_axis(s, chosen, axis=-1)
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20) * scaling
        out = jnp.zeros_like(r)
        for e in range(w["w_up"].shape[0]):
            gate = jnp.sum(jnp.where(chosen == first_held + e, wts, 0.0), -1)
            out = out + gate[:, None] * swiglu(r, w["w_up"][e],
                                               w["w_down"][e])
        return out
    return in_blocks(rows, u, TOKEN_BLOCK)


def layer_weights(params, i, kind, routed):
    """Block i's weights from the program's parameter tree, float32."""
    p = params[f"layers_{i}"]
    a = p["swa" if kind else "attn"]
    w = {n: a[n]["kernel"] for n in ("wq", "wk", "wv", "wo")}
    if "sink" in a:
        w["sink"] = a["sink"]
    if routed:
        m = p["moe"]
        w.update(router=m["router"], bias=m["e_score_correction_bias"],
                 w_up=m["w_up"], w_down=m["w_down"])
    else:
        w.update({n: p["mlp"][n]["kernel"]
                  for n in ("w_gate", "w_up", "w_down")})
    w["input_norm"] = p["input_norm"]["scale"]
    w["pre_ff_norm"] = p["pre_ff_norm"]["scale"]
    return jax.tree.map(lambda x: jnp.asarray(x).astype(F32), w)


def hidden(params, ids, *, layer_pattern, moe_pattern, eps, **sizes):
    """Final-norm hidden states [b, t, hidden] of token ids [b, t]; one
    jitted program a KIND of block, one row at a time."""
    x = params["embed_tokens"].astype(F32)[ids]

    def block(kind, routed):
        ffn = routed_ffn if routed else dense_ffn

        def one(r, w):
            r = r + attention_layer(rms_norm(r, w["input_norm"], eps), w,
                                    kind, **sizes)
            return r + ffn(rms_norm(r, w["pre_ff_norm"], eps), w, **sizes)
        return jax.jit(lambda x, w: jax.lax.map(lambda r: one(r, w), x))
    kinds = list(zip(layer_pattern, moe_pattern))
    blocks = {k: block(*k) for k in set(kinds)}
    for i, k in enumerate(kinds):
        x = blocks[k](x, layer_weights(params, i, *k))
    return rms_norm(x, params["norm_f"]["scale"].astype(F32), eps)


def logits(params, hidden_states):
    """Logits of hidden states [..., hidden] through the untied head."""
    return hidden_states @ params["lm_head"]["kernel"].astype(F32)
