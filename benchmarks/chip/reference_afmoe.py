"""Plain reference of the ``afmoe`` family (arcee-ai Trinity): the
forward pass in straightforward float32 ``jax.numpy`` -- no kernels, no
cache, no ring, no grouped matmul, nothing imported from the program
under test.  The callers run it under
``jax.default_matmul_precision("highest")``; the weights are the
program's own, upcast one block at a time.

``x = embed[ids] * sqrt(hidden)``; then per block, with four RMSNorms
(eps ``eps``): ``h = x + PostAttnNorm(Attn_i(InputNorm(x)))``, ``x = h +
PostFfNorm(FFN_i(PreFfNorm(h)))``; then the final RMSNorm and the untied
head.

Attention, by ``layer_types[i]``: ``q`` and the gate ``g`` as ``heads``
x ``head_dim``, ``k`` and ``v`` as ``kv_heads`` x ``head_dim``; q and k
RMS-normed over ``head_dim`` (one weight each, shared by the heads).  A
``"sliding_attention"`` layer rotates all ``head_dim`` features of q and
k in the half-rotation convention at base ``theta`` and scores ``q_i .
k_j / sqrt(head_dim)`` for ``i - window < j <= i``; a
``"full_attention"`` layer rotates nothing and scores ``j <= i``.  Plain
softmax, ``o_i = sum_j p_ij v_j``, output ``(concat_h(o) * sigmoid(g))
W_o``.  Scores are computed for a block of query rows at a time, over
all ``t`` keys under the mask.

Feed-forward: block ``i < dense_layers`` is ``W_down(silu(W_gate u) *
W_up u)``; the others ``Shared(u) + sum_k w_k Expert_k(u)`` with ``s =
sigmoid(u W_r)`` over all the router's experts, the ``per_token``
largest of ``s + bias`` chosen, ``w = s[chosen] / (sum + 1e-20) x
scale``, each expert a SwiGLU (its gate and up matrices side by side in
``w_up``), as a loop over the experts HELD here (``first_held .. +
held``, ``held`` those the tree carries) with a mask; what the absent
experts would add is left out, the shared expert is counted whole.

Departures from the published description, each the configuration
file's ``assumed``: the output gate's form, the per-head norms and the
rotary-in-window-layers-only rule are the family's modelling code, not
keys of ``config.json``; ``expert_bias`` is the program's parameter
(zeros at a seeded init); the "depth-scaled" start of the post-norms'
gains is the program's init and not a term here (the gains are the
tree's).
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOKEN_BLOCK = 2048      # feed-forward rows at a time
QUERY_BLOCK = 128       # attention query rows at a time
WINDOW = "sliding_attention"


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


def rotary(x, pos, theta):
    """Half-rotation rotary on all features of x [t, h, d]: feature i
    pairs with feature i + d / 2."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q, k, v, window):
    """q [t, h, d], k, v [t, kv, d] -> [t, h, d]; query head i reads kv
    head i // (h / kv).  ``window`` 0 is full causal attention."""
    t, h, d = q.shape
    kv = k.shape[1]
    g = h // kv
    k_pos = jnp.arange(t)

    def block(args):
        qb, q_pos = args                          # [blk, h, d], [blk]
        s = jnp.einsum("qkgd,tkd->kgqt", qb.reshape(-1, kv, g, d), k) \
            / jnp.sqrt(F32(d))
        mask = k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v).reshape(-1, h, d)
    n = -(-t // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - t
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n, QUERY_BLOCK, h, d)
    # a padding query sits at the last position: it sees keys, is unused
    pos = jnp.minimum(jnp.arange(n * QUERY_BLOCK), t - 1) \
        .reshape(n, QUERY_BLOCK)
    return jax.lax.map(block, (qp, pos)).reshape(-1, h, d)[:t]


def attention_layer(u, w, kind, *, heads, kv_heads, head_dim, theta, window,
                    eps, drop=(), **_):
    """``drop`` names pieces to leave out or get wrong (the unit tests
    show that the comparison sees each)."""
    t = u.shape[0]
    q = (u @ w["wq"]).reshape(t, heads, head_dim)
    k = (u @ w["wk"]).reshape(t, kv_heads, head_dim)
    v = (u @ w["wv"]).reshape(t, kv_heads, head_dim)
    if "q_norm" not in drop:
        q = rms_norm(q, w["q_norm"], eps)
    if "k_norm" not in drop:
        k = rms_norm(k, w["k_norm"], eps)
    sliding = kind == WINDOW
    if (sliding and "window_rotary" not in drop) or \
            (not sliding and "full_rotary" in drop):
        pos = jnp.arange(t)
        q, k = rotary(q, pos, F32(theta)), rotary(k, pos, F32(theta))
    win = window + 1 if "window_edge" in drop else window
    o = attention(q, k, v, win if sliding else 0).reshape(t, -1)
    if "gate" not in drop:
        o = o * jax.nn.sigmoid(u @ w["wg"])
    return o @ w["wo"]


def swiglu(u, w_gate_up, w_down):
    inter = w_down.shape[0]
    h = u @ w_gate_up
    return (jax.nn.silu(h[:, :inter]) * h[:, inter:]) @ w_down


def mlp(u, w):
    return in_blocks(
        lambda r: (jax.nn.silu(r @ w["w_gate"]) * (r @ w["w_up"]))
        @ w["w_down"], u, TOKEN_BLOCK)


def dense_ffn(u, w, **_):
    return mlp(u, w)


def routed_ffn(u, w, *, per_token, scale, first_held, held=None, drop=(),
               **_):
    held = w["w_up"].shape[0] if held is None else held
    scale = 1.0 if "route_scale" in drop else scale

    def rows(r):
        s = jax.nn.sigmoid(r @ w["router"])
        choose = s if "expert_bias" in drop else s + w["bias"]
        _, chosen = jax.lax.top_k(choose, per_token)
        wts = jnp.take_along_axis(s, chosen, axis=-1)
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20) * scale
        out = jnp.zeros_like(r)
        for e in range(held):
            gate = jnp.sum(jnp.where(chosen == first_held + e, wts, 0.0), -1)
            out = out + gate[:, None] * swiglu(r, w["w_up"][e],
                                               w["w_down"][e])
        return out
    out = in_blocks(rows, u, TOKEN_BLOCK)
    if "shared" not in drop and "shared" in w:
        out = out + mlp(u, w["shared"])
    return out


def layer_weights(params, i, kind, routed):
    """Block i's weights from the program's parameter tree, float32."""
    p = params[f"layers_{i}"]
    a = p["swa" if kind == WINDOW else "attn"]
    w = {n: a[n]["kernel"] for n in ("wq", "wk", "wv", "wg", "wo")}
    w.update(q_norm=a["q_norm"]["scale"], k_norm=a["k_norm"]["scale"])

    def kernels(m):
        return {n: m[n]["kernel"] for n in ("w_gate", "w_up", "w_down")}
    if routed:
        m = p["moe"]
        w.update(router=m["router"], bias=m["expert_bias"],
                 w_up=m["w_up"], w_down=m["w_down"])
        if "shared" in m:
            w["shared"] = kernels(m["shared"])
    else:
        w.update(kernels(p["mlp"]))
    for n in ("input_norm", "post_attn_norm", "pre_ff_norm",
              "post_ff_norm"):
        w[n] = p[n]["scale"]
    return jax.tree.map(lambda x: jnp.asarray(x).astype(F32), w)


def hidden(params, ids, *, layer_types, dense_layers, eps, drop=(),
           **sizes):
    """Final-norm hidden states [b, t, hidden] of token ids [b, t]; one
    jitted program a KIND of block, one row at a time."""
    embed = params["embed_tokens"].astype(F32)
    x = embed[ids]
    if "embed_scale" not in drop:
        x = x * jnp.sqrt(F32(embed.shape[1]))

    def block(kind, routed):
        ffn = routed_ffn if routed else dense_ffn

        def one(r, w):
            a = attention_layer(rms_norm(r, w["input_norm"], eps), w, kind,
                                eps=eps, drop=drop, **sizes)
            if "post_attn_norm" not in drop:
                a = rms_norm(a, w["post_attn_norm"], eps)
            r = r + a
            f = ffn(rms_norm(r, w["pre_ff_norm"], eps), w, drop=drop,
                    **sizes)
            if "post_ff_norm" not in drop:
                f = rms_norm(f, w["post_ff_norm"], eps)
            return r + f
        return jax.jit(lambda x, w: jax.lax.map(lambda r: one(r, w), x))
    kinds = [(kind, i >= dense_layers) for i, kind in enumerate(layer_types)]
    blocks = {k: block(*k) for k in set(kinds)}
    for i, k in enumerate(kinds):
        x = blocks[k](x, layer_weights(params, i, *k))
    return rms_norm(x, params["norm_f"]["scale"].astype(F32), eps)


def logits(params, hidden_states):
    """Logits of hidden states [..., hidden] through the untied head."""
    return hidden_states @ params["lm_head"]["kernel"].astype(F32)
