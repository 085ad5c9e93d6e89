"""Plain reference of the ``deepseek_v3`` family (kakaocorp/kanana-2-30b-
a3b-instruct-2601): the forward pass in straightforward float32
``jax.numpy`` — no kernels, no cache, no latent pool, no absorbed
matrices, no grouped matmul, nothing imported from the program under
test.  The callers run it under ``jax.default_matmul_precision
("highest")``; the weights are the program's own, upcast one block at a
time.

Pre-norm residual: ``x <- x + Attn(RMSNorm(x))``, ``x <- x +
FFN_i(RMSNorm(x))`` (eps ``eps``); then the final RMSNorm and the untied
head.

Attention, the PER-HEAD (published) form, so that the comparison also
checks the program's absorbed one: ``q = u W_q`` as ``heads`` x (``nope``
+ ``rope``), split ``q_nope | q_rope``.  ``[c | k_r] = u W_kva`` (``rank``
| ``rope``); ``c <- RMSNorm(c)`` with its own weight and the same eps.
Rotary at base ``theta`` on ``k_r`` (ONE key a token, shared by all
heads) and on every head's ``q_rope``: on pairs (2i, 2i + 1) where
``interleave`` is true, else on pairs (i, i + rope / 2).  ``[k_nope,h |
v_h] = c W_kvb`` as ``heads`` x (``nope`` | ``v_dim``).  ``s_ij,h =
(q_nope,i,h . k_nope,j,h + q_rope,i,h . k_r,j) / sqrt(nope + rope)`` for
``j <= i``; softmax; ``o_i,h = sum_j p_ij,h v_j,h``; output ``concat_h(o)
W_o``.  Scores are computed for a block of query rows at a time.

Feed-forward: layer ``i < first_dense`` is ``W_down(silu(W_gate u) * W_up
u)``.  Every later layer is routed: ``s = sigmoid(u W_r)`` over all the
router's experts, the ``per_token`` largest of ``s + bias`` chosen,
weights ``s[chosen] / (sum + 1e-20) x scaling``, each chosen expert the
same SwiGLU (its gate and up matrices side by side in ``w_up``), as a
loop over the experts HELD here (``first_held .. + held``) with a mask —
what the absent experts would add is left out — PLUS one shared SwiGLU
MLP on every token, unweighted.
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


def rotary(x, pos, theta, interleave):
    """Rotary over the whole last dim of x [t, h, d]: feature 2i pairs
    with 2i + 1 (``interleave``) or feature i with i + d / 2."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q, k, v):
    """Causal attention of q [t, h, d] over k [t, h, d], v [t, h, dv]
    -> [t, h, dv], a block of query rows at a time."""
    t, h, d = q.shape
    k_pos = jnp.arange(t)

    def block(args):
        qb, q_pos = args                          # [blk, h, d], [blk]
        s = jnp.einsum("qhd,thd->hqt", qb, k) / jnp.sqrt(F32(d))
        s = jnp.where((k_pos[None, :] <= q_pos[:, None])[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqt,thd->qhd", p, v)
    n = -(-t // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - t
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n, QUERY_BLOCK, h, d)
    # a padding query sits at the last position: it sees keys, is unused
    pos = jnp.minimum(jnp.arange(n * QUERY_BLOCK), t - 1) \
        .reshape(n, QUERY_BLOCK)
    return jax.lax.map(block, (qp, pos)).reshape(-1, h, v.shape[-1])[:t]


def attention_layer(u, w, *, heads, rank, nope, rope, v_dim, theta,
                    interleave, eps, drop=(), **_):
    t = u.shape[0]
    q = (u @ w["wq"]).reshape(t, heads, nope + rope)
    ckr = u @ w["wkv_a"]
    c, k_r = ckr[:, :rank], ckr[:, None, rank:]
    if "latent_norm" not in drop:
        c = rms_norm(c, w["kv_a_norm"], eps)
    pos = jnp.arange(t)
    q_rope = rotary(q[..., nope:], pos, F32(theta), interleave)
    if "key_rope" not in drop:
        k_r = rotary(k_r, pos, F32(theta), interleave)
    kv = jnp.einsum("tr,rhd->thd", c, w["wkv_b"])
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (t, heads, rope))], -1)
    o = attention(jnp.concatenate([q[..., :nope], q_rope], -1), k,
                  kv[..., nope:])
    return o.reshape(t, heads * v_dim) @ w["wo"]


def swiglu(u, w_gate_up, w_down):
    inter = w_down.shape[0]
    h = u @ w_gate_up
    return (jax.nn.silu(h[:, :inter]) * h[:, inter:]) @ w_down


def mlp(r, w, prefix=""):
    return (jax.nn.silu(r @ w[prefix + "w_gate"]) * (r @ w[prefix + "w_up"])) \
        @ w[prefix + "w_down"]


def dense_ffn(u, w, **_):
    return in_blocks(lambda r: mlp(r, w), u, TOKEN_BLOCK)


def routed_ffn(u, w, *, per_token, scaling, first_held, drop=(), **_):
    def rows(r):
        s = jax.nn.sigmoid(r @ w["router"])
        choose = s if "score_bias" in drop else s + w["bias"]
        _, chosen = jax.lax.top_k(choose, per_token)
        wts = jnp.take_along_axis(s, chosen, axis=-1)
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20)
        if "routed_scale" not in drop:
            wts = wts * scaling
        out = jnp.zeros_like(r)
        for e in range(w["w_up"].shape[0]):
            gate = jnp.sum(jnp.where(chosen == first_held + e, wts, 0.0), -1)
            out = out + gate[:, None] * swiglu(r, w["w_up"][e],
                                               w["w_down"][e])
        if "shared_w_up" in w and "shared" not in drop:
            out = out + mlp(r, w, "shared_")
        return out
    return in_blocks(rows, u, TOKEN_BLOCK)


def layer_weights(params, i, routed):
    """Block i's weights from the program's parameter tree, float32."""
    p = params[f"layers_{i}"]
    a = p["attn"]
    w = {n: a[n]["kernel"] for n in ("wq", "wkv_a", "wo")}
    w["wkv_b"] = a["wkv_b"]
    w["kv_a_norm"] = a["kv_a_norm"]["scale"]
    if routed:
        m = p["moe"]
        w.update(router=m["router"], bias=m["e_score_correction_bias"],
                 w_up=m["w_up"], w_down=m["w_down"])
        if "shared" in m:
            w.update({"shared_" + n: m["shared"][n]["kernel"]
                      for n in ("w_gate", "w_up", "w_down")})
    else:
        w.update({n: p["mlp"][n]["kernel"]
                  for n in ("w_gate", "w_up", "w_down")})
    w["input_norm"] = p["input_norm"]["scale"]
    w["pre_ff_norm"] = p["pre_ff_norm"]["scale"]
    return jax.tree.map(lambda x: jnp.asarray(x).astype(F32), w)


def hidden(params, ids, *, layers, first_dense, eps, **sizes):
    """Final-norm hidden states [b, t, hidden] of token ids [b, t]; one
    jitted program a KIND of block, one row at a time."""
    x = params["embed_tokens"].astype(F32)[ids]

    def block(routed):
        ffn = routed_ffn if routed else dense_ffn

        def one(r, w):
            r = r + attention_layer(rms_norm(r, w["input_norm"], eps), w,
                                    eps=eps, **sizes)
            return r + ffn(rms_norm(r, w["pre_ff_norm"], eps), w, **sizes)
        return jax.jit(lambda x, w: jax.lax.map(lambda r: one(r, w), x))
    blocks = {routed: block(routed) for routed in (False, True)}
    for i in range(layers):
        routed = i >= first_dense
        x = blocks[routed](x, layer_weights(params, i, routed))
    return rms_norm(x, params["norm_f"]["scale"].astype(F32), eps)


def logits(params, hidden_states):
    """Logits of hidden states [..., hidden] through the untied head."""
    return hidden_states @ params["lm_head"]["kernel"].astype(F32)
