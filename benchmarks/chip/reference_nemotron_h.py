"""Plain reference of the ``nemotron_h`` family (NVIDIA-Nemotron-3-Nano):
the forward pass in straightforward float32 ``jax.numpy`` — no kernels,
no cache, no chunked scan, no grouped matmul, nothing imported from the
program under test.  The callers run it under
``jax.default_matmul_precision("highest")``; the weights are the
program's own, upcast one layer at a time.

Block i, by the i-th character of ``pattern``: ``x + mixer(RMSNorm(x))``
(eps ``eps``), one mixer a block; then ``norm_f`` and the untied head.

``M`` Mamba-2: ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(causal
depthwise conv_k(xBC) + b)``; split into x [heads, p], B, C [groups,
n]; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; the
recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t =
h_t . C_t + D x_t`` as a ``lax.scan`` over time from a zero state;
``y = RMSNorm_grouped(y * silu(z))`` over ``groups`` groups with a
learned scale; ``out_proj``.

``E`` routed feed-forward: ``s = sigmoid(W_r u)`` over all
``router_experts``; the ``per_token`` largest of ``s + bias`` are
chosen; weights ``s[chosen] / (sum + 1e-20) * scaling``; the routed part
``sum_k w_k down_k(relu(up_k u)^2)`` as a loop over the experts HELD
here (``first_held .. + held``) with a mask, what the absent experts
would add left out; plus the shared expert, unweighted.

``*`` attention: grouped-query causal softmax attention with an
explicit head size, no bias, no positional embedding.

Departures from the published description, each also under ``assumed``
in the configuration's file: the family applies no rotary embedding in
its attention layers (``rope_theta`` is unused); ``n_group`` = 1 and
``topk_group`` = 1 make the router's group step the identity, so it is
not written; dt is not clamped (the published limit is (0, inf)).
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def causal_attention(q, k, v, q_block=512):
    """q [t, h, d], k/v [t, kv_h, d] -> [t, h, d]; query head i reads
    kv head i // (h / kv_h), scores for ``q_block`` query rows at a
    time."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    out = []
    for s in range(0, t, q_block):
        e = min(t, s + q_block)
        sc = jnp.einsum("qhd,khd->hqk", q[s:e], k) / jnp.sqrt(F32(d))
        mask = jnp.arange(s, e)[:, None] >= jnp.arange(t)[None, :]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    return jnp.concatenate(out, 0)


def attention_mixer(u, w, *, heads, kv_heads, head_dim, **_):
    t = u.shape[0]
    q = (u @ w["wq"]).reshape(t, heads, head_dim)
    k = (u @ w["wk"]).reshape(t, kv_heads, head_dim)
    v = (u @ w["wv"]).reshape(t, kv_heads, head_dim)
    return causal_attention(q, k, v).reshape(t, heads * head_dim) @ w["wo"]


def mamba_mixer(u, w, *, mamba_heads, mamba_head_dim, groups, state, eps,
                **_):
    t = u.shape[0]
    inner, gn = mamba_heads * mamba_head_dim, groups * state
    zxbcdt = u @ w["in_proj"]
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * gn],
                  zxbcdt[:, 2 * inner + 2 * gn:])
    k = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc], 0)
    conv = sum(padded[j:j + t] * w["conv_w"][j] for j in range(k))
    act = jax.nn.silu(conv + w["conv_b"])
    x = act[:, :inner].reshape(t, mamba_heads, mamba_head_dim)
    rep = mamba_heads // groups
    b_mat = jnp.repeat(act[:, inner:inner + gn].reshape(t, groups, state),
                       rep, axis=1)
    c_mat = jnp.repeat(act[:, inner + gn:].reshape(t, groups, state),
                       rep, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t * a)[:, None, None] * h + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)
    _, y = jax.lax.scan(
        step, jnp.zeros((mamba_heads, mamba_head_dim, state), F32),
        (x, dt, b_mat, c_mat))
    y = (y + w["D"][:, None] * x).reshape(t, inner) * jax.nn.silu(z)
    y = y.reshape(t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return (y.reshape(t, inner) * w["norm"]) @ w["out_proj"]


def moe_mixer(u, w, *, per_token, scaling, first_held, **_):
    s = jax.nn.sigmoid(u @ w["router"])
    _, chosen = jax.lax.top_k(s + w["bias"], per_token)
    wts = jnp.take_along_axis(s, chosen, axis=-1)
    wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20) * scaling
    out = relu2(u @ w["shared_up"]) @ w["shared_down"]
    for e in range(w["w_up"].shape[0]):
        gate = jnp.sum(jnp.where(chosen == first_held + e, wts, 0.0), -1)
        out = out + gate[:, None] * (relu2(u @ w["w_up"][e]) @ w["w_down"][e])
    return out


MIXERS = {"M": ("mamba", mamba_mixer), "E": ("moe", moe_mixer),
          "*": ("attn", attention_mixer)}


def layer_weights(params, i, kind):
    """Block i's weights from the program's parameter tree, float32."""
    p = params[f"layers_{i}"]
    m = p[MIXERS[kind][0]]
    if kind == "M":
        w = {n: m[n] for n in ("conv_w", "conv_b", "dt_bias", "A_log", "D",
                               "norm")}
        w.update(in_proj=m["in_proj"]["kernel"],
                 out_proj=m["out_proj"]["kernel"])
    elif kind == "E":
        w = {"router": m["router"], "bias": m["e_score_correction_bias"],
             "w_up": m["w_up"], "w_down": m["w_down"],
             "shared_up": m["shared_up"]["kernel"],
             "shared_down": m["shared_down"]["kernel"]}
    else:
        w = {n: m[n]["kernel"] for n in ("wq", "wk", "wv", "wo")}
    w["block_norm"] = p["norm"]["scale"]
    return jax.tree.map(lambda a: jnp.asarray(a).astype(F32), w)


def hidden(params, ids, *, pattern, eps, **sizes):
    """Final-norm hidden states [b, t, hidden] of token ids [b, t]; one
    jitted program a KIND of block, one row at a time."""
    x = params["embed_tokens"].astype(F32)[ids]
    sizes = dict(sizes, eps=eps)

    def block(kind):
        mixer = MIXERS[kind][1]
        return jax.jit(lambda x, w: jax.lax.map(
            lambda r: r + mixer(rms_norm(r, w["block_norm"], eps), w,
                                **sizes), x))
    blocks = {kind: block(kind) for kind in set(pattern)}
    for i, kind in enumerate(pattern):
        x = blocks[kind](x, layer_weights(params, i, kind))
    return rms_norm(x, params["norm_f"]["scale"].astype(F32), eps)


def logits(params, hidden_states):
    """Logits of hidden states [..., hidden] through the untied head."""
    return hidden_states @ params["lm_head"]["kernel"].astype(F32)
