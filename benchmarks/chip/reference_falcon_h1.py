"""Plain reference of the ``falcon_h1`` family (Falcon-H1-34B-Instruct):
the forward pass in straightforward float32 ``jax.numpy`` — no kernels,
no cache, no chunked scan, nothing imported from the program under
test.  The callers run it under
``jax.default_matmul_precision("highest")``; the weights are the
program's own, upcast one layer at a time.

With ``x`` the residual stream, every norm an RMSNorm with ``eps``::

    x = embed[ids] * embedding_multiplier
    per block:
      u = RMSNorm_in(x)
      a = Attn(u * attention_in_multiplier) * attention_out_multiplier
      m = Mamba2(u * ssm_in_multiplier)     * ssm_out_multiplier
      x = x + a + m
      v = RMSNorm_ff(x)
      x = x + down(silu(gate(v) * mlp_multipliers[0]) * up(v))
              * mlp_multipliers[1]
    logits = lm_head(RMSNorm_f(x)) * lm_head_multiplier      (head untied)

Attn: ``q, k, v = Wq u', Wk u', Wv u'`` without bias; ``k = k *
key_multiplier``; rotary over the whole head dim in the rotate-half
convention at ``rope_theta``; grouped-query causal softmax attention
(query head i reads kv head i // (heads / kv_heads)); ``Wo``.

Mamba2: ``[z | x | B | C | dt] = (W_in u') * mup_vector``, where
``mup_vector`` lays ``ssm_multipliers[0..4]`` over those five segments;
``[x | B | C] = silu(causal depthwise conv_k([x | B | C]) + b)``;
``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; the recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t . C_t +
D x_t`` as a ``lax.scan`` over time from a zero state (head h reads
group h // (heads / groups)); ``y = RMSNorm_grouped(y * silu(z))`` over
``groups`` groups with a learned scale; ``W_out``.

Departures and assumptions, each also under ``assumed`` in the
configuration's file: the segment order of ``ssm_multipliers`` and the
rotate-half rotary are recalled, not read (no network); dt is not
clamped (the published limit is (0, inf)); the harness calls
``logits(params, rows)`` with no further argument, so ``hidden``
returns the final-norm hidden states ALREADY times
``lm_head_multiplier`` — the head is linear, ``lm_head(h) * m ==
lm_head(h * m)``, and the multiplier (2**-7) is exact in any precision.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotary(x, theta):
    """x [t, h, d] at positions 0..t-1: pairs (x[i], x[i + d/2])."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = 1.0 / (float(theta) ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, q_block=512):
    """q [t, h, d], k/v [t, kv_h, d] -> [t, h, d]; scores for
    ``q_block`` query rows at a time."""
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


def attention_mixer(u, w, *, heads, kv_heads, head_dim, theta,
                    key_multiplier, **_):
    t = u.shape[0]
    q = (u @ w["wq"]).reshape(t, heads, head_dim)
    k = ((u @ w["wk"]) * key_multiplier).reshape(t, kv_heads, head_dim)
    v = (u @ w["wv"]).reshape(t, kv_heads, head_dim)
    out = causal_attention(rotary(q, theta), rotary(k, theta), v)
    return out.reshape(t, heads * head_dim) @ w["wo"]


def mamba_mixer(u, w, *, mamba_heads, mamba_head_dim, groups, state, eps,
                ssm_multipliers, **_):
    t = u.shape[0]
    inner, gn = mamba_heads * mamba_head_dim, groups * state
    widths = (inner, inner, gn, gn, mamba_heads)
    mup_vector = jnp.concatenate([jnp.full((n,), m, F32) for n, m in
                                  zip(widths, ssm_multipliers)])
    zxbcdt = (u @ w["in_proj"]) * mup_vector
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


def block(x, w, *, eps, attention_in_multiplier, attention_out_multiplier,
          ssm_in_multiplier, ssm_out_multiplier, mlp_multipliers, **sizes):
    """One block over one sequence x [t, hidden]."""
    u = rms_norm(x, w["input_norm"], eps)
    a = attention_mixer(u * attention_in_multiplier, w, **sizes)
    m = mamba_mixer(u * ssm_in_multiplier, w, eps=eps, **sizes)
    x = x + a * attention_out_multiplier + m * ssm_out_multiplier
    v = rms_norm(x, w["pre_ff_norm"], eps)
    gate = jax.nn.silu((v @ w["w_gate"]) * mlp_multipliers[0])
    return x + ((gate * (v @ w["w_up"])) @ w["w_down"]) * mlp_multipliers[1]


def layer_weights(params, i):
    """Block i's weights from the program's parameter tree, float32."""
    p = params[f"layers_{i}"]
    m = p["mamba"]
    w = {n: m[n] for n in ("conv_w", "conv_b", "dt_bias", "A_log", "D",
                           "norm")}
    w.update(in_proj=m["in_proj"]["kernel"],
             out_proj=m["out_proj"]["kernel"])
    w.update({n: p["attn"][n]["kernel"] for n in ("wq", "wk", "wv", "wo")})
    w.update({n: p["mlp"][n]["kernel"]
              for n in ("w_gate", "w_up", "w_down")})
    w.update(input_norm=p["input_norm"]["scale"],
             pre_ff_norm=p["pre_ff_norm"]["scale"])
    return jax.tree.map(lambda a: jnp.asarray(a).astype(F32), w)


def hidden(params, ids, *, layers, eps, embedding_multiplier,
           lm_head_multiplier, **args):
    """Final-norm hidden states [b, t, hidden] of token ids [b, t],
    times ``lm_head_multiplier`` (the module's docstring says why); one
    jitted program for every block, one row at a time."""
    x = params["embed_tokens"].astype(F32)[ids] * embedding_multiplier
    args = {k: tuple(v) if isinstance(v, list) else v
            for k, v in args.items()}
    one_block = jax.jit(lambda x, w: jax.lax.map(
        lambda r: block(r, w, eps=eps, **args), x))
    for i in range(layers):
        x = one_block(x, layer_weights(params, i))
    return rms_norm(x, params["norm_f"]["scale"].astype(F32), eps) * \
        lm_head_multiplier


def logits(params, hidden_states):
    """Logits of hidden states [..., hidden] (``hidden``'s, already
    times the head's multiplier) through the untied head."""
    return hidden_states @ params["lm_head"]["kernel"].astype(F32)
