"""Plain references: each configuration family's forward pass in
straightforward float32 ``jax.numpy`` — no kernels, no cache, no
batching tricks, nothing imported from the program under test.

They follow the published descriptions (Mistral/Llama family: RMSNorm,
rotary embeddings in the rotate-half convention with the config's base,
grouped-query causal attention, SwiGLU, untied head; GPT-2: learned
positions, pre-LayerNorm, fused qkv with biases, tanh-approximated GELU,
tied head).  The callers upcast the program's own weights one layer at
a time, and run under ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul is otherwise done in bf16 passes.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rotary(x, base):
    """x [t, heads, d], positions 0..t-1, rotate-half pairs (i, i+d/2)."""
    t, _, d = x.shape
    half = d // 2
    freq = 1.0 / (base ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, q_block=512):
    """q [t, h, d], k/v [t, kv_h, d] -> [t, h, d]; query head i reads
    kv head i // (h / kv_h).  Scores are formed for ``q_block`` query
    rows at a time so a 4k context never holds a [h, t, t] tensor."""
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


def llama_layer(x, w, *, heads, kv_heads, rope_base, eps):
    """One block on x [t, hidden]; ``w`` holds float32 wq wk wv wo
    w_gate w_up w_down (each [in, out]) and the two norm scales."""
    t, hid = x.shape
    d = hid // heads
    a = rms_norm(x, w["input_norm"], eps)
    q = rotary((a @ w["wq"]).reshape(t, heads, d), rope_base)
    k = rotary((a @ w["wk"]).reshape(t, kv_heads, d), rope_base)
    v = (a @ w["wv"]).reshape(t, kv_heads, d)
    x = x + causal_attention(q, k, v).reshape(t, hid) @ w["wo"]
    m = rms_norm(x, w["post_attn_norm"], eps)
    return x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


def llama_layer_weights(params, i):
    """Layer i's weights from the program's parameter tree, float32."""
    p = params[f"layers_{i}"]
    w = {n: p["attn"][n]["kernel"] for n in ("wq", "wk", "wv", "wo")}
    w.update({n: p["mlp"][n]["kernel"] for n in ("w_gate", "w_up",
                                                 "w_down")})
    w["input_norm"] = p["input_norm"]["scale"]
    w["post_attn_norm"] = p["post_attn_norm"]["scale"]
    return jax.tree.map(lambda a: a.astype(F32), w)


def llama_hidden(params, ids, *, layers, heads, kv_heads, rope_base, eps):
    """Final-norm hidden states [b, t, hidden] of token ids [b, t], the
    weights upcast one layer at a time."""
    x = params["embed_tokens"].astype(F32)[ids]
    layer = jax.jit(
        lambda x, w: jax.lax.map(
            lambda r: llama_layer(r, w, heads=heads, kv_heads=kv_heads,
                                  rope_base=rope_base, eps=eps), x))
    for i in range(layers):
        x = layer(x, llama_layer_weights(params, i))
    return rms_norm(x, params["norm"]["scale"].astype(F32), eps)


def llama_logits(params, hidden):
    """Logits of hidden states [..., hidden] through the untied head."""
    return hidden @ params["lm_head"]["kernel"].astype(F32)


def gpt2_layer(x, w, *, heads, eps):
    t, hid = x.shape
    d = hid // heads
    a = layer_norm(x, w["ln_1_s"], w["ln_1_b"], eps)
    q, k, v = jnp.split(a @ w["qkv"] + w["qkv_b"], 3, axis=-1)
    o = causal_attention(q.reshape(t, heads, d), k.reshape(t, heads, d),
                         v.reshape(t, heads, d)).reshape(t, hid)
    x = x + o @ w["proj"] + w["proj_b"]
    m = layer_norm(x, w["ln_2_s"], w["ln_2_b"], eps)
    m = jax.nn.gelu(m @ w["fc_in"] + w["fc_in_b"], approximate=True)
    return x + m @ w["fc_out"] + w["fc_out_b"]


def gpt2_layer_weights(params, i):
    p = params[f"h_{i}"]
    w = {"qkv": p["attn"]["qkv"]["kernel"], "qkv_b": p["attn"]["qkv"]["bias"],
         "proj": p["attn"]["proj"]["kernel"],
         "proj_b": p["attn"]["proj"]["bias"],
         "fc_in": p["mlp"]["fc_in"]["kernel"],
         "fc_in_b": p["mlp"]["fc_in"]["bias"],
         "fc_out": p["mlp"]["fc_out"]["kernel"],
         "fc_out_b": p["mlp"]["fc_out"]["bias"],
         "ln_1_s": p["ln_1"]["scale"], "ln_1_b": p["ln_1"]["bias"],
         "ln_2_s": p["ln_2"]["scale"], "ln_2_b": p["ln_2"]["bias"]}
    return jax.tree.map(lambda a: a.astype(F32), w)


def gpt2_hidden(params, ids, *, layers, heads, eps):
    """Final-LayerNorm hidden states [b, t, hidden] of token ids [b, t]."""
    t = ids.shape[1]
    x = params["wte"].astype(F32)[ids] + params["wpe"].astype(F32)[:t][None]
    layer = jax.jit(lambda x, w: jax.lax.map(
        lambda r: gpt2_layer(r, w, heads=heads, eps=eps), x))
    for i in range(layers):
        x = layer(x, gpt2_layer_weights(params, i))
    return layer_norm(x, params["ln_f"]["scale"].astype(F32),
                      params["ln_f"]["bias"].astype(F32), eps)


def gpt2_logits(params, ids, **kw):
    """Logits [b, t, vocab] of token ids [b, t] (tied head)."""
    return gpt2_hidden(params, ids, **kw) @ params["wte"].astype(F32).T


def gpt2_loss(params, ids, **kw):
    """Mean next-token cross-entropy of ids [b, t]; the logits are
    formed one sequence at a time, so a batch of 1024-token sequences
    never holds a [b, t, 50257] float32 tensor."""
    wte = params["wte"].astype(F32)
    hidden = gpt2_hidden(params, ids, **kw)
    per_seq = jax.lax.map(
        lambda hx: next_token_loss((hx[0] @ wte.T)[None], hx[1][None]),
        (hidden, ids))
    return jnp.mean(per_seq)


def next_token_loss(logits, ids):
    """Mean next-token cross-entropy of ids [b, t] under logits
    [b, t, vocab] (position i predicts token i + 1)."""
    lp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    ll = jnp.take_along_axis(lp, ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(ll)
