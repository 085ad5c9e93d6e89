"""Plain float32 reference of a family ``reference.py`` does not hold:
OPT-style decoder blocks (learned positions stored at index + 2,
pre-LayerNorm, fused qkv with biases, ReLU feed-forward, tied head).
Test-local: the tests drop this file into a copy of the harness and a
configuration names it as ``"reference_opt:<function>"``.  Nothing is
imported from the program under test or from the harness."""

import jax
import jax.numpy as jnp

F32 = jnp.float32
POS_OFFSET = 2
ACTIVATIONS = {"relu": jax.nn.relu}


def layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def block(x, p, heads, eps, act):
    t, hid = x.shape
    d = hid // heads
    q, k, v = (a.reshape(t, heads, d) for a in jnp.split(
        dense(layer_norm(x, p["ln_1"], eps), p["attn"]["qkv"]), 3, -1))
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    sc = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    x = x + dense(o.reshape(t, hid), p["attn"]["proj"])
    m = act(dense(layer_norm(x, p["ln_2"], eps), p["mlp"]["fc_in"]))
    return x + dense(m, p["mlp"]["fc_out"])


def hidden(params, ids, *, layer_types, heads, eps, activation):
    """Final-LayerNorm hidden states [b, t, hidden] of ids [b, t]; one
    block per entry of ``layer_types`` (all "full" attention here)."""
    assert set(layer_types) == {"full"}
    p = jax.tree.map(lambda a: a.astype(F32), params)
    t = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][POS_OFFSET:POS_OFFSET + t][None]
    for i in range(len(layer_types)):
        x = jax.vmap(lambda r: block(r, p[f"h_{i}"], heads, eps,
                                     ACTIVATIONS[activation]))(x)
    return layer_norm(x, p["ln_f"], eps)


def logits(params, rows):
    """Logits of hidden states [..., hidden] through the tied head."""
    return rows @ params["wte"].astype(F32).T


def loss(params, ids, **kw):
    """Mean next-token cross-entropy of ids [b, t]."""
    lp = jax.nn.log_softmax(logits(params, hidden(params, ids, **kw)), -1)
    return -jnp.mean(jnp.take_along_axis(lp[:, :-1], ids[:, 1:, None], -1))
