"""Dropless top-k routing over a HELD share of the experts.

The layer expert parallelism asks for, on the chip that holds experts
``first .. first + held - 1`` of ``num_experts``: the router scores ALL
experts and chooses ``k`` of them a token; this chip computes its own
experts' part of the result for the (token, choice) pairs that landed
on them; what the absent experts would have added is left out — that
is the other chips' part of the sum, and nothing here stands in for
them or for their exchange.  ``moe/sharded_moe.py`` keeps the
capacity-factor top-1/top-2 gate that ``models/gpt2.py`` trains with.

No pair is dropped whatever the imbalance, in either of the two forms
the same sum takes (chosen by the token count, which is static at
trace time):

* over ``DENSE_MAX_TOKENS`` tokens (prefill) the pairs are sorted by
  expert and ONE grouped matmul pair (``jax.lax.ragged_dot``) runs over
  the groups, with the static row bound tokens x k; the rows past the
  held pairs belong to no group and cost nothing;
* at or under it (decode) every held expert is computed on every token
  by one batched matmul pair that reads each expert's weights once, and
  the combine keeps the pairs the router chose.
"""

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32

# The dense form costs t x held x 4 x hidden x inter FLOPs whichever
# tokens chose what, and reads the held x 4 x hidden x inter bytes of
# bf16 weights once; FLOPs and bytes meet at t = peak FLOP/s / bytes/s
# whatever the widths: 197e12 / 819e9 = 240 tokens on a TPU v5e.  Under
# that the weight read is the cost and the dense form is the cheapest
# correct program.  The scheduler dispatches 32, 128, 512 and 2,048
# tokens; 256 is the power of two beside the crossover.  Measured on
# the chip at 16 x 2688 x 1856 (PERF.md section 6, PR 36): one layer
# call at 128 tokens takes 0.43 ms dense (the weights' 0.39 ms at the
# memory's rate) against 3.5 ms for XLA's grouped matmul pair, which is
# priced by the group touched (256-row tiles), not by the row.
DENSE_MAX_TOKENS = 256


def dense_form(tokens):
    """Whether a call over ``tokens`` tokens takes the dense-over-held
    form: a function of the shape alone."""
    return tokens <= DENSE_MAX_TOKENS


def sigmoid_topk_router(x, router_w, score_bias, k, scale, normalize=True):
    """DeepSeek-V3-style router (no expert groups).  ``x`` [t, hidden],
    ``router_w`` [hidden, experts], ``score_bias`` [experts].  Scores
    ``s = sigmoid(x @ W)`` in float32; the ``k`` largest of ``s + bias``
    are chosen (the bias takes part in the CHOICE only); weights
    ``s[chosen] / (sum + 1e-20) * scale``.  Returns (chosen [t, k]
    int32, weights [t, k] float32)."""
    with jax.named_scope("router"):
        s = jax.nn.sigmoid(jnp.dot(x.astype(F32), router_w.astype(F32),
                                   preferred_element_type=F32))
        _, chosen = lax.top_k(s + score_bias.astype(F32), k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if normalize:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), w * scale


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def swiglu(x):
    """A gated expert through the one ``activation`` seam: ``w_up``
    holds gate and up side by side, [held, hidden, 2 x inter], and the
    activation of the packed product is ``silu(gate) * up`` at ``inter``
    — so ``held_experts_ffn`` is the same two matmuls for either kind
    of expert."""
    inter = x.shape[-1] // 2
    return jax.nn.silu(x[..., :inter]) * x[..., inter:]


def held_experts_ffn(x, chosen, weights, w_up, w_down, first, live=None,
                     activation=relu2):
    """The held experts' part of ``sum_k w_k * down_k(act(up_k(x)))``.

    ``x`` [t, hidden]; ``chosen``/``weights`` [t, k] from the router
    over all experts; ``w_up`` [held, hidden, inter], ``w_down`` [held,
    inter, hidden] are experts ``first ..`` (``w_up`` twice as wide
    under :func:`swiglu`); ``live`` [t] bool marks
    the tokens that exist (padding columns and idle slots route
    nowhere).  Returns (out [t, hidden] in x's dtype, group sizes
    [held] int32: the pairs each held expert computed)."""
    t, k = chosen.shape
    held = w_up.shape[0]
    local = chosen - first
    mine = (local >= 0) & (local < held)
    if live is not None:
        mine &= live[:, None]
    key = jnp.where(mine, local, held).reshape(t * k)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    w_up, w_down = w_up.astype(x.dtype), w_down.astype(x.dtype)
    if dense_form(t):
        with jax.named_scope("experts"):
            up = jnp.einsum("th,ehi->eti", x, w_up)
            down = jnp.einsum("eti,eih->eth", activation(up), w_down)
        # a token's k choices are distinct experts, so at most one term
        # of the sum over k: the weight of (token, held expert) where
        # the router chose it, 0 where it did not
        gate = jnp.sum(jnp.where(
            mine[..., None] & (local[..., None] == jnp.arange(held)),
            weights[..., None], 0.0), axis=1)
        out = jnp.sum(gate.T[..., None] * down.astype(F32), axis=0)
        return out.astype(x.dtype), sizes
    # pairs sorted by held expert; every other pair sorts to the end
    order = jnp.argsort(key, stable=True)
    with jax.named_scope("experts"):
        up = lax.ragged_dot(x[order // k], w_up, sizes)
        down = lax.ragged_dot(activation(up), w_down, sizes)
    # back in (token, choice) order the k parts of a token add up in
    # float32; a row outside every group was not written by the grouped
    # matmul, so it is masked, not multiplied by a zero weight
    down = down[jnp.argsort(order)].reshape(t, k, -1)
    out = jnp.sum(jnp.where(mine[..., None], down.astype(F32) *
                            weights[..., None], 0.0), axis=1)
    return out.astype(x.dtype), sizes


def routing_stats(chosen, sizes, live=None):
    """uint32 [5] of one layer call: (token, choice) pairs routed, pairs
    that landed on held experts, round(1024 x busiest held expert's
    pairs / mean) (0 with no held pair), 1 (the call), and 1 if the
    call took the dense form (``dense_form`` of its token count)."""
    t, k = chosen.shape
    n = t if live is None else jnp.sum(live)
    held = jnp.sum(sizes)
    ratio = jnp.where(held > 0, jnp.max(sizes) * sizes.shape[0] /
                      jnp.maximum(held, 1), 0.0)
    return jnp.stack([n * k, held, jnp.round(ratio * 1024), 1,
                      int(dense_form(t))]).astype(jnp.uint32)
