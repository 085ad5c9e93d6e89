"""Dropless top-k routing over a held share of the experts
(moe/held_experts.py, models/nemotron_h.NemotronMoE) against a
per-token loop and against the plain reference's uncut layer
(benchmarks/chip/reference_nemotron_h.py, loaded from there: the repo
has one plain reference, not two).

The experts' sum has two forms, chosen by the token count alone (at or
under ``held_experts.DENSE_MAX_TOKENS`` every held expert is computed
on every token; over it the pairs are sorted and grouped): each test of
the sum runs under both, by a token count on either side of the
constant or, where the same inputs must go through both, by moving the
constant.

float32 on the CPU; every tolerance is 1e-5 absolute on outputs of
order 0.1-1: float32 rounding of two small matmuls and a weighted sum.
bfloat16 activations miss it by two orders of magnitude
(``test_bfloat16_activations_would_fail``).
"""

import importlib.util
import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.nemotron_h import NemotronMoE, nemotron_h_tiny
from deepspeed_tpu.moe import held_experts

TOL = 1e-5
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_nemotron_h", os.path.join(
            REPO, "benchmarks", "chip", "reference_nemotron_h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
T, HID, INTER, E, K = 24, 32, 48, 16, 3
# a token count on either side of the constant: the form each runs
FORMS = {"dense": T, "grouped": held_experts.DENSE_MAX_TOKENS + 8}
form = pytest.mark.parametrize("t", FORMS.values(), ids=FORMS.keys())


def layer(seed=0, held=4, t=T):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"x": jax.random.normal(k[0], (t, HID)),
            "router": jax.random.normal(k[1], (HID, E)) * 0.5,
            "w_up": jax.random.normal(k[2], (held, HID, INTER)) * 0.2,
            "w_down": jax.random.normal(k[3], (held, INTER, HID)) * 0.2}


def route(p):
    return held_experts.sigmoid_topk_router(p["x"], p["router"],
                                            jnp.zeros(E), K, 2.5)


def per_token(x, chosen, weights, w_up, w_down, first):
    out = np.zeros(x.shape, np.float64)
    computed = 0
    for t in range(x.shape[0]):
        for e, w in zip(np.asarray(chosen[t]), np.asarray(weights[t])):
            if first <= e < first + w_up.shape[0]:
                h = np.maximum(np.asarray(x[t]) @ np.asarray(
                    w_up[e - first]), 0) ** 2
                out[t] += w * (h @ np.asarray(w_down[e - first]))
                computed += 1
    return out, computed


def test_the_forms_lie_on_either_side_of_the_constant():
    assert held_experts.dense_form(FORMS["dense"])
    assert held_experts.dense_form(held_experts.DENSE_MAX_TOKENS)
    assert not held_experts.dense_form(FORMS["grouped"])


@form
@pytest.mark.parametrize("first", [0, 4, 12])
def test_each_form_is_the_per_token_loop(first, t):
    p = layer(t=t)
    chosen, weights = route(p)
    out, sizes = held_experts.held_experts_ffn(
        p["x"], chosen, weights, p["w_up"], p["w_down"], first)
    want, computed = per_token(p["x"], chosen, weights, p["w_up"],
                               p["w_down"], first)
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)
    assert int(sizes.sum()) == computed > 0


@form
def test_bfloat16_activations_would_fail(t):
    p = layer(t=t)
    chosen, weights = route(p)
    low, _ = held_experts.held_experts_ffn(
        p["x"].astype(jnp.bfloat16), chosen, weights, p["w_up"],
        p["w_down"], 0)
    want, _ = per_token(p["x"], chosen, weights, p["w_up"], p["w_down"], 0)
    assert np.abs(np.asarray(low, np.float64) - want).max() > 20 * TOL


@form
def test_no_pair_is_dropped_when_every_token_chooses_one_expert(t):
    """Dropless: all the tokens on one held expert are all computed (a
    capacity-factor gate would keep ceil(t * k / experts) of them)."""
    p = layer(t=t)
    chosen = jnp.stack([jnp.full((t,), 5), jnp.full((t,), 14),
                        jnp.full((t,), 15)], 1).astype(jnp.int32)
    weights = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (t, K)))
    out, sizes = held_experts.held_experts_ffn(
        p["x"], chosen, weights, p["w_up"], p["w_down"], 4)
    want, computed = per_token(p["x"], chosen, weights, p["w_up"],
                               p["w_down"], 4)
    assert computed == t and list(np.asarray(sizes)) == [0, t, 0, 0]
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)


@form
def test_idle_tokens_route_nowhere(t):
    p = layer(t=t)
    chosen, weights = route(p)
    live = jnp.arange(t) % 3 != 0
    out, sizes = held_experts.held_experts_ffn(
        p["x"], chosen, weights, p["w_up"], p["w_down"], 0, live)
    want, _ = per_token(p["x"], chosen, weights, p["w_up"], p["w_down"], 0)
    np.testing.assert_allclose(out[live], want[np.asarray(live)], atol=TOL,
                               rtol=0)
    assert not np.asarray(out[~live]).any()
    stats = held_experts.routing_stats(chosen, sizes, live)
    assert int(stats[0]) == int(live.sum()) * K
    assert int(stats[1]) == int(sizes.sum()) and int(stats[3]) == 1
    ratio = float(sizes.max()) * 4 / float(sizes.sum())
    assert abs(int(stats[2]) / 1024 - ratio) < 1e-3


@form
def test_the_fifth_counter_says_which_form_ran(t):
    p = layer(t=t)
    chosen, weights = route(p)
    _, sizes = held_experts.held_experts_ffn(
        p["x"], chosen, weights, p["w_up"], p["w_down"], 0)
    stats = held_experts.routing_stats(chosen, sizes)
    assert stats.shape == (5,) and stats.dtype == jnp.uint32
    assert int(stats[4]) == int(t == FORMS["dense"])


def primitives(jaxpr):
    """Names of every primitive of a jaxpr, inner jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            names |= primitives(inner)
    return names


def test_the_form_follows_the_shape_alone():
    """128 tokens (a decode step over 128 slots) trace no grouped
    matmul, no sort and no gather; 512 (a prefill dispatch) trace the
    grouped pair.  Nothing but the token count differs."""
    def traced(t):
        p = layer(t=t)
        chosen, weights = route(p)
        return primitives(jax.make_jaxpr(
            lambda x, w: held_experts.held_experts_ffn(
                x, chosen, w, p["w_up"], p["w_down"], 0))(
                    p["x"], weights).jaxpr)
    small, large = traced(128), traced(512)
    assert not small & {"ragged_dot_general", "sort", "gather"}
    assert "dot_general" in small
    assert {"ragged_dot_general", "sort", "gather"} <= large


def forced(monkeypatch, limit, *args):
    monkeypatch.setattr(held_experts, "DENSE_MAX_TOKENS", limit)
    return held_experts.held_experts_ffn(*args)


def routed_case(case):
    """``layer(t=128)`` with its (chosen, weights, first, live)."""
    p = layer(t=128)
    chosen, weights = route(p)
    live = None
    if case == "live":
        live = jnp.arange(128) % 5 != 0
    elif case == "one_expert":
        chosen = jnp.stack([jnp.full((128,), 6), jnp.full((128,), 1),
                            jnp.full((128,), 15)], 1).astype(jnp.int32)
    elif case == "none_held":
        chosen = 8 + chosen % 8                  # experts 8..15 only
    return p, chosen, weights, 4, live


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["router", "live", "one_expert",
                                  "none_held"])
def test_both_forms_agree_on_the_same_inputs(monkeypatch, case, dtype):
    """The same 128 tokens through both forms: equal to float32
    rounding in float32, to bfloat16 rounding (2**-8 relative, a few
    roundings deep) in bfloat16, and the same group sizes."""
    p, chosen, weights, first, live = routed_case(case)
    args = (p["x"].astype(dtype), chosen, weights,
            p["w_up"].astype(dtype), p["w_down"].astype(dtype), first, live)
    dense, d_sizes = forced(monkeypatch, 1 << 30, *args)
    grouped, g_sizes = forced(monkeypatch, 0, *args)
    assert dense.dtype == grouped.dtype == dtype
    np.testing.assert_array_equal(d_sizes, g_sizes)
    dense, grouped = (np.asarray(a, np.float64) for a in (dense, grouped))
    scale = np.abs(grouped).max()
    if case == "none_held":
        assert int(d_sizes.sum()) == 0 and scale == 0
        assert not dense.any()
        return
    assert int(d_sizes.sum()) > 0 and scale > 0.1
    tol = TOL if dtype == jnp.float32 else 4 * 2.0 ** -8 * scale
    np.testing.assert_allclose(dense, grouped, atol=tol, rtol=0)
    if live is not None:
        assert not dense[~np.asarray(live)].any()


def test_the_bias_moves_the_choice_and_not_the_weights():
    p = layer()
    plain_c, plain_w = held_experts.sigmoid_topk_router(
        p["x"], p["router"], jnp.zeros(E), K, 2.5)
    bias = jnp.zeros(E).at[7].set(10.0)          # expert 7 always wins
    chosen, weights = held_experts.sigmoid_topk_router(
        p["x"], p["router"], bias, K, 2.5)
    assert (np.asarray(chosen) == 7).any(1).all()
    assert not (np.asarray(plain_c) == 7).any(1).all()
    # weights are the UNBIASED scores of the chosen, normalised x 2.5
    s = np.asarray(jax.nn.sigmoid(p["x"] @ p["router"]))
    picked = np.take_along_axis(s, np.asarray(chosen), 1)
    np.testing.assert_allclose(
        weights, picked / picked.sum(1, keepdims=True) * 2.5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(1), 2.5, atol=1e-5)


@pytest.mark.parametrize("limit", [1 << 30, 0], ids=FORMS.keys())
def test_the_shares_add_up(monkeypatch, limit):
    """The routed parts of the 4 shares of a 16-expert layer, plus the
    shared expert counted once, are the uncut reference layer, under
    either form of the experts' sum."""
    monkeypatch.setattr(held_experts, "DENSE_MAX_TOKENS", limit)
    cfg = nemotron_h_tiny(num_held_experts=16)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.hidden_size))
    full = flax.core.meta.unbox(NemotronMoE(cfg).init(
        jax.random.PRNGKey(1), x, None)["params"])
    full["e_score_correction_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(4), (16,))
    full["w_up"] = full["w_up"] * 10            # routed part of order 1
    w = {"router": full["router"], "bias": full["e_score_correction_bias"],
         "w_up": full["w_up"], "w_down": full["w_down"],
         "shared_up": full["shared_up"]["kernel"],
         "shared_down": full["shared_down"]["kernel"]}
    tokens = x.reshape(-1, cfg.hidden_size)
    want = REF.moe_mixer(tokens, w, per_token=cfg.num_experts_per_tok,
                         scaling=cfg.routed_scaling_factor, first_held=0)
    shared = REF.relu2(tokens @ w["shared_up"]) @ w["shared_down"]
    total = shared
    for first in range(0, 16, 4):
        share_cfg = nemotron_h_tiny(num_held_experts=4,
                                    first_held_expert=first)
        share = dict(full, w_up=full["w_up"][first:first + 4],
                     w_down=full["w_down"][first:first + 4])
        out, _ = NemotronMoE(share_cfg).apply({"params": share}, x, None)
        total = total + (out.reshape(-1, cfg.hidden_size) - shared)
    assert float(jnp.abs(want - shared).max()) > 0.05   # routing matters
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
