"""Dropless top-k routing over a held share of the experts
(moe/held_experts.py, models/nemotron_h.NemotronMoE) against a
per-token loop and against the plain reference's uncut layer
(benchmarks/chip/reference_nemotron_h.py, loaded from there: the repo
has one plain reference, not two).

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


def layer(seed=0, held=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"x": jax.random.normal(k[0], (T, HID)),
            "router": jax.random.normal(k[1], (HID, E)) * 0.5,
            "w_up": jax.random.normal(k[2], (held, HID, INTER)) * 0.2,
            "w_down": jax.random.normal(k[3], (held, INTER, HID)) * 0.2}


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


@pytest.mark.parametrize("first", [0, 4, 12])
def test_grouped_is_the_per_token_loop(first):
    p = layer()
    chosen, weights = held_experts.sigmoid_topk_router(
        p["x"], p["router"], jnp.zeros(E), K, 2.5)
    out, sizes = held_experts.held_experts_ffn(
        p["x"], chosen, weights, p["w_up"], p["w_down"], first)
    want, computed = per_token(p["x"], chosen, weights, p["w_up"],
                               p["w_down"], first)
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)
    assert int(sizes.sum()) == computed > 0


def test_bfloat16_activations_would_fail():
    p = layer()
    chosen, weights = held_experts.sigmoid_topk_router(
        p["x"], p["router"], jnp.zeros(E), K, 2.5)
    low, _ = held_experts.held_experts_ffn(
        p["x"].astype(jnp.bfloat16), chosen, weights, p["w_up"],
        p["w_down"], 0)
    want, _ = per_token(p["x"], chosen, weights, p["w_up"], p["w_down"], 0)
    assert np.abs(np.asarray(low, np.float64) - want).max() > 20 * TOL


def test_no_pair_is_dropped_when_every_token_chooses_one_expert():
    """Dropless: all the tokens on one held expert are all computed (a
    capacity-factor gate would keep ceil(t * k / experts) of them)."""
    p = layer()
    chosen = jnp.stack([jnp.full((T,), 5), jnp.full((T,), 14),
                        jnp.full((T,), 15)], 1).astype(jnp.int32)
    weights = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (T, K)))
    out, sizes = held_experts.held_experts_ffn(
        p["x"], chosen, weights, p["w_up"], p["w_down"], 4)
    want, computed = per_token(p["x"], chosen, weights, p["w_up"],
                               p["w_down"], 4)
    assert computed == T and list(np.asarray(sizes)) == [0, T, 0, 0]
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)


def test_idle_tokens_route_nowhere():
    p = layer()
    chosen, weights = held_experts.sigmoid_topk_router(
        p["x"], p["router"], jnp.zeros(E), K, 2.5)
    live = jnp.arange(T) % 3 != 0
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


def test_the_shares_add_up():
    """The routed parts of the 4 shares of a 16-expert layer, plus the
    shared expert counted once, are the uncut reference layer."""
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
