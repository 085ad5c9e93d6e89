"""A tiny AFMoE (Trinity: gated, QK-normed attention, rotary in the
window layers alone, sandwich norms, sigmoid top-k experts over a held
share beside a shared expert, the embedding scaled) against the plain
reference's full forward (benchmarks/chip/reference_afmoe.py, loaded
from there), with the norm weights, ``expert_bias`` and every other
parameter that a seeded init leaves at a default drawn away from it.

Logits are compared, never sampled tokens.  ``TOL`` = 2e-6 absolute on
logits of scale ~0.7: float32 rounding through five blocks with four
norms each reads 4e-7 here; the least of the pieces left out of the
reference (rotary put into the one full layer) moves a logit by 0.12.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import afmoe
from deepspeed_tpu.models.afmoe import AFMoE, AFMoEConfig, afmoe_tiny

TOL = 2e-6
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "reference_afmoe", os.path.join(REPO, "benchmarks", "chip",
                                    "reference_afmoe.py"))
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)


def reference_args(cfg):
    return dict(layer_types=cfg.layer_types,
                dense_layers=cfg.num_dense_layers, eps=cfg.rms_eps,
                heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, theta=cfg.rope_theta,
                window=cfg.sliding_window,
                per_token=cfg.num_experts_per_tok, scale=cfg.route_scale,
                first_held=cfg.first_held_expert,
                held=cfg.num_held_experts)


def drawn_params(cfg, seed=3):
    """A seeded init with every default drawn away from it: norm
    weights (ones) uniform in [0.5, 1.5], ``expert_bias`` (zeros)
    normal(0.2)."""
    params = jax.jit(AFMoE(cfg).init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    from flax.core import meta
    params = meta.unbox(params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = {}
    for n, (path, leaf) in enumerate(flat):
        names = [p.key for p in path]
        key = jax.random.PRNGKey(1000 + n)
        if names[-1] == "scale":
            leaf = jax.random.uniform(key, leaf.shape, minval=0.5,
                                      maxval=1.5)
        elif names[-1] == "expert_bias":
            leaf = 0.2 * jax.random.normal(key, leaf.shape)
        node = out
        for name in names[:-1]:
            node = node.setdefault(name, {})
        node[names[-1]] = leaf
    return out


def reference_logits(params, ids, args):
    with jax.default_matmul_precision("highest"):
        hidden = REF.hidden(params, jnp.asarray(ids)[None], **args)
        return np.asarray(REF.logits(params, hidden))[0]


# 75 tokens: longer than the window (32) and than its ring (64)
IDS = np.random.default_rng(5).integers(0, 256, 75).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return drawn_params(afmoe_tiny())


@pytest.fixture(scope="module")
def want(params):
    return reference_logits(params, IDS, reference_args(afmoe_tiny()))


def test_the_config_holds_its_layer_types_and_the_published_widths():
    with pytest.raises(ValueError, match="layer_types"):
        afmoe_tiny(layer_types=("full_attention",))
    with pytest.raises(ValueError, match="layer_types"):
        afmoe_tiny(layer_types=("chunked_attention",) * 5)
    with pytest.raises(ValueError, match="held experts"):
        afmoe_tiny(first_held_expert=14)
    cfg = AFMoEConfig()             # Trinity-Large-Preview as published
    assert cfg.layer_types.count(afmoe.WINDOW) == 45
    assert cfg.num_kv_layers == 15 and cfg.layer_types[3] == afmoe.FULL
    assert not cfg.routed(5) and cfg.routed(6)
    # the window and two pages of 128
    assert cfg.ring_rows == 4352 and afmoe_tiny().ring_rows == 64
    # a configuration file hands a list over
    assert afmoe_tiny(layer_types=list(afmoe_tiny().layer_types)) \
        .layer_types == afmoe_tiny().layer_types
    with pytest.raises(ValueError, match="whole pages"):
        afmoe_tiny(sliding_window=40).ring_rows


@pytest.mark.parametrize("overrides, depth", [
    ({}, 5), ({"depth_scale_layers": 60}, 60)])
def test_a_seeded_init_scales_the_post_norms_by_the_depth(overrides, depth):
    """The post-norms' gains start at (2 x depth) ** -0.5 -- of the
    layers the model has, or of those it was cut from -- and every other
    norm at 1; a tree that has the parameter keeps its own value."""
    from flax.core import meta
    cfg = afmoe_tiny(**overrides)
    assert cfg.post_norm_gain == pytest.approx((2 * depth) ** -0.5)
    assert AFMoEConfig().post_norm_gain == pytest.approx(120 ** -0.5)
    model = AFMoE(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    tree = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(0),
                                          ids)["params"])
    for i in range(cfg.num_layers):
        block = tree[f"layers_{i}"]
        for name in ("post_attn_norm", "post_ff_norm"):
            np.testing.assert_allclose(block[name]["scale"],
                                       cfg.post_norm_gain, rtol=1e-6)
        for name in ("input_norm", "pre_ff_norm"):
            np.testing.assert_array_equal(block[name]["scale"], 1.0)
    np.testing.assert_array_equal(tree["norm_f"]["scale"], 1.0)
    # the gain is an init, not a factor of the forward pass
    other = AFMoE(afmoe_tiny(depth_scale_layers=7))
    np.testing.assert_array_equal(other.apply({"params": tree}, ids),
                                  model.apply({"params": tree}, ids))


def test_full_forward_logits_are_the_references(params, want):
    got = AFMoE(afmoe_tiny()).apply({"params": params}, IDS[None])[0]
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("piece", [
    "gate", "q_norm", "k_norm", "full_rotary", "window_rotary",
    "window_edge", "post_attn_norm", "post_ff_norm", "shared",
    "route_scale", "expert_bias", "embed_scale"])
def test_a_reference_with_one_piece_left_out_fails(params, want, piece):
    """The output gate, either per-head norm, rotary put into the full
    layers or taken out of the window layers, a window of one position
    more, either post-norm, the shared expert, ``route_scale``, the
    bias of the choice, the embedding's scale: the comparison sees
    each."""
    wrong = reference_logits(params, IDS, dict(
        reference_args(afmoe_tiny()), drop=(piece,)))
    assert np.abs(want - wrong).max() > 100 * TOL


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """Guide section 4's share test on one routed block's FFN: the parts
    that the four chips of an expert-parallel group compute (experts
    0-3, 4-7, 8-11, 12-15 of 16), the shared expert counted ONCE, add
    up to what one chip holding all 16 computes."""
    whole = afmoe_tiny(num_held_experts=16)
    moe = jax.tree.map(jnp.asarray, drawn_params(whole)["layers_1"]["moe"])
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 19, whole.hidden_size))
    full, _ = afmoe.AFMoEMoE(whole).apply({"params": moe}, u)
    shared = afmoe.SwiGLUMLP(whole, whole.moe_intermediate_size).apply(
        {"params": moe["shared"]}, u)
    parts = 0.0
    for first in range(0, 16, 4):
        cfg = afmoe_tiny(first_held_expert=first)
        share = dict(moe, w_up=moe["w_up"][first:first + 4],
                     w_down=moe["w_down"][first:first + 4])
        out, _ = afmoe.AFMoEMoE(cfg).apply({"params": share}, u)
        parts = parts + (out - shared)
    np.testing.assert_allclose(parts + shared, full, atol=2e-6, rtol=0)
    # and the routed part is not nothing
    assert float(jnp.abs(full - shared).max()) > 1e-3


def test_the_module_exports_the_family_contract():
    cfg = afmoe_tiny()
    # four window layers: 64 rows x 2 KV heads x (16 + 16) x 4 B
    assert afmoe.state_bytes_per_slot(cfg, jnp.float32) == 4 * 64 * 256
    assert afmoe.window_ring(cfg, jnp.float32) == (32, 4 * 64 * 256)
    # one full layer's page of 16
    assert afmoe.kv_page_bytes(cfg, 16, jnp.float32) == 16 * 256
    pools = jax.eval_shape(lambda: afmoe.init_paged_kv_cache(
        cfg, 6, 16, jnp.float32, num_slots=3))["layers"]
    for i, (kind, entry) in enumerate(zip(cfg.layer_types, pools)):
        names = {"k_ring", "v_ring"} if kind == afmoe.WINDOW \
            else {"k_pages", "v_pages"}
        assert set(entry) == names | ({"routing", "walked"} if i else set())
    assert pools[0]["k_ring"].shape == (3, 64, 2, 16)
    assert pools[2]["k_pages"].shape == (6, 16, 2, 16)
    with pytest.raises(ValueError, match="num_slots"):
        afmoe.init_paged_kv_cache(cfg, 6, 16)
    dense = jax.eval_shape(lambda: afmoe.init_kv_cache(cfg, 2, max_len=16))
    assert len(dense["layers"]) == 5
