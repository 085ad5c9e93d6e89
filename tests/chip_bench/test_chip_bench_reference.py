"""Both plain references against the repo's own tiny models' forward, at
float32 on the CPU, on seeded random weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_bench_paths  # noqa: F401
import reference

IDS = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)
# float32 on both sides: only the order of summation differs
TOL = 2e-5


def init(module):
    from deepspeed_tpu.parallel import sharding as shd
    return shd.unbox(module.init(jax.random.PRNGKey(1),
                                 jnp.asarray(IDS))["params"])


@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_llama_family_reference_matches_the_model(kv_heads):
    from deepspeed_tpu.models.llama import Llama, llama_tiny
    cfg = llama_tiny(attn_impl="reference", num_kv_heads=kv_heads,
                     rope_base=1e6)
    model = Llama(cfg)
    params = init(model)
    hidden = reference.llama_hidden(
        params, jnp.asarray(IDS), layers=cfg.num_layers,
        heads=cfg.num_heads, kv_heads=kv_heads, rope_base=cfg.rope_base,
        eps=cfg.rms_eps)
    got = reference.llama_logits(params, hidden)
    want = model.apply({"params": params}, jnp.asarray(IDS))
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_gpt2_reference_matches_the_model_and_its_loss():
    from deepspeed_tpu.models.gpt2 import GPT2, gpt2_loss_fn, gpt2_tiny
    cfg = gpt2_tiny(attn_impl="reference")
    model = GPT2(cfg)
    params = init(model)
    got = reference.gpt2_logits(params, jnp.asarray(IDS),
                                layers=cfg.num_layers, heads=cfg.num_heads,
                                eps=cfg.layer_norm_eps)
    want = model.apply({"params": params}, jnp.asarray(IDS))
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    want_loss = float(gpt2_loss_fn(want, {"input_ids": jnp.asarray(IDS)}))
    assert float(reference.next_token_loss(got, jnp.asarray(IDS))) == \
        pytest.approx(want_loss, abs=1e-5)
    assert float(reference.gpt2_loss(
        params, jnp.asarray(IDS), layers=cfg.num_layers,
        heads=cfg.num_heads, eps=cfg.layer_norm_eps)) == \
        pytest.approx(want_loss, abs=1e-5)


def test_blocked_attention_equals_one_block():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(40, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(40, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(40, 2, 8)), jnp.float32)
    a = reference.causal_attention(q, k, v, q_block=16)
    b = reference.causal_attention(q, k, v, q_block=64)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5
