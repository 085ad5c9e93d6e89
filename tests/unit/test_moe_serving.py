"""MoE serving: Megatron-DeepSpeed-MoE ingestion + expert-parallel
decode through the inference engine (VERDICT r3 item 4; reference
ops/transformer/inference/moe_inference.py:108,
module_inject/containers/megatron_gpt_moe.py:1)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import GPT2, GPTConfig



VOCAB, H, LAYERS, HEADS, EXPERTS = 128, 64, 4, 4, 4


def _native_model(use_residual=False):
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=H, num_layers=LAYERS,
                    num_heads=HEADS, max_seq_len=64,
                    moe_num_experts=EXPERTS, moe_every=2,
                    moe_use_residual=use_residual)
    return GPT2(cfg)


def _to_megatron_moe_sd(params, use_residual=False):
    """Reverse-convert our random-init param tree into a synthetic
    Megatron-DeepSpeed-MoE state dict (known weight correspondence), so
    ingestion is validated by exact logits parity."""
    hd = H // HEADS

    def de_split_qkv(kernel, bias):
        # [in, 3h] contiguous q|k|v -> megatron v2 (heads, 3, hd) fused
        w = np.asarray(kernel).T            # [3h, in]
        q, k, v = np.split(w, 3, axis=0)
        inter = np.stack([q.reshape(HEADS, hd, H), k.reshape(HEADS, hd, H),
                          v.reshape(HEADS, hd, H)], axis=1)
        b = np.asarray(bias)
        bq, bk, bv = np.split(b, 3)
        ib = np.stack([bq.reshape(HEADS, hd), bk.reshape(HEADS, hd),
                       bv.reshape(HEADS, hd)], axis=1)
        return inter.reshape(3 * H, H), ib.reshape(3 * H)

    sd = {"language_model.embedding.word_embeddings.weight":
              np.asarray(params["wte"]),
          "language_model.embedding.position_embeddings.weight":
              np.asarray(params["wpe"]),
          "language_model.transformer.final_layernorm.weight":
              np.asarray(params["ln_f"]["scale"]),
          "language_model.transformer.final_layernorm.bias":
              np.asarray(params["ln_f"]["bias"])}
    for i in range(LAYERS):
        blk = params[f"h_{i}"]
        h = f"language_model.transformer.layers.{i}."
        qkv_w, qkv_b = de_split_qkv(blk["attn"]["qkv"]["kernel"],
                                    blk["attn"]["qkv"]["bias"])
        sd[h + "attention.query_key_value.weight"] = qkv_w
        sd[h + "attention.query_key_value.bias"] = qkv_b
        sd[h + "attention.dense.weight"] = \
            np.asarray(blk["attn"]["proj"]["kernel"]).T
        sd[h + "attention.dense.bias"] = \
            np.asarray(blk["attn"]["proj"]["bias"])
        sd[h + "input_layernorm.weight"] = np.asarray(blk["ln_1"]["scale"])
        sd[h + "input_layernorm.bias"] = np.asarray(blk["ln_1"]["bias"])
        sd[h + "post_attention_layernorm.weight"] = \
            np.asarray(blk["ln_2"]["scale"])
        sd[h + "post_attention_layernorm.bias"] = \
            np.asarray(blk["ln_2"]["bias"])
        if "moe" in blk:
            moe = blk["moe"]
            sd[h + "mlp.deepspeed_moe.gate.wg.weight"] = \
                np.asarray(moe["gate"]).T
            for j in range(EXPERTS):
                ex = h + f"mlp.deepspeed_moe.experts.deepspeed_experts.{j}."
                sd[ex + "dense_h_to_4h.weight"] = \
                    np.asarray(moe["experts"]["wi"][j]).T
                sd[ex + "dense_h_to_4h.bias"] = \
                    np.asarray(moe["experts"]["bi"][j])
                sd[ex + "dense_4h_to_h.weight"] = \
                    np.asarray(moe["experts"]["wo"][j]).T
                sd[ex + "dense_4h_to_h.bias"] = \
                    np.asarray(moe["experts"]["bo"][j])
            if use_residual:
                sd[h + "mlp.mlp.dense_h_to_4h.weight"] = \
                    np.asarray(moe["res_fc_in"]["kernel"]).T
                sd[h + "mlp.mlp.dense_h_to_4h.bias"] = \
                    np.asarray(moe["res_fc_in"]["bias"])
                sd[h + "mlp.mlp.dense_4h_to_h.weight"] = \
                    np.asarray(moe["res_fc_out"]["kernel"]).T
                sd[h + "mlp.mlp.dense_4h_to_h.bias"] = \
                    np.asarray(moe["res_fc_out"]["bias"])
                sd[h + "mlp.coefficient.weight"] = \
                    np.asarray(moe["coefficient"]["kernel"]).T
                sd[h + "mlp.coefficient.bias"] = \
                    np.asarray(moe["coefficient"]["bias"])
        else:
            sd[h + "mlp.dense_h_to_4h.weight"] = \
                np.asarray(blk["mlp"]["fc_in"]["kernel"]).T
            sd[h + "mlp.dense_h_to_4h.bias"] = \
                np.asarray(blk["mlp"]["fc_in"]["bias"])
            sd[h + "mlp.dense_4h_to_h.weight"] = \
                np.asarray(blk["mlp"]["fc_out"]["kernel"]).T
            sd[h + "mlp.dense_4h_to_h.bias"] = \
                np.asarray(blk["mlp"]["fc_out"]["bias"])
    return sd


def _moe_cfg(use_residual=False):
    from types import SimpleNamespace
    return SimpleNamespace(
        model_type="megatron-moe", vocab_size=VOCAB, hidden_size=H,
        num_layers=LAYERS, num_attention_heads=HEADS,
        max_position_embeddings=64, ffn_hidden_size=4 * H,
        num_experts=EXPERTS, moe_every=2, moe_top_k=1,
        moe_use_residual=use_residual, layernorm_epsilon=1e-5)


@pytest.mark.parametrize("use_residual", [False, True])
def test_megatron_moe_ingestion_logits_parity(use_residual):
    from deepspeed_tpu.module_inject.policy import MegatronGPTMoEPolicy
    from deepspeed_tpu.module_inject.replace_policy import policy_for
    from deepspeed_tpu.parallel import sharding as shd

    cfg = _moe_cfg(use_residual)
    assert policy_for(cfg) is MegatronGPTMoEPolicy
    native = _native_model(use_residual)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, VOCAB, (2, 12)), "i4")
    ref_params = shd.unbox(
        native.init(jax.random.PRNGKey(0), ids)["params"])
    sd = _to_megatron_moe_sd(ref_params, use_residual)

    module = MegatronGPTMoEPolicy.build_module(cfg)
    got_params = MegatronGPTMoEPolicy.convert(cfg, sd)
    got_params = jax.tree.map(lambda x: np.asarray(x, np.float32),
                              got_params)
    ref = native.apply({"params": ref_params}, ids)
    got = module.apply({"params": got_params}, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_megatron_moe_layer_pattern_mismatch_raises():
    from deepspeed_tpu.module_inject.policy import MegatronGPTMoEPolicy
    cfg = _moe_cfg()
    native = _native_model()
    ids = jnp.zeros((1, 8), jnp.int32)
    from deepspeed_tpu.parallel import sharding as shd
    params = shd.unbox(native.init(jax.random.PRNGKey(0), ids)["params"])
    sd = _to_megatron_moe_sd(params)
    cfg.moe_every = 4   # checkpoint has experts at layers 1,3 — not 3 only
    with pytest.raises(ValueError, match="every-4th-block"):
        MegatronGPTMoEPolicy.convert(cfg, sd)


def test_moe_expert_parallel_serving(tmp_path):
    """Generate from an ingested MoE checkpoint on an expert>1 mesh:
    expert weights shard over the expert axis at rest, the fused decode
    scan routes tokens through the gate + all_to_all placement."""
    import deepspeed_tpu
    from deepspeed_tpu.module_inject.policy import MegatronGPTMoEPolicy
    from deepspeed_tpu.parallel import sharding as shd

    cfg = _moe_cfg()
    native = _native_model()
    ids0 = jnp.zeros((1, 8), jnp.int32)
    params = shd.unbox(native.init(jax.random.PRNGKey(1), ids0)["params"])
    sd = _to_megatron_moe_sd(params)

    module = MegatronGPTMoEPolicy.build_module(cfg)
    conv = MegatronGPTMoEPolicy.convert(cfg, sd)
    conv = jax.tree.map(lambda x: np.asarray(x, np.float32), conv)
    # rebox so the engine's sharding rules see the logical axes
    boxed = module.init(jax.random.PRNGKey(0), ids0)["params"]
    conv = jax.tree.map(
        lambda box, arr: box.replace_boxed(jnp.asarray(arr))
        if hasattr(box, "replace_boxed") else jnp.asarray(arr),
        boxed, conv, is_leaf=lambda x: hasattr(x, "replace_boxed"))

    engine = deepspeed_tpu.init_inference(
        module, dtype="float32", max_out_tokens=48,
        mesh={"data": 2, "expert": 4})
    engine.set_params(conv)
    assert engine.mesh.shape["expert"] == 4

    # expert-stacked leaves are sharded over the expert axis at rest
    wi = engine.params[f"h_1"]["moe"]["experts"]["wi"]
    spec = wi.sharding.spec
    assert "expert" in str(spec), spec

    ids = np.random.default_rng(3).integers(0, VOCAB, (2, 16)).astype("i4")
    out = engine.generate(ids, max_new_tokens=8)
    assert out.shape == (2, 24)
    # parity with the unsharded native forward on the prompt
    ref = np.asarray(native.apply({"params": params}, jnp.asarray(ids)))
    got = np.asarray(jax.device_get(engine.forward(ids)))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_moe_zero_inference_offload():
    """ZeRO-Inference + MoE: expert weights live in pinned host memory
    and stream per decode step."""
    import deepspeed_tpu

    module = _native_model()
    engine = deepspeed_tpu.init_inference(
        module, dtype="float32", max_out_tokens=48,
        mesh={"data": 2, "expert": 4}, zero={"stage": 3})
    engine.init_params()
    assert engine._offload_params
    wi = engine.params["h_1"]["moe"]["experts"]["wi"]
    assert wi.sharding.memory_kind == "pinned_host"
    ids = np.random.default_rng(4).integers(0, VOCAB, (1, 12)).astype("i4")
    out = engine.generate(ids, max_new_tokens=6)
    assert out.shape == (1, 18)
