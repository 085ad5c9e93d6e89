"""Inference engine: init_inference surface, generation correctness vs the
no-cache oracle path, TP-sharded serving (reference
tests/unit/inference/test_inference.py spirit at fixture scale)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu


from deepspeed_tpu.models.llama import Llama, llama_tiny


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama_tiny(num_layers=2)
    model = Llama(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return model, params


def test_init_inference_surface(tiny_llama):
    model, params = tiny_llama
    engine = deepspeed_tpu.init_inference(
        model=model, dtype="float32", params=params,
        tensor_parallel={"tp_size": 1}, mesh={"data": 1, "model": 1})
    logits = engine(np.zeros((1, 8), np.int32))
    assert logits.shape[-1] == model.cfg.vocab_size
    assert len(engine.model_times()) == 1


def test_greedy_generate_matches_nocache(tiny_llama):
    """KV-cache decode must produce the same greedy tokens as full
    re-forward generation (the correctness oracle)."""
    model, params = tiny_llama
    engine = deepspeed_tpu.init_inference(
        model=model, dtype="float32", kv_cache_dtype="float32", params=params,
        mesh={"data": 1, "model": 1})
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, size=(2, 6)).astype(np.int32)

    out_cached = engine.generate(prompt, max_new_tokens=8, do_sample=False)
    out_nocache = engine._generate_nocache(prompt, 8, False, 1.0, 0, 1.0,
                                           None)
    np.testing.assert_array_equal(out_cached, out_nocache)


def test_generate_with_eos_stops(tiny_llama):
    model, params = tiny_llama
    engine = deepspeed_tpu.init_inference(
        model=model, dtype="float32", kv_cache_dtype="float32", params=params,
        mesh={"data": 1, "model": 1})
    prompt = np.zeros((1, 4), np.int32)
    # force every token to be eos by choosing eos = greedy first token
    first = engine.generate(prompt, max_new_tokens=1, do_sample=False)
    eos = int(first[0, -1])
    out = engine.generate(prompt, max_new_tokens=16, do_sample=False,
                          eos_token_id=eos)
    assert out.shape[1] < 4 + 16 or (out[:, 4:] == eos).any()


def test_sampling_reproducible_and_topk(tiny_llama):
    model, params = tiny_llama
    engine = deepspeed_tpu.init_inference(
        model=model, dtype="float32", kv_cache_dtype="float32", params=params,
        mesh={"data": 1, "model": 1})
    prompt = np.zeros((1, 4), np.int32)
    out = engine.generate(prompt, max_new_tokens=4, do_sample=True,
                          temperature=0.8, top_k=5)
    assert out.shape == (1, 8)
    assert (out[:, 4:] < model.cfg.vocab_size).all()


@pytest.mark.parametrize("tp", [4])
def test_tensor_parallel_serving(tiny_llama, tp):
    """TP-sharded weights over the model axis, output identical to
    single-device (auto-TP equivalence, reference AutoTP). tp=4 equals
    num_heads (clean per-head sharding, exact on every runtime); tp=8
    would oversubscribe the 4-head axis — formerly an env-bound skip
    (the legacy jax<0.5 CPU partitioner silently miscompiles intra-head
    sharding), now a construction-time ValueError on EVERY runtime
    (test_oversubscribed_tp_rejected_at_construction below)."""
    model, params = tiny_llama
    e1 = deepspeed_tpu.init_inference(model=model, dtype="float32",
                                      params=params,
                                      mesh={"data": 1, "model": 1})
    etp = deepspeed_tpu.init_inference(model=model, dtype="float32",
                                       params=params,
                                       tensor_parallel={"tp_size": tp},
                                       mesh={"data": 1, "model": tp})
    ids = np.arange(8, dtype=np.int32)[None] % 256
    l1 = np.asarray(e1(ids))
    ltp = np.asarray(etp(ids))
    np.testing.assert_allclose(l1, ltp, atol=1e-4, rtol=1e-4)
    # check at least one weight is actually sharded over 'model'
    specs = jax.tree.leaves(jax.tree.map(
        lambda x: str(x.sharding.spec), etp.params))
    assert any("model" in s for s in specs), specs


def test_oversubscribed_tp_rejected_at_construction(tiny_llama):
    """tp=8 over a 4-head model shards attention MID-head — a shape
    the legacy jax<0.5 CPU SPMD partitioner silently miscompiles into
    ~1e-2 output drift (the seed-era red test, triaged PR 2 behind the
    `legacy_spmd_oversubscribed_tp` skip).  Since the mesh-validation
    work it is a loud construction-time ValueError naming the axis and
    head count, on every runtime — deterministic coverage where the
    skip used to hide an env-bound silent failure."""
    model, params = tiny_llama
    with pytest.raises(ValueError, match=r"model.*8.*num_heads=4"):
        deepspeed_tpu.init_inference(model=model, dtype="float32",
                                     params=params,
                                     tensor_parallel={"tp_size": 8},
                                     mesh={"data": 1, "model": 8})


def test_inference_from_training_checkpoint(tmp_path, tiny_llama):
    """Train briefly, save, serve from the checkpoint (ZeRO-Inference path)."""
    model, _ = tiny_llama
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "mesh": {"data": 8},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    gen = np.random.default_rng(0)
    batch = {"input_ids": gen.integers(0, 256, size=(16, 16)).astype(np.int32)}
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    engine.save_checkpoint(str(tmp_path))

    inf = deepspeed_tpu.init_inference(model=model, dtype="float32",
                                       mesh={"data": 1, "model": 1},
                                       checkpoint=str(tmp_path))
    logits = inf(batch["input_ids"][:2, :8])
    ref = model.apply({"params": jax.tree.map(
        lambda x: x.astype(jnp.float32),
        jax.device_get(engine.state.params))}, batch["input_ids"][:2, :8])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


def test_zero_inference_host_offload(tiny_llama):
    """ZeRO-Inference (reference zero.stage=3 + init_inference): weights
    live in pinned host memory and stream to the device inside the jitted
    forward; logits match the on-device engine."""
    import deepspeed_tpu
    module, params = tiny_llama
    ids = np.random.default_rng(0).integers(3, 250, (2, 12)).astype("i4")

    ref_e = deepspeed_tpu.init_inference(module, params=params,
                                         dtype="float32")
    ref = np.asarray(jax.device_get(ref_e.forward(ids)))

    off_e = deepspeed_tpu.init_inference(module, params=params,
                                         dtype="float32",
                                         zero={"stage": 3})
    kinds = {getattr(l.sharding, "memory_kind", None)
             for l in jax.tree.leaves(off_e.params)}
    assert kinds == {"pinned_host"}, kinds
    got = np.asarray(jax.device_get(off_e.forward(ids)))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    # generation runs through the offloaded decode path
    out = off_e.generate(ids[:, :6], max_new_tokens=4)
    ref_out = ref_e.generate(ids[:, :6], max_new_tokens=4)
    np.testing.assert_array_equal(out, ref_out)


def test_zero_inference_with_int8(tiny_llama):
    """Offload + int8: the host->device stream carries quantized bytes."""
    import deepspeed_tpu
    from deepspeed_tpu.ops.quant import QTensor
    module, params = tiny_llama
    ids = np.random.default_rng(1).integers(3, 250, (2, 8)).astype("i4")
    e = deepspeed_tpu.init_inference(module, params=params, dtype="int8",
                                     zero={"stage": 3},
                                     quant={"group_size": 32})
    qleaves = [l for l in jax.tree.leaves(
        e.params, is_leaf=lambda x: isinstance(x, QTensor))
        if isinstance(l, QTensor)]
    assert qleaves and all(
        q.q.sharding.memory_kind == "pinned_host" for q in qleaves)
    out = e.generate(ids, max_new_tokens=4)
    assert out.shape == (2, 12)


def test_zero_inference_checkpoint_restore_streams_to_host(tmp_path,
                                                           tiny_llama):
    """Offloaded engines restore checkpoints straight into host memory
    (the larger-than-HBM load path: no full float tree on device)."""
    import deepspeed_tpu
    module, params = tiny_llama
    ids = np.random.default_rng(2).integers(3, 250, (2, 8)).astype("i4")

    # train-engine-style checkpoint to restore from (attribute-path
    # .params like the engine's TrainState)
    import flax.struct

    @flax.struct.dataclass
    class FakeState:
        params: dict

    ref_e = deepspeed_tpu.init_inference(module, params=params,
                                         dtype="float32")
    from deepspeed_tpu.checkpoint.engine import save_state
    save_state(str(tmp_path / "t"), FakeState(params=ref_e.params))
    (tmp_path / "latest").write_text("t")

    off_e = deepspeed_tpu.init_inference(
        module, dtype="float32", zero={"stage": 3},
        checkpoint={"checkpoint_dir": str(tmp_path)})
    kinds = {getattr(l.sharding, "memory_kind", None)
             for l in jax.tree.leaves(off_e.params)}
    assert kinds == {"pinned_host"}, kinds
    ref = np.asarray(jax.device_get(ref_e.forward(ids)))
    got = np.asarray(jax.device_get(off_e.forward(ids)))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
