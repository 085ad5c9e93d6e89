"""A tiny Nemotron-H hybrid (one whole period ``MEMEM*E``: Mamba-2,
routed experts over a held share, GQA attention) through the normal
serving path — ``init_inference`` + ``ServingScheduler`` — against the
plain reference's full forward (benchmarks/chip/reference_nemotron_h.py,
loaded from there).

Logits are compared, never sampled tokens.  float32 on the CPU: boundary
logits agree to 2e-5 absolute at a logit scale of ~0.6, and a served
token's logit lies within 2e-5 of the reference's best (float32
rounding through seven blocks); bfloat16 anywhere float32 is stated
misses that by two orders of magnitude.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.nemotron_h import (NemotronH, NemotronHConfig,
                                             nemotron_h_tiny)
from deepspeed_tpu.serving import ServingScheduler

TOL = 2e-5
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "reference_nemotron_h", os.path.join(REPO, "benchmarks", "chip",
                                         "reference_nemotron_h.py"))
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)


def reference_args(cfg):
    return dict(pattern=cfg.pattern, eps=cfg.rms_eps, heads=cfg.num_heads,
                kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                mamba_heads=cfg.mamba_num_heads,
                mamba_head_dim=cfg.mamba_head_dim, groups=cfg.n_groups,
                state=cfg.ssm_state_size, per_token=cfg.num_experts_per_tok,
                scaling=cfg.routed_scaling_factor,
                first_held=cfg.first_held_expert)


def build_engine(**kw):
    cfg = nemotron_h_tiny(first_held_expert=4)
    eng = deepspeed_tpu.init_inference(
        NemotronH(cfg), dtype="float32", kv_cache_dtype="float32", **kw)
    eng.init_params(seed=3)
    # the correction bias is zeros at a seeded init: make it matter
    params = jax.tree.map(lambda a: a, eng.params)
    for i, ch in enumerate(cfg.pattern):
        if ch == "E":
            params[f"layers_{i}"]["moe"]["e_score_correction_bias"] = \
                0.2 * jax.random.normal(jax.random.PRNGKey(i), (16,))
    eng.set_params(params)
    return eng


@pytest.fixture(scope="module")
def engine():
    return build_engine()


def reference_logits(engine, ids):
    cfg = engine.module.cfg
    with jax.default_matmul_precision("highest"):
        hidden = REF.hidden(engine.params, jnp.asarray(ids)[None],
                            **reference_args(cfg))
        return np.asarray(REF.logits(engine.params, hidden))[0]


def margins(engine, prompt, out_tokens):
    """How far each served token's logit lies under the reference's
    best at its position (teacher-forced full forward)."""
    ids = np.concatenate([prompt, out_tokens]).astype(np.int32)
    lg = reference_logits(engine, ids)
    pos = len(prompt) - 1 + np.arange(len(out_tokens))
    return lg[pos].max(-1) - lg[pos, out_tokens]


def test_the_pattern_must_hold_num_layers_known_characters():
    with pytest.raises(ValueError, match="pattern"):
        NemotronHConfig(num_layers=3, pattern="ME")
    with pytest.raises(ValueError, match="held experts"):
        nemotron_h_tiny(first_held_expert=14)


def test_chunked_prefill_boundary_logits_are_the_full_forwards(engine):
    """Engine level: two slots prefill in chunks of 8 from staggered
    starts; every chunk's boundary logits are the reference's at that
    position, so the carried conv tail and state are the sequence's."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (21, 13)]
    want = [reference_logits(engine, p) for p in prompts]
    pools = engine.init_paged_cache(8, 16, num_slots=3)
    table = np.array([[0, 1, 8, 8], [2, 3, 8, 8], [4, 5, 8, 8]], np.int32)
    lengths = np.zeros(3, np.int32)
    done = [0, 0]
    for step in range(3):
        rows = [j for j in (0, 1) if done[j] < len(prompts[j])
                and not (j == 1 and step == 0)]          # slot 2 joins late
        ids = np.zeros((len(rows), 8), np.int32)
        n_valid = np.zeros(len(rows), np.int32)
        for r, j in enumerate(rows):
            chunk = prompts[j][done[j]:done[j] + 8]
            ids[r, :len(chunk)], n_valid[r] = chunk, len(chunk)
        slots = np.array([[0, 2][j] for j in rows], np.int32)
        logits, pools = engine.prefill_into_slots(
            ids, slots, n_valid, table, lengths, pools)
        for r, j in enumerate(rows):
            done[j] += n_valid[r]
            lengths[slots[r]] += n_valid[r]
            np.testing.assert_allclose(logits[r], want[j][done[j] - 1],
                                       atol=TOL, rtol=0)
    assert done == [21, 13]


@pytest.fixture(scope="module")
def served(engine):
    """Staggered admissions over 3 slots and a 9-page pool: chunked
    prefill beside decode, fused horizons, slot reuse, and a pool small
    enough to force a recompute-preemption."""
    rng = np.random.default_rng(0)
    before = (engine.serving_decode_multi_compile_count(),
              engine.serving_prefill_compile_count())
    sched = ServingScheduler(engine, num_slots=3, num_pages=9, page_size=8,
                             max_pages_per_slot=6, prefill_chunk=8,
                             decode_horizon_steps=4, prefix_cache=True)
    lens = [(5, 9), (19, 12), (33, 10), (8, 14), (27, 9), (12, 16)]
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n, _ in lens]
    reqs = [sched.submit(p, m) for p, (_, m) in zip(prompts[:3], lens[:3])]
    for _ in range(3):
        sched.step()
    reqs += [sched.submit(p, m) for p, (_, m) in zip(prompts[3:], lens[3:])]
    sched.run()
    compiled = (engine.serving_decode_multi_compile_count() - before[0],
                engine.serving_prefill_compile_count() - before[1])
    return sched, prompts, reqs, compiled


def test_served_tokens_are_the_references_argmax_to_rounding(engine, served):
    sched, prompts, reqs, compiled = served
    assert sched.metrics.preemptions > 0, "the pool was sized to preempt"
    assert len(sched.horizon_buckets) > 1
    for p, r in zip(prompts, reqs):
        assert r.state == "finished" and len(r.out_tokens) == \
            r.max_new_tokens
        assert margins(engine, p, np.asarray(r.out_tokens)).max() <= TOL
    assert sched.kv.pool.pages_in_use == 0
    assert 1 <= compiled[0] <= len(sched.horizon_buckets)
    assert 1 <= compiled[1] <= len(sched.prefill_row_buckets)


def test_the_state_pool_and_the_routing_counters_are_reported(served):
    sched, prompts, reqs, _ = served
    h, s = sched.health(), sched.summary()
    cfg = sched.engine.module.cfg
    per_slot = sched.engine.state_bytes_per_slot()
    assert per_slot == 3 * (3 * cfg.conv_dim * 4 + 8 * 8 * 16 * 4)
    routing = 3 * 5 * 4           # three E layers' uint32 [5] counters
    assert h["state_pool_bytes_total"] == 3 * per_slot + routing
    assert s["state_pool_bytes"] == h["state_pool_bytes_total"]
    # K/V pages are counted apart: one attention layer's two pools
    assert h["kv_pool_bytes_total"] == 2 * 9 * 8 * 2 * 16 * 4 == \
        9 * sched.engine.kv_page_bytes(8)
    # every admission and every re-prefill after a preemption begins at 0
    assert s["state_resets"] == len(reqs) + s["preemptions"]
    # each model token is routed k ways in each of the three E layers;
    # preempted work is routed again
    tokens = sum(len(p) + r.max_new_tokens - 1 for p, r in
                 zip(prompts, reqs))
    assert s["moe_assignments"] >= 3 * 3 * tokens
    assert 0 < s["moe_held_assignments"] < s["moe_assignments"]
    assert s["moe_held_load_max_over_mean"] >= 1.0
    assert h["moe_assignments"] == s["moe_assignments"]
    # every dispatch of this fixture is under the constant's token count
    assert s["moe_dense_calls"] == s["moe_calls"] > 0


def test_the_prefix_cache_is_refused_with_its_reason(served):
    sched = served[0]
    h = sched.health()
    assert sched.prefix_cache is None and h["prefix_cache"] is False
    assert "recurrent state" in h["prefix_cache_refused"]
    assert "NemotronH" in h["prefix_cache_refused"]
    assert sched.summary()["prefix_cache_refused"] == 1


def test_a_model_without_state_keeps_its_prefix_cache():
    from deepspeed_tpu.models import Llama, llama_tiny
    eng = deepspeed_tpu.init_inference(Llama(llama_tiny()), dtype="float32")
    eng.init_params()
    sched = ServingScheduler(eng, num_slots=2, num_pages=8, page_size=8,
                             prefix_cache=True)
    h = sched.health()
    assert sched.prefix_cache is not None
    assert h["prefix_cache_refused"] is None
    assert h["state_pool_bytes_total"] == 0 and not eng.slot_state
    assert eng.state_bytes_per_slot() == 0
    assert eng.routing_counters(sched.pools) is None


@pytest.mark.parametrize("kwargs,feature", [
    ({"spec_decode": "ngram"}, "spec_decode"),
    ({"seq_parallel_threshold": 64}, "seq_parallel_prefill"),
    ({"on_handoff": lambda *a: None}, "handoff"),
])
def test_what_cannot_carry_a_state_raises_by_name(engine, kwargs, feature):
    with pytest.raises(ValueError, match=feature) as err:
        ServingScheduler(engine, num_slots=2, num_pages=8, page_size=8,
                         **kwargs)
    assert "NemotronH keeps recurrent state" in str(err.value)


def test_handoff_requests_and_engine_primitives_raise_by_name(engine):
    sched = ServingScheduler(engine, num_slots=2, num_pages=8, page_size=8)
    with pytest.raises(ValueError, match="handoff"):
        sched.submit(np.arange(4, dtype=np.int32), 4, handoff=True)
    with pytest.raises(ValueError, match="handoff"):
        sched.attach_handoff(np.arange(4, dtype=np.int32), [0], 4, 1,
                             max_new_tokens=4)
    with pytest.raises(ValueError, match="handoff"):
        engine.export_page_chain(sched.pools, [0])
    with pytest.raises(ValueError, match="prefix_cache"):
        engine.copy_page(sched.pools, 0, 1)
    with pytest.raises(ValueError, match="spec_decode"):
        engine.verify_multi(None, None, None, None, None, sched.pools,
                            widths=None, budgets=None, eos_ids=None)
    from deepspeed_tpu.serving.cluster.router import \
        make_disaggregated_group
    with pytest.raises(ValueError, match="handoff"):
        make_disaggregated_group(engine, num_pages=8, page_size=8)
    with pytest.raises(ValueError, match="num_slots"):
        engine.init_paged_cache(8, 8)


def test_paged_serving_names_the_contract_a_module_lacks():
    import flax.linen as nn

    class Bare(nn.Module):
        @nn.compact
        def __call__(self, ids):
            return nn.Embed(8, 4)(ids)
    eng = deepspeed_tpu.init_inference(Bare(), dtype="float32")
    with pytest.raises(ValueError, match="init_paged_kv_cache"):
        eng.init_paged_cache(4, 8)


def test_the_axis_rules_on_a_mesh():
    """8 virtual devices as data=2 x model=2 x expert=2: slots over
    ``data``, state heads and KV heads over ``model``, the experts' rule
    resolved; the served tokens are still the reference's."""
    from jax.sharding import PartitionSpec as P
    eng = build_engine(tensor_parallel={"tp_size": 2},
                       mesh={"data": 2, "model": 2, "expert": 2})
    sched = ServingScheduler(eng, num_slots=4, num_pages=16, page_size=8,
                             max_pages_per_slot=6, prefill_chunk=8)
    axes = sched.health()["serving_axes"]
    assert axes["slots"] == "data" and axes["kv_heads"] == "model"
    assert axes["ssm_heads"] == "model" and axes["experts"] == "expert"
    layers = sched.pools["layers"]
    assert layers[0]["ssm"].sharding.spec == P("data", "model", None, None)
    assert layers[0]["conv"].sharding.spec == P("data", None, None)
    assert layers[5]["k_pages"].sharding.spec == P(None, None, "model",
                                                   None)
    assert layers[1]["routing"].sharding.spec == P(None)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (11, 20)]
    reqs = [sched.submit(p, 6) for p in prompts]
    sched.run()
    for p, r in zip(prompts, reqs):
        assert margins(eng, p, np.asarray(r.out_tokens)).max() <= TOL
    # the pools came back where they were pinned
    assert sched.pools["layers"][0]["ssm"].sharding.spec == \
        P("data", "model", None, None)
