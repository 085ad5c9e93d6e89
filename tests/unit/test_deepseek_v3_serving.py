"""A tiny DeepSeek-V3 (multi-head latent attention: a latent of 32 and a
shared rope key of 8 cached as ONE vector of 40 a token a layer, 4 query
heads over that one latent head; a dense layer, then two routed layers
with 4 of 16 experts held and a shared MLP) through the normal serving
path — ``init_inference`` + ``ServingScheduler`` — against the plain
reference's full forward (benchmarks/chip/reference_deepseek_v3.py,
loaded from there), which computes the PER-HEAD form: every comparison
of the paged path also checks the absorption.

Logits are compared, never sampled tokens.  ``TOL`` = 2e-6 absolute on
logits at the tiny preset's scale of ~0.6: float32 rounding through
three blocks reads 2e-7 here (full forward, chunked prefill through
latent pages, decode alike); the least of the reference's terms dropped
moves a logit by 1e-3.
"""

import dataclasses
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import deepseek_v3
from deepspeed_tpu.models.deepseek_v3 import (DeepseekMoE, DeepseekV3,
                                              DeepseekV3Config,
                                              deepseek_v3_tiny)
from deepspeed_tpu.ops.attention import kv_cache, reference as attn_ref
from deepspeed_tpu.ops.attention.decode import (kernel_mode_scope,
                                                paged_decode_attention,
                                                paged_kernel_decision)
from deepspeed_tpu.ops.quant import kv as kvq
from deepspeed_tpu.serving import ServingScheduler
from deepspeed_tpu.serving.sharding import (ServingShardingConfig,
                                            split_pools)

TOL = 2e-6
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "reference_deepseek_v3", os.path.join(REPO, "benchmarks", "chip",
                                          "reference_deepseek_v3.py"))
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)


def reference_args(cfg):
    return dict(layers=cfg.num_layers, first_dense=cfg.first_k_dense_replace,
                eps=cfg.rms_eps, heads=cfg.num_heads, rank=cfg.kv_lora_rank,
                nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                v_dim=cfg.v_head_dim, theta=cfg.rope_theta,
                interleave=cfg.rope_interleave,
                per_token=cfg.num_experts_per_tok,
                scaling=cfg.routed_scaling_factor,
                first_held=cfg.first_held_expert)


def build_engine(cfg=None, **kw):
    eng = deepspeed_tpu.init_inference(
        DeepseekV3(cfg or deepseek_v3_tiny()), dtype="float32",
        kv_cache_dtype="float32", **kw)
    eng.init_params(seed=3)
    # the correction bias is zeros and the latent's norm weight ones at
    # a seeded init: give both values, so that the choice the bias makes
    # (and only the choice) and the norm's own weight are under test
    params = jax.tree.map(lambda a: a, eng.params)
    cfg = eng.module.cfg
    for i in range(cfg.num_layers):
        params[f"layers_{i}"]["attn"]["kv_a_norm"]["scale"] = 1.0 + \
            0.3 * jax.random.normal(jax.random.PRNGKey(70 + i),
                                    (cfg.kv_lora_rank,))
        if i >= cfg.first_k_dense_replace:
            params[f"layers_{i}"]["moe"]["e_score_correction_bias"] = \
                0.2 * jax.random.normal(jax.random.PRNGKey(40 + i), (16,))
    eng.set_params(params)
    return eng


@pytest.fixture(scope="module")
def engine():
    return build_engine()


def reference_logits(params, ids, args):
    with jax.default_matmul_precision("highest"):
        hidden = REF.hidden(params, jnp.asarray(ids)[None], **args)
        return np.asarray(REF.logits(params, hidden))[0]


# 61 tokens: longer than a page (8) seven times over
IDS = np.random.default_rng(5).integers(0, 256, 61).astype(np.int32)


@pytest.fixture(scope="module")
def want(engine):
    return reference_logits(engine.params, IDS,
                            reference_args(engine.module.cfg))


def test_the_config_holds_the_published_widths_and_refuses_by_name():
    cfg = DeepseekV3Config()        # kanana-2-30b-a3b as published
    assert (cfg.latent_dim, cfg.qk_head_dim, cfg.num_kv_heads) == \
        (576, 192, 1)
    assert cfg.num_routed_layers == 47
    # 576 is 4.5 lane tiles: the pool stores it at 640
    assert kvq.latent_stored_dim(576) == 640
    assert kvq.latent_stored_dim(40) == 40
    assert deepseek_v3.latent_bytes_per_token(cfg) == (48 * 1152, 48 * 1280)
    with pytest.raises(ValueError, match="q_lora_rank"):
        deepseek_v3_tiny(q_lora_rank=16)
    with pytest.raises(ValueError, match="rope_scaling"):
        deepseek_v3_tiny(rope_scaling={"type": "yarn"})
    with pytest.raises(ValueError, match="moe_layer_freq"):
        deepseek_v3_tiny(moe_layer_freq=2)
    with pytest.raises(ValueError, match="held experts"):
        deepseek_v3_tiny(first_held_expert=14)


# ------------------------------------- (a), (e): the reference's logits

def test_full_forward_logits_are_the_references(engine, want):
    got = engine.module.apply({"params": engine.params}, IDS[None])[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("term", ["latent_norm", "key_rope", "routed_scale",
                                  "shared", "score_bias"])
def test_a_reference_with_one_term_dropped_fails(engine, want, term):
    """The latent's norm, the rotary on the shared key, the routed
    scale, the shared MLP, the correction bias in the choice: the
    comparison sees each."""
    wrong = reference_logits(engine.params, IDS, dict(
        reference_args(engine.module.cfg), drop=(term,)))
    assert np.abs(want - wrong).max() > 100 * TOL


def test_the_other_rotary_pairing_is_another_model(engine, want):
    wrong = reference_logits(engine.params, IDS, dict(
        reference_args(engine.module.cfg), interleave=False))
    assert np.abs(want - wrong).max() > 100 * TOL
    half = build_engine(deepseek_v3_tiny(rope_interleave=False))
    got = half.module.apply({"params": half.params}, IDS[None])[0]
    np.testing.assert_allclose(got, wrong, atol=TOL, rtol=0)


# -------------------- (b): chunked prefill and decode through the pool

def paged_decode_logits(engine, tok, active, table, lengths, pools):
    """One decode step of the MODEL through the pools (the engine's
    decode primitives return sampled tokens)."""
    step = kv_cache.decode_step(pools["layers"], jnp.asarray(table),
                                jnp.asarray(lengths), jnp.asarray(active))
    with engine._serving_scope():
        logits, new = engine.module.apply(
            {"params": engine.params}, jnp.asarray(tok)[:, None], cache=step)
    return np.asarray(logits[:, 0]), new.pools, np.asarray(new.lengths)


@pytest.mark.parametrize("chunk,kernel,lanes", [
    (4, "auto", 128), (8, "auto", 128), (24, "auto", 128),
    (8, "force", 128), (8, "auto", 16), (24, "force", 16)])
def test_chunked_prefill_then_decode_through_the_latent_pool(
        want, monkeypatch, chunk, kernel, lanes):
    """48 prompt tokens (six pages) in chunks of ``chunk`` — 24 is
    longer than a page — into slot 2, every chunk's boundary logits the
    reference's; then teacher-forced decode steps.  ``force`` runs the
    two paged Pallas kernels' shared read in interpret mode (fewer
    decode steps: each traces the interpreter anew); a lane tile of 16
    stores the vector of 40 zero-padded to 48, as the published 576 is
    stored at 640."""
    monkeypatch.setattr(kvq, "LANES", lanes)
    engine = build_engine(paged_kernel=kernel)
    stored = 40 if lanes == 128 else 48
    one = engine.init_paged_cache(1, 8)["layers"]
    assert all(e["c_pages"].shape == (1, 8, stored) for e in one)
    # (h) the module's own page bytes: the leaf as stored
    assert engine.kv_page_bytes(8) == 3 * 8 * stored * 4 == \
        sum(e["c_pages"].nbytes for e in one)
    assert engine.latent_bytes_per_token() == (3 * 40 * 4, 3 * stored * 4)
    assert engine.state_bytes_per_slot() == 0
    pools = engine.init_paged_cache(12, 8)
    table = np.array([[12] * 8, [8, 9, 10, 11, 12, 12, 12, 12],
                      [0, 1, 2, 3, 4, 5, 6, 7]], np.int32)
    lengths = np.zeros(3, np.int32)
    n_prompt = 48
    for at in range(0, n_prompt, chunk):
        ids = np.zeros((1, chunk), np.int32)
        part = IDS[at:min(at + chunk, n_prompt)]
        ids[0, :len(part)] = part
        logits, pools = engine.prefill_into_slots(
            ids, [2], [len(part)], table, lengths, pools)
        lengths[2] += len(part)
        np.testing.assert_allclose(logits[0], want[lengths[2] - 1],
                                   atol=TOL, rtol=0)
    # the padding columns of the pool hold zeros
    assert float(jnp.abs(pools["layers"][0]["c_pages"][..., 40:]).max()
                 if stored > 40 else 0.0) == 0.0
    for t in range(n_prompt, len(IDS) if kernel == "auto" else n_prompt + 3):
        tok = np.zeros(3, np.int32)
        tok[2] = IDS[t]
        logits, pools, new_len = paged_decode_logits(
            engine, tok, [False, False, True], table, lengths, pools)
        assert list(new_len) == [0, 0, lengths[2] + 1]   # advanced ONCE
        lengths = new_len
        np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0)


def test_an_entry_is_one_latent_leaf_and_the_routing_counters(engine):
    pools = engine.init_paged_cache(8, 8)
    cfg = engine.module.cfg
    for i, entry in enumerate(pools["layers"]):
        routed = i >= cfg.first_k_dense_replace
        assert set(entry) == {"c_pages"} | ({"routing", "walked"} if routed
                                            else set())
        assert entry["c_pages"].shape == (8, 8, 40)
    # the page ledgers bill the latent leaf by the page, the counters not
    kv, other = split_pools(pools)
    assert all(set(e) == {"c_pages"} for e in kv)
    assert [set(e) for e in other] == [set(), {"routing"}, {"routing"}]
    # generate()'s dense cache is per head: the published form
    dense = deepseek_v3.init_kv_cache(cfg, 2, max_len=16,
                                      dtype=jnp.float32)
    assert dense["layers"][0]["k"].shape == (2, 16, 4, 24)
    assert dense["layers"][0]["v"].shape == (2, 16, 4, 16)


def test_a_slot_reused_by_a_shorter_request_sees_nothing_of_the_last(
        engine, want):
    """Another request's 37 tokens through slot 1, then OUR first 11
    tokens into the same slot and the same pages from position 0: the
    pages are NOT cleared, and the boundary logits are the reference's
    of our prompt alone."""
    pools = engine.init_paged_cache(8, 8)
    table = np.array([[8] * 6, [0, 1, 2, 3, 4, 5]], np.int32)
    lengths = np.zeros(2, np.int32)
    other = np.random.default_rng(9).integers(0, 256, (1, 40)).astype(
        np.int32)
    _, pools = engine.prefill_into_slots(other, [1], [37], table, lengths,
                                         pools)
    held = np.asarray(pools["layers"][1]["c_pages"])
    assert (np.abs(held[:4]).max(axis=2) > 0).all()
    logits, pools = engine.prefill_into_slots(IDS[None, :16], [1], [11],
                                              table, lengths, pools)
    np.testing.assert_allclose(logits[0], want[10], atol=TOL, rtol=0)
    # positions 11.. still hold the last tenant's vectors: masked
    now = np.asarray(pools["layers"][1]["c_pages"])
    assert np.array_equal(held[1, 3:], now[1, 3:])
    assert np.array_equal(held[2:], now[2:])


def test_an_idle_slots_pages_are_bit_identical_after_a_decode_step(engine):
    pools = engine.init_paged_cache(8, 8)
    table = np.array([[0, 1, 8, 8], [2, 3, 8, 8]], np.int32)
    lengths = np.zeros(2, np.int32)
    ids = np.stack([IDS[:8], IDS[8:16]])
    _, pools = engine.prefill_into_slots(ids, [0, 1], [8, 7], table, lengths,
                                         pools)
    lengths = np.array([8, 7], np.int32)
    before = jax.tree.map(np.array, pools)      # host copies
    _, after, new_len = paged_decode_logits(
        engine, IDS[20:22], [True, False], table, lengths, pools)
    assert list(new_len) == [9, 7]
    for old, new in zip(before["layers"], after["layers"]):
        # slot 1's pages and those nobody holds
        assert np.array_equal(old["c_pages"][2:],
                              np.asarray(new["c_pages"][2:]))
        assert np.array_equal(old["c_pages"][0],
                              np.asarray(new["c_pages"][0]))
        assert not np.array_equal(old["c_pages"][1],
                                  np.asarray(new["c_pages"][1]))


def test_a_padding_row_and_a_padding_column_write_nothing(engine):
    pools = engine.init_paged_cache(8, 8)
    table = np.array([[0, 1, 8, 8], [2, 3, 8, 8]], np.int32)
    ids = np.stack([IDS[:8], IDS[8:16]])
    # row 1 is padding (n_valid 0) and carries a live slot id; row 0
    # holds five tokens and three padding columns
    _, after = engine.prefill_into_slots(ids, [0, 0], [5, 0], table,
                                         np.zeros(2, np.int32), pools)
    c = np.asarray(after["layers"][1]["c_pages"])
    assert (np.abs(c[0, :5]).max(axis=1) > 0).all()
    assert np.abs(c[0, 5:]).max() == 0 and np.abs(c[1:]).max() == 0


# ------------------------- (c): absorbed (paged) against per-head (dense)

def test_the_absorbed_paged_form_is_the_per_head_dense_form(engine):
    """The program against itself: ``generate()``'s dense cache holds
    per-head keys of 24 and values of 16 and attends per head; the
    scheduler's pool holds one vector of 40 and attends absorbed.  Same
    greedy tokens, and boundary logits to float32 rounding."""
    prompt = IDS[:29]
    dense = deepseek_v3.init_kv_cache(engine.module.cfg, 1, max_len=32,
                                      dtype=jnp.float32)
    per_head, _ = engine.module.apply({"params": engine.params},
                                      prompt[None], cache=dense)
    pools = engine.init_paged_cache(4, 8)
    table = np.array([[0, 1, 2, 3]], np.int32)
    ids = np.zeros((1, 32), np.int32)
    ids[0, :29] = prompt
    absorbed, _ = engine.prefill_into_slots(ids, [0], [29], table,
                                            np.zeros(1, np.int32), pools)
    np.testing.assert_allclose(absorbed[0], per_head[0, -1], atol=TOL,
                               rtol=0)
    sched = ServingScheduler(engine, num_slots=2, num_pages=12, page_size=8,
                             max_pages_per_slot=6, prefill_chunk=8)
    req = sched.submit(prompt, max_new_tokens=9)
    got = sched.run()[req.rid]
    out = engine.generate(prompt[None], max_new_tokens=9, do_sample=False)
    assert list(np.asarray(out)[0, 29:]) == got


def _one_block_over_a_padded_pool(dtype, monkeypatch, rows, chunk):
    """A latent pool of 40 stored at 48 (lane tile 16), a ``[rows,
    chunk]`` step over it -- decode where ``chunk`` is 1 -- and an
    ``MLAttention`` with its parameters."""
    monkeypatch.setattr(kvq, "LANES", 16)
    cfg = deepseek_v3_tiny(dtype=dtype, param_dtype=dtype)
    assert kvq.latent_stored_dim(cfg.latent_dim) == 48
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((rows, chunk, cfg.hidden_size)),
                    dtype)
    pool = kvq.latent_pool_layer(3 * rows, 8, cfg.latent_dim, dtype)
    table = jnp.arange(3 * rows, dtype=jnp.int32).reshape(rows, 3)
    lengths = jnp.asarray([5, 0, 9, 2][:rows], jnp.int32)
    if chunk == 1:
        step = kv_cache.decode_step(pool, table, lengths,
                                    jnp.ones(rows, bool))
    else:
        step = kv_cache.prefill_step(
            pool, table, lengths, jnp.arange(rows, dtype=jnp.int32),
            jnp.asarray([chunk, chunk - 2, 1, 0][:rows], jnp.int32))
    pos = kv_cache.positions(step, rows, chunk)
    attn = deepseek_v3.MLAttention(cfg)
    params = nn.meta.unbox(attn.init(jax.random.PRNGKey(2), x, pos))
    return cfg, attn, params, x, pos, step


@pytest.mark.parametrize("rows,chunk", [(4, 8), (3, 1)],
                         ids=["chunk", "decode"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_absorbed_query_reaches_attend_at_the_stored_width(
        monkeypatch, dtype, rows, chunk):
    """ONE contraction makes a chunk's query as the pool is read:
    ``attend`` is handed q at ``latent_stored_dim(rank + rope)`` — its
    rope features ``rope(q_rope)`` value for value (a feature times 1.0
    summed with zeros), its trailing features exact zeros — and the key
    at the published width, which ``attend`` alone pads.  A dispatch of
    fewer tokens than a head's query has features (3 against 24: a
    decode step) hands it ``[q' | q_rope]`` at the published width."""
    cfg, attn, params, x, pos, step = _one_block_over_a_padded_pool(
        dtype, monkeypatch, rows, chunk)
    h, rank, dn, dr = cfg.num_heads, cfg.kv_lora_rank, \
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    width = 48 if rows * chunk >= dn + dr else 40
    seen, roped = {}, []
    real_attend = kv_cache.attend
    real_rope = deepseek_v3.apply_rotary_emb_interleaved

    def attend(q, k, v, positions, cache, **kw):
        seen.update(q=q, k=k, **kw)
        return real_attend(q, k, v, positions, cache, **kw)

    def rope(x, positions, base):
        roped.append(real_rope(x, positions, base=base))
        return roped[-1]
    monkeypatch.setattr(kv_cache, "attend", attend)
    monkeypatch.setattr(deepseek_v3, "apply_rotary_emb_interleaved", rope)
    out, new = attn.apply(params, x, pos, step)
    assert out.shape == x.shape and set(new) == {"c_pages"}
    q = seen["q"]
    assert q.shape == (rows, chunk, h, width) and q.dtype == dtype
    assert seen["k"].shape == (rows, chunk, 40) and seen["value_dim"] == rank
    q = np.asarray(q, np.float32)
    # rope() ran on the shared key, then on the heads' q_rope
    q_rope = np.asarray(roped[-1], np.float32)
    assert q_rope.shape == (rows, chunk, h, dr)
    assert np.array_equal(q[..., rank:rank + dr], q_rope)
    assert np.abs(q_rope).max() > 0.01 and not q[..., rank + dr:].any()
    # and the leading features are W_UK,h q_nope,h
    wq = np.asarray(params["params"]["wq"]["kernel"], np.float32)
    w_uk = np.asarray(params["params"]["wkv_b"], np.float32)[..., :dn]
    q_nope = (np.asarray(x, np.float32) @ wq).reshape(
        rows, chunk, h, dn + dr)[..., :dn]
    if dtype == jnp.bfloat16:       # the projection's own rounding
        q_nope = np.asarray(jnp.asarray(q_nope, dtype), np.float32)
    want = np.einsum("blhd,rhd->blhr", q_nope, w_uk)
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -7 * np.abs(want).max()
    assert np.abs(q[..., :rank] - want).max() <= tol


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("kernel", ["auto", "force"])
def test_no_pass_widens_the_absorbed_query_in_the_prefill_program(
        monkeypatch, kernel):
    """A count over the jaxpr of the tiny model's ``[4, 8]`` prefill
    program (never a time): no ``pad`` and no ``concatenate`` yields a
    ``[rows, chunk, heads, w]`` array at the cached vector's width (40)
    or the pool's (48) — the absorption writes the query at the stored
    width itself.  What IS concatenated a layer is its 24-wide input,
    ``[q_nope | rope(q_rope)]``, and the chunk's 40-wide key a token,
    which is what is padded."""
    monkeypatch.setattr(kvq, "LANES", 16)
    engine = build_engine(paged_kernel=kernel)
    cfg = engine.module.cfg
    pools = engine.init_paged_cache(12, 8)
    assert pools["layers"][0]["c_pages"].shape[-1] == 48

    def program(params, layers, ids):
        step = kv_cache.prefill_step(
            layers, jnp.arange(12, dtype=jnp.int32).reshape(4, 3),
            jnp.asarray([5, 0, 9, 2], jnp.int32),
            jnp.asarray([1, 0, 3, 2], jnp.int32),
            jnp.asarray([8, 6, 1, 0], jnp.int32))
        with engine._serving_scope():
            logits, new = engine.module.apply({"params": params}, ids,
                                              cache=step)
        return logits, new.pools
    jaxpr = jax.make_jaxpr(program)(engine.params, pools["layers"],
                                    jnp.zeros((4, 8), jnp.int32))
    made = {}
    for eqn in _equations(jaxpr.jaxpr):
        if eqn.primitive.name in ("pad", "concatenate"):
            for var in eqn.outvars:
                key = (eqn.primitive.name,) + tuple(var.aval.shape)
                made[key] = made.get(key, 0) + 1
    head = (4, 8, cfg.num_heads)
    for width in (40, 48):
        assert not [k for k in made if k[1:] == head + (width,)], made
    assert made[("concatenate",) + head + (24,)] == cfg.num_layers
    assert made[("concatenate", 4, 8, 40)] == cfg.num_layers
    assert made[("pad", 4, 8, 48)] == cfg.num_layers


# -------------------------------------- (d): the shares of the experts

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(engine):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one routed
    layer; each routes over all 16 and computes its own part, and each
    computes the shared MLP whole.  The four routed parts, with the
    router and the shared MLP counted once, are the uncut reference's
    layer."""
    cfg = engine.module.cfg
    rng = jax.random.PRNGKey(7)
    uncut = dataclasses.replace(cfg, num_held_experts=16)
    u = jax.random.normal(rng, (1, 33, cfg.hidden_size))
    params = DeepseekMoE(uncut).init(rng, u)["params"]
    params = jax.tree.map(lambda a: getattr(a, "value", a), params,
                          is_leaf=lambda a: hasattr(a, "value"))
    params["e_score_correction_bias"] = 0.2 * jax.random.normal(rng, (16,))
    w = {"router": params["router"],
         "bias": params["e_score_correction_bias"],
         "w_up": params["w_up"], "w_down": params["w_down"]}
    w.update({"shared_" + n: params["shared"][n]["kernel"]
              for n in ("w_gate", "w_up", "w_down")})
    no_shared = dataclasses.replace(cfg, n_shared_experts=0)
    with jax.default_matmul_precision("highest"):
        want = REF.routed_ffn(u[0], w, per_token=cfg.num_experts_per_tok,
                              scaling=cfg.routed_scaling_factor,
                              first_held=0)
        shared = REF.mlp(u[0], w, "shared_")
        assert float(jnp.abs(shared).max()) > 0
        total = jnp.zeros_like(u)
        for first in (0, 4, 8, 12):
            part = dict(params, w_up=params["w_up"][first:first + 4],
                        w_down=params["w_down"][first:first + 4])
            whole, _ = DeepseekMoE(dataclasses.replace(
                cfg, first_held_expert=first)).apply({"params": part}, u)
            part.pop("shared")
            routed, _ = DeepseekMoE(dataclasses.replace(
                no_shared, first_held_expert=first)).apply(
                    {"params": part}, u)
            # this chip's output is its routed part plus the shared MLP
            np.testing.assert_allclose(whole[0] - routed[0], shared,
                                       atol=TOL, rtol=0)
            assert float(jnp.abs(routed).max()) > 0
            total = total + routed
    np.testing.assert_allclose(total[0] + shared, want, atol=TOL, rtol=0)


# --------- (f): one latent head, d_k 576, value 512 from the key block

def _latent_pool(rng, stored, ps, dtype):
    c = rng.standard_normal((12, ps, stored))
    c[..., 576:] = 0                      # the pool's padding
    return jnp.asarray(c, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_shared_read_through_the_paged_decode_kernel(dtype):
    """32 query heads over ONE latent head (a group of 32), the key 576
    wide stored at 640, the value its leading 512 features (the
    published geometry): the kernel in interpret mode against
    ops/attention/reference.py over the gathered pages."""
    rng = np.random.default_rng(0)
    h, w, dv, ps, slots = 32, 640, 512, 16, 3
    pool = _latent_pool(rng, w, ps, dtype)
    table = jnp.asarray(rng.permutation(12)[:9].reshape(slots, 3), jnp.int32)
    pos = jnp.asarray([0, 17, 47], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, 1, h, w)), dtype)
    scale = 192 ** -0.5
    got = paged_decode_attention(q, pool, None, table, pos, value_dim=dv,
                                 scale=scale, force_kernel=True,
                                 interpret=True)
    assert got.shape == (slots, 1, h, dv)
    k = pool[table].reshape(slots, -1, 1, w)
    want = attn_ref.decode_attention_reference(
        q, jnp.repeat(k, h, 2), jnp.repeat(k[..., :dv], h, 2), pos + 1,
        scale=scale)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 1e-5 if dtype == jnp.float32 else 2 * 2.0 ** -8 * np.abs(want).max()
    assert np.abs(got - want).max() <= tol
    # the jnp fallback takes the latent leaf too
    ref = paged_decode_attention(q, pool, None, table, pos, value_dim=dv,
                                 scale=scale)
    assert np.abs(np.asarray(ref, np.float32) - want).max() <= tol
    # an inactive slot's row is zeros, an active one's unchanged
    act = paged_decode_attention(
        q, pool, None, table, pos, value_dim=dv, scale=scale,
        force_kernel=True, interpret=True,
        active=jnp.asarray([True, False, True]))
    act = np.asarray(act, np.float32)
    assert np.abs(act[1]).max() == 0 and np.array_equal(act[0], got[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_shared_read_through_the_paged_prefill_kernel(dtype):
    rng = np.random.default_rng(1)
    h, w, dv, ps, l = 32, 640, 512, 16, 8
    pools = {"c_pages": _latent_pool(rng, w, ps, dtype)}
    table = jnp.asarray(rng.permutation(12)[:9].reshape(3, 3), jnp.int32)
    lengths = jnp.asarray([0, 21, ps], jnp.int32)
    rows = jnp.asarray([1, 2, 0], jnp.int32)
    count = jnp.asarray([l, l - 3, 1], jnp.int32)
    step = kv_cache.prefill_step(pools, table, lengths, rows, count)
    q = jnp.asarray(rng.standard_normal((3, l, h, 576)), dtype)
    c = jnp.asarray(rng.standard_normal((3, l, 576)), dtype)
    pos = kv_cache.positions(step, 3, l)
    outs = {}
    for mode in ("force", "reference"):
        with kernel_mode_scope(mode):
            outs[mode], new = kv_cache.attend(q, c, None, pos, step,
                                              value_dim=dv,
                                              scale=192 ** -0.5)
        assert set(new) == {"c_pages"} and outs[mode].shape == (3, l, h, dv)
    got, want = (np.asarray(outs[m], np.float32)
                 for m in ("force", "reference"))
    tol = 1e-5 if dtype == jnp.float32 else 2 * 2.0 ** -8 * np.abs(want).max()
    for r, n in enumerate([l, l - 3, 1]):       # the valid columns
        assert np.abs(got[r, :n] - want[r, :n]).max() <= tol
    # and the reference path is plain attention over what was written
    pool = np.asarray(new["c_pages"], np.float32)
    r, slot, start, n = 1, 2, ps, l - 3     # row 1 is slot 2, at 16
    keys = pool[np.asarray(table)[slot]].reshape(-1, w)[:start + n]
    s = np.einsum("qhd,kd->hqk", np.asarray(q[r, :n], np.float32),
                  keys[:, :576]) * 192 ** -0.5
    mask = np.arange(start + n)[None, :] <= (start + np.arange(n))[:, None]
    s = np.where(mask[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    plain = np.einsum("hqk,kd->qhd", p, keys[:, :dv])
    assert np.abs(want[r, :n] - plain).max() <= \
        (1e-4 if dtype == jnp.float32 else 4 * tol)


def test_the_latent_form_of_attend_says_what_it_needs():
    q, c = jnp.zeros((1, 1, 4, 40)), jnp.zeros((1, 1, 40))
    pos = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(AssertionError, match="latent page pool only"):
        kv_cache.attend(q, c, None, pos, None, value_dim=32, scale=1.0)
    pages = kv_cache.decode_step(
        kvq.paged_pool_layer(2, 8, 1, 40, jnp.float32),
        jnp.zeros((1, 2), jnp.int32), jnp.zeros(1, jnp.int32),
        jnp.ones(1, bool))
    with pytest.raises(AssertionError, match="latent page pool only"):
        kv_cache.attend(q, c, None, pos, pages, value_dim=32, scale=1.0)


def test_the_kernel_decision_reports_the_latent_path():
    """One latent head under 32 query heads is decided like any other
    geometry, and the engine reports it for health(): on the tests' 8
    virtual devices (a ``data`` axis of 8) the kernels run per shard,
    the latent leaf whole on each."""
    on = paged_kernel_decision(num_heads=32, num_kv_heads=1, page_size=128,
                               backend="tpu")
    assert (on["path"], on["dispatch"]) == ("kernel", "direct")
    dec = build_engine(paged_kernel="force").paged_kernel_decision(
        page_size=8)
    assert dec["heads"] == [4, 1]
    assert dec["path"] == dec["multi_token"]["path"] == "kernel"
    assert dec["dispatch"] in ("direct", "shard_map")
    off = build_engine(paged_kernel="reference").paged_kernel_decision(
        page_size=8)
    assert off["path"] == off["multi_token"]["path"] == "reference"


# ---- (g): prefix cache, verify, preemption; the refusals, each by name

@pytest.fixture(scope="module")
def served(engine):
    """Staggered admissions over 3 slots and a 9-page pool, a prefix
    cache on and prompts that share their first 16 tokens: chunked
    prefill beside decode, fused horizons, slot reuse, cache hits with a
    copy-on-write tail, and a pool small enough to force a
    recompute-preemption."""
    rng = np.random.default_rng(0)
    sched = ServingScheduler(engine, num_slots=3, num_pages=9, page_size=8,
                             max_pages_per_slot=6, prefill_chunk=8,
                             decode_horizon_steps=4, prefix_cache=True)
    assert sched.prefix_cache is not None and not sched.slot_state
    lens = [(21, 9), (19, 12), (33, 10), (20, 14), (27, 9), (28, 16)]
    shared = rng.integers(0, 256, 20).astype(np.int32)
    prompts = [np.concatenate([shared[:min(n, 20)],
                               rng.integers(0, 256, max(n - 20, 0))
                               .astype(np.int32)]) for n, _ in lens]
    reqs = [sched.submit(p, m) for p, (_, m) in zip(prompts[:3], lens[:3])]
    for _ in range(3):
        sched.step()
    reqs += [sched.submit(p, m) for p, (_, m) in zip(prompts[3:], lens[3:])]
    sched.run()
    return sched, prompts, reqs


def test_prefix_cache_and_preemption_run_over_latent_pages(engine, served):
    sched, prompts, reqs = served
    s = sched.summary()
    assert s["preemptions"] > 0, "the pool was sized to preempt"
    assert sched.metrics.prefix_hits > 0, "the prompts share 16 tokens"
    assert engine.serving_page_copy_compile_count() <= 1
    for p, r in zip(prompts, reqs):
        assert r.state == "finished"
        out = engine.generate(p[None], max_new_tokens=r.max_new_tokens,
                              do_sample=False)
        assert list(np.asarray(out)[0, len(p):]) == list(r.out_tokens)
    # the counters MLA's metrics read
    assert s["kv_latent_bytes_per_token"] == 3 * 40 * 4 == \
        s["kv_stored_bytes_per_token"]
    assert s["kv_pool_bytes"] == 9 * engine.kv_page_bytes(8) == \
        9 * 8 * s["kv_stored_bytes_per_token"]
    assert s["state_pool_bytes"] == 0 and s["prefix_cache_refused"] == 0
    assert s["decode_kv_tokens"] > 0 and s["prefill_kv_pairs"] > 0
    # prompts of at most 33 tokens in slots of 48: the dispatches never
    # reach the whole of their page tables
    assert 0 < s["prefill_live_page_share"] < 1
    assert 0 < s["moe_held_assignments"] < s["moe_assignments"]
    h = sched.health()
    assert h["paged_attention"]["heads"] == [4, 1]
    assert h["kv_dtype"] == "float32"


def test_a_verify_step_runs_over_latent_pages(engine):
    """Speculative decoding (prompt-lookup drafts over a repeated motif)
    stays token-exact: the verify dispatch writes and reads latent pages
    as prefill does."""
    motif = np.array([7, 3, 9, 4, 11, 5], np.int32)
    prompt = np.tile(motif, 5)
    sched = ServingScheduler(engine, num_slots=2, num_pages=16, page_size=8,
                             max_pages_per_slot=8, prefill_chunk=8,
                             spec_decode="ngram", spec_k=4)
    req = sched.submit(prompt, max_new_tokens=12)
    got = sched.run()[req.rid]
    out = engine.generate(prompt[None], max_new_tokens=12, do_sample=False)
    assert list(np.asarray(out)[0, len(prompt):]) == got
    assert engine.serving_verify_compile_count() >= 1
    assert engine.slot_state_refusal("spec_decode") is None
    assert engine.slot_state_refusal("prefix_cache") is None


def test_what_a_latent_pool_cannot_do_is_refused_by_name(engine):
    with pytest.raises(ValueError, match="kv_dtype='int8' over a latent"):
        engine.init_paged_cache(4, 8, kv_dtype="int8")
    with pytest.raises(ValueError, match="over a latent page pool"):
        engine.kv_page_bytes(8, kv_dtype="fp8")
    with pytest.raises(ValueError, match="handoff cannot serve this model: "
                                         "DeepseekV3 keeps latent pages"):
        ServingScheduler(engine, num_slots=2, num_pages=8, page_size=8,
                         max_pages_per_slot=4, prefill_chunk=8,
                         on_handoff=lambda *a: None)
    with pytest.raises(ValueError, match="latent pages"):
        engine.export_page_chain(engine.init_paged_cache(4, 8), [0])

    # a `model` axis over the one-head pool: refused before any compile
    class Mesh:
        shape = {"data": 1, "model": 2}
    with pytest.raises(ValueError, match="ONE head a token"):
        ServingShardingConfig().validate(Mesh(), 1)
    ServingShardingConfig().validate(Mesh(), 2)      # two heads split
