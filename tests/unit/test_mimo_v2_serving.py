"""A tiny MiMo-V2 (full and sliding-window attention layers by a pattern,
each kind with its own KV-head count, keys of 24 beside values of 16, a
sink logit a head in the window layers, sigmoid-routed SwiGLU experts of
which 4 of 16 are held) through the normal serving path —
``init_inference`` + ``ServingScheduler`` — against the plain
reference's full forward (benchmarks/chip/reference_mimo_v2.py, loaded
from there).

Logits are compared, never sampled tokens.  ``TOL`` = 2e-6 absolute on
logits at the tiny preset's scale of ~0.6: float32 rounding through
seven blocks reads 3e-7 here (full forward, chunked prefill through
pages and rings, decode alike); the least of the reference's terms
dropped (the partial rotary) moves a logit by 5e-3.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import mimo_v2
from deepspeed_tpu.models.mimo_v2 import MiMoMoE, MiMoV2, MiMoV2Config, \
    mimo_v2_tiny
from deepspeed_tpu.ops.attention import kv_cache, reference as attn_ref, \
    window as window_ops
from deepspeed_tpu.ops.attention.decode import (kernel_mode_scope,
                                                paged_decode_attention)
from deepspeed_tpu.serving import ServingScheduler

TOL = 2e-6
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "reference_mimo_v2", os.path.join(REPO, "benchmarks", "chip",
                                      "reference_mimo_v2.py"))
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)


def reference_args(cfg):
    return dict(layer_pattern=cfg.layer_pattern, moe_pattern=cfg.moe_pattern,
                eps=cfg.rms_eps, heads=cfg.num_heads,
                kv_heads=cfg.num_kv_heads, swa_kv_heads=cfg.swa_num_kv_heads,
                head_dim=cfg.head_dim, v_head_dim=cfg.v_head_dim,
                rotary_factor=cfg.partial_rotary_factor,
                theta=cfg.rope_theta, swa_theta=cfg.swa_rope_theta,
                window=cfg.sliding_window,
                value_scale=cfg.attention_value_scale,
                swa_sink=cfg.add_swa_attention_sink_bias,
                full_sink=cfg.add_full_attention_sink_bias,
                per_token=cfg.num_experts_per_tok, scaling=None,
                first_held=cfg.first_held_expert)


def build_engine(cfg=None, **kw):
    eng = deepspeed_tpu.init_inference(
        MiMoV2(cfg or mimo_v2_tiny()), dtype="float32",
        kv_cache_dtype="float32", **kw)
    eng.init_params(seed=3)
    # the correction bias is zeros at a seeded init: give it values, so
    # that the choice it makes (and only the choice) is under test
    params = jax.tree.map(lambda a: a, eng.params)
    for i, routed in enumerate(eng.module.cfg.moe_pattern):
        if routed:
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


# 61 tokens: longer than the window (16), than a page (8), than both
IDS = np.random.default_rng(5).integers(0, 256, 61).astype(np.int32)


@pytest.fixture(scope="module")
def want(engine):
    return reference_logits(engine.params, IDS,
                            reference_args(engine.module.cfg))


def test_the_config_holds_its_patterns_and_the_published_widths():
    with pytest.raises(ValueError, match="layer_pattern"):
        mimo_v2_tiny(layer_pattern=(0, 1))
    with pytest.raises(ValueError, match="held experts"):
        mimo_v2_tiny(first_held_expert=14)
    cfg = MiMoV2Config()            # MiMo-V2-Flash as published
    assert cfg.rotary_dim == 64 and cfg.routed_scaling_factor == 1.0
    # a key of 192 takes two whole lane tiles in the page pool
    assert cfg.k_pool_dim == 256 and mimo_v2_tiny().k_pool_dim == 24
    assert cfg.layer_pattern.count(1) == 39 and cfg.num_kv_layers == 9
    assert (cfg.kv_heads(0), cfg.kv_heads(1)) == (4, 8)
    # a configuration file hands lists over
    assert mimo_v2_tiny(layer_pattern=[0, 1, 1, 1, 1, 0, 1]).layer_pattern \
        == (0, 1, 1, 1, 1, 0, 1)
    assert mimo_v2_tiny().rotary_dim == 8


def test_full_forward_logits_are_the_references(engine, want):
    got = engine.module.apply({"params": engine.params}, IDS[None])[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("term", ["sink", "value_scale", "partial_rotary",
                                  "window_edge", "score_bias"])
def test_a_reference_with_one_term_dropped_fails(engine, want, term):
    """The sink left out of the softmax, v unscaled, the rotary over the
    whole head, a window of one position more, the correction bias left
    out of the choice: the comparison sees each."""
    wrong = reference_logits(engine.params, IDS, dict(
        reference_args(engine.module.cfg), drop=(term,)))
    assert np.abs(want - wrong).max() > 100 * TOL


def paged_decode_logits(engine, tok, active, table, lengths, pools):
    """One decode step of the MODEL through pools and rings (the
    engine's decode primitives return sampled tokens)."""
    step = kv_cache.decode_step(pools["layers"], jnp.asarray(table),
                                jnp.asarray(lengths), jnp.asarray(active))
    with engine._serving_scope():
        logits, new = engine.module.apply(
            {"params": engine.params}, jnp.asarray(tok)[:, None], cache=step)
    return np.asarray(logits[:, 0]), new.pools, np.asarray(new.lengths)


@pytest.mark.parametrize("chunk,kernel,lanes", [
    (4, "auto", 128), (8, "auto", 128), (24, "auto", 128),
    (8, "force", 128), (8, "auto", 16), (24, "force", 16)])
def test_chunked_prefill_then_decode_through_pool_and_ring(
        want, monkeypatch, chunk, kernel, lanes):
    """48 prompt tokens (three windows, six pages) in chunks of
    ``chunk`` — 24 is longer than the window — into slot 2, every
    chunk's boundary logits the reference's; then teacher-forced decode
    steps.  ``force`` runs the two paged Pallas kernels at d_k 24 /
    d_v 16 in interpret mode for the full layers (fewer decode steps:
    each traces the interpreter anew); a lane tile of 16 stores the key
    of 24 zero-padded to 32 in the page pool, as the published 192 is
    stored at 256."""
    monkeypatch.setattr(mimo_v2, "LANES", lanes)
    engine = build_engine(mimo_v2_tiny(), paged_kernel=kernel)
    one = engine.init_paged_cache(1, 8, num_slots=1)["layers"]
    assert one[0]["k_pages"].shape[-1] == (24 if lanes == 128 else 32)
    # the module's own page bytes: keys at the pool's width
    assert engine.kv_page_bytes(8) == sum(
        e[n].nbytes for e in one for n in ("k_pages", "v_pages") if n in e)
    pools = engine.init_paged_cache(12, 8, num_slots=3)
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
    for t in range(n_prompt, len(IDS) if kernel == "auto" else n_prompt + 3):
        tok = np.zeros(3, np.int32)
        tok[2] = IDS[t]
        logits, pools, new_len = paged_decode_logits(
            engine, tok, [False, False, True], table, lengths, pools)
        assert list(new_len) == [0, 0, lengths[2] + 1]   # advanced ONCE
        lengths = new_len
        np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0)


def test_an_entry_is_pages_or_a_ring_by_the_layers_kind(engine):
    pools = engine.init_paged_cache(8, 8, num_slots=3)
    cfg = engine.module.cfg
    for kind, routed, entry in zip(cfg.layer_pattern, cfg.moe_pattern,
                                   pools["layers"]):
        names = {"k_ring", "v_ring"} if kind else {"k_pages", "v_pages"}
        assert set(entry) == names | ({"routing"} if routed else set())
        if kind:        # 16 positions a slot, 4 KV heads, 24 | 16 wide
            assert entry["k_ring"].shape == (3, 16, 4, 24)
            assert entry["v_ring"].shape == (3, 16, 4, 16)
        else:           # pages, 2 KV heads
            assert entry["k_pages"].shape == (8, 8, 2, 24)
            assert entry["v_pages"].shape == (8, 8, 2, 16)
    dense = mimo_v2.init_kv_cache(cfg, 2, max_len=16, dtype=jnp.float32)
    assert [e["k"].shape[2] for e in dense["layers"]] == \
        [2, 4, 4, 4, 4, 2, 4]
    assert dense["layers"][0]["v"].shape == (2, 16, 2, 16)
    with pytest.raises(ValueError, match="num_slots"):
        engine.init_paged_cache(8, 8)


def test_a_slot_reused_by_a_shorter_request_sees_nothing_of_the_last(
        engine, want):
    """Another request's 37 tokens through slot 1 (its ring wrapped
    twice), then OUR first 11 tokens into the same slot from position 0,
    its pages handed over too: the ring is NOT cleared, and the boundary
    logits are the reference's of our prompt alone."""
    pools = engine.init_paged_cache(8, 8, num_slots=2)
    table = np.array([[8] * 6, [0, 1, 2, 3, 4, 5]], np.int32)
    lengths = np.zeros(2, np.int32)
    other = np.random.default_rng(9).integers(0, 256, (1, 40)).astype(
        np.int32)
    _, pools = engine.prefill_into_slots(other, [1], [37], table, lengths,
                                         pools)
    held = np.asarray(pools["layers"][1]["k_ring"][1])
    assert (np.abs(held).max(axis=(1, 2)) > 0).all()     # every row used
    logits, pools = engine.prefill_into_slots(IDS[None, :16], [1], [11],
                                              table, lengths, pools)
    np.testing.assert_allclose(logits[0], want[10], atol=TOL, rtol=0)
    # rows 11..15 still hold the last tenant's keys: masked, not cleared
    assert np.array_equal(held[11:],
                          np.asarray(pools["layers"][1]["k_ring"][1, 11:]))


def test_an_idle_slots_ring_and_pages_are_bit_identical_after_a_decode_step(
        engine):
    pools = engine.init_paged_cache(8, 8, num_slots=2)
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
        for name in ("k_ring", "v_ring"):
            if name in old:
                assert np.array_equal(old[name][1], np.asarray(new[name][1]))
                assert not np.array_equal(old[name][0],
                                          np.asarray(new[name][0]))
        for name in ("k_pages", "v_pages"):
            if name in old:    # slot 1's pages and those nobody holds
                assert np.array_equal(old[name][2:],
                                      np.asarray(new[name][2:]))
                assert not np.array_equal(old[name][1],
                                          np.asarray(new[name][1]))


def test_a_padding_row_writes_nothing_and_a_verify_step_raises(engine):
    pools = engine.init_paged_cache(8, 8, num_slots=2)
    table = np.array([[0, 1, 8, 8], [2, 3, 8, 8]], np.int32)
    before = jax.tree.map(np.array, pools)
    ids = np.stack([IDS[:8], IDS[8:16]])
    # row 1 is padding (n_valid 0) and carries a live slot id
    _, after = engine.prefill_into_slots(ids, [0, 0], [8, 0], table,
                                         np.zeros(2, np.int32), pools)
    ring = after["layers"][1]["k_ring"]
    assert np.array_equal(before["layers"][1]["k_ring"][1],
                          np.asarray(ring[1]))
    assert float(jnp.abs(ring[0, :8]).min()) > 0
    assert float(jnp.abs(ring[0, 8:]).max()) == 0
    step = kv_cache.layer_view(kv_cache.verify_step(
        after["layers"], table, np.zeros(2, np.int32),
        np.ones(2, np.int32)), 1)
    q = jnp.zeros((2, 2, 8, 24))
    kv = jnp.zeros((2, 2, 4, 24))
    with pytest.raises(NotImplementedError, match="window-ring"):
        kv_cache.attend(q, kv, kv[..., :16], jnp.zeros((2, 2), jnp.int32),
                        dataclasses.replace(step, layers={
                            n: a for n, a in step.layers.items()
                            if n != "routing"}), window=16)
    # a window layer handed pages says what it wanted
    pages = kv_cache.layer_view(kv_cache.decode_step(
        after["layers"], table, np.zeros(2, np.int32), np.ones(2, bool)), 0)
    with pytest.raises(ValueError, match="a ring a slot, not pages"):
        kv_cache.attend(q[:, :1], kv[:, :1], kv[:, :1, :, :16],
                        jnp.zeros((2, 1), jnp.int32), pages, window=16)


# ------------------------------------------ the ring against a plain mask

@pytest.mark.parametrize("chunk", [3, 16, 40])
def test_the_ring_is_a_window_mask_at_any_chunk_length(chunk):
    """Chunks of any length (40 is 2.5 windows) through one slot's ring,
    then decode steps, against masked attention over the whole
    sequence."""
    rng = np.random.default_rng(chunk)
    t, w, h, kv_h, dk, dv = 83, 16, 4, 2, 24, 16
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, n, d)), jnp.float32)
               for n, d in ((h, dk), (kv_h, dk), (kv_h, dv)))
    sink = jnp.asarray(rng.standard_normal(h), jnp.float32)
    want = np.asarray(window_ops.attend_fresh(q, k, v, window=w, sink=sink))
    entry = window_ops.init_ring(2, w, kv_h, dk, dv, jnp.float32)
    lengths = np.zeros(2, np.int32)
    n_prompt = 70
    for at in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - at)
        pad = [(0, 0), (0, chunk - n), (0, 0), (0, 0)]
        step = kv_cache.prefill_step(
            entry, jnp.zeros((2, 1), jnp.int32), jnp.asarray(lengths),
            jnp.asarray([1]), jnp.asarray([n]))
        pos = kv_cache.positions(step, 1, chunk)
        got, entry = kv_cache.attend(
            *(jnp.pad(a[:, at:at + n], pad) for a in (q, k, v)), pos, step,
            window=w, sink=sink)
        np.testing.assert_allclose(got[0, :n], want[0, at:at + n],
                                   atol=1e-5, rtol=0)
        lengths[1] += n
    for at in range(n_prompt, t):
        step = kv_cache.decode_step(
            entry, jnp.zeros((2, 1), jnp.int32), jnp.asarray(lengths),
            jnp.asarray([False, True]))
        pos = kv_cache.positions(step, 2, 1)
        two = [jnp.concatenate([a[:, at:at + 1]] * 2) for a in (q, k, v)]
        got, entry = kv_cache.attend(*two, pos, step, window=w, sink=sink)
        np.testing.assert_allclose(got[1, 0], want[0, at], atol=1e-5, rtol=0)
        lengths[1] += 1
    assert float(jnp.abs(entry["k_ring"][0]).max()) == 0   # slot 0 idle


# -------------------------------------- the shares of the experts add up

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(engine):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one routed
    layer; each routes over all 16 and computes its own part.  The four
    parts, the router counted once, are the uncut reference's layer."""
    cfg = engine.module.cfg
    rng = jax.random.PRNGKey(7)
    uncut = dataclasses.replace(cfg, num_held_experts=16)
    u = jax.random.normal(rng, (1, 33, cfg.hidden_size))
    params = MiMoMoE(uncut).init(rng, u)["params"]
    params = jax.tree.map(lambda a: getattr(a, "value", a), params,
                          is_leaf=lambda a: hasattr(a, "value"))
    params["e_score_correction_bias"] = 0.2 * jax.random.normal(rng, (16,))
    w = {"router": params["router"],
         "bias": params["e_score_correction_bias"],
         "w_up": params["w_up"], "w_down": params["w_down"]}
    with jax.default_matmul_precision("highest"):
        want = REF.routed_ffn(u[0], w, per_token=cfg.num_experts_per_tok,
                              scaling=None, first_held=0)
        total = jnp.zeros_like(u)
        for first in (0, 4, 8, 12):
            share = dataclasses.replace(cfg, first_held_expert=first)
            part = dict(params, w_up=params["w_up"][first:first + 4],
                        w_down=params["w_down"][first:first + 4])
            out, _ = MiMoMoE(share).apply({"params": part}, u)
            assert float(jnp.abs(out).max()) > 0
            total = total + out
    np.testing.assert_allclose(total[0], want, atol=TOL, rtol=0)


# ----------------------- keys of 192 beside values of 128 in the kernels

def _gathered(pools, table):
    return tuple(pools[n][table].reshape(table.shape[0], -1,
                                         *pools[n].shape[2:])
                 for n in ("k_pages", "v_pages"))


def _pools(rng, kv_h, dk, dv, ps, dtype):
    return {"k_pages": jnp.asarray(rng.standard_normal((12, ps, kv_h, dk)),
                                   dtype),
            "v_pages": jnp.asarray(rng.standard_normal((12, ps, kv_h, dv)),
                                   dtype)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_keys_of_192_and_values_of_128_through_the_paged_decode_kernel(
        dtype):
    """64 query heads over 4 KV heads (a group of 16), d_k 192, d_v 128
    (the published geometry of the full layers): the kernel in interpret
    mode against ops/attention/reference.py over the gathered pages."""
    rng = np.random.default_rng(0)
    h, kv_h, dk, dv, ps, slots = 64, 4, 192, 128, 16, 3
    pools = _pools(rng, kv_h, dk, dv, ps, dtype)
    table = jnp.asarray(rng.permutation(12)[:9].reshape(slots, 3), jnp.int32)
    pos = jnp.asarray([0, 17, 47], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, 1, h, dk)), dtype)
    got = paged_decode_attention(q, pools["k_pages"], pools["v_pages"],
                                 table, pos, force_kernel=True,
                                 interpret=True)
    assert got.shape == (slots, 1, h, dv)
    k, v = _gathered(pools, table)
    want = attn_ref.decode_attention_reference(
        q, jnp.repeat(k, h // kv_h, 2), jnp.repeat(v, h // kv_h, 2), pos + 1)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 1e-5 if dtype == jnp.float32 else 2 * 2.0 ** -8 * np.abs(want).max()
    assert np.abs(got - want).max() <= tol
    # the jnp fallback takes the two widths too
    ref = paged_decode_attention(q, pools["k_pages"], pools["v_pages"],
                                 table, pos)
    assert np.abs(np.asarray(ref, np.float32) - want).max() <= tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_keys_of_192_and_values_of_128_through_the_paged_prefill_kernel(
        dtype):
    rng = np.random.default_rng(1)
    h, kv_h, dk, dv, ps, l = 64, 4, 192, 128, 16, 8
    pools = _pools(rng, kv_h, dk, dv, ps, dtype)
    table = jnp.asarray(rng.permutation(12)[:9].reshape(3, 3), jnp.int32)
    lengths = jnp.asarray([0, 21, ps], jnp.int32)
    rows = jnp.asarray([1, 2, 0], jnp.int32)
    count = jnp.asarray([l, l - 3, 1], jnp.int32)
    step = kv_cache.prefill_step(pools, table, lengths, rows, count)
    q, k, v = (jnp.asarray(rng.standard_normal((3, l, n, d)), dtype)
               for n, d in ((h, dk), (kv_h, dk), (kv_h, dv)))
    pos = kv_cache.positions(step, 3, l)
    outs = {}
    for mode in ("force", "reference"):
        with kernel_mode_scope(mode):
            outs[mode], new = jax.jit(lambda q, k, v: kv_cache._paged_multi(
                q, k, v, pos, step, None))(q, k, v)
    k_all, v_all = _gathered(new, table[rows])
    k_pos = jnp.arange(k_all.shape[1])
    bias = jnp.where(k_pos[None, None, :] <= pos[:, :, None], 0.0,
                     jnp.finfo(jnp.float32).min)[:, None]
    want = attn_ref.mha_reference(
        q, jnp.repeat(k_all, h // kv_h, 2), jnp.repeat(v_all, h // kv_h, 2),
        causal=False, bias=bias)
    valid = np.arange(l)[None, :] < np.asarray(count)[:, None]
    want = np.asarray(want, np.float32)[valid]
    tol = 1e-5 if dtype == jnp.float32 else 2 * 2.0 ** -8 * np.abs(want).max()
    for mode, got in outs.items():
        assert got.shape == (3, l, h, dv)
        assert np.abs(np.asarray(got, np.float32)[valid] - want).max() \
            <= tol, mode


# ------------------------------------------------ the normal serving path

def margins(engine, prompt, out_tokens):
    ids = np.concatenate([prompt, out_tokens]).astype(np.int32)
    lg = reference_logits(engine.params, ids,
                          reference_args(engine.module.cfg))
    pos = len(prompt) - 1 + np.arange(len(out_tokens))
    return lg[pos].max(-1) - lg[pos, out_tokens]


LENS = [(5, 9), (19, 12), (33, 10), (8, 14), (27, 9), (12, 16)]


@pytest.fixture(scope="module")
def served(engine):
    """Staggered admissions over 3 slots and a 9-page pool: chunked
    prefill beside decode, fused horizons, slot reuse by shorter
    requests, and a pool small enough to force a recompute-preemption."""
    rng = np.random.default_rng(0)
    before = (engine.serving_decode_multi_compile_count(),
              engine.serving_prefill_compile_count())
    sched = ServingScheduler(engine, num_slots=3, num_pages=9, page_size=8,
                             max_pages_per_slot=6, prefill_chunk=8,
                             decode_horizon_steps=4, prefix_cache=True)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n, _ in LENS]
    reqs = [sched.submit(p, m) for p, (_, m) in zip(prompts[:3], LENS[:3])]
    for _ in range(3):
        sched.step()
    reqs += [sched.submit(p, m) for p, (_, m) in zip(prompts[3:], LENS[3:])]
    sched.run()
    compiled = (engine.serving_decode_multi_compile_count() - before[0],
                engine.serving_prefill_compile_count() - before[1])
    return sched, prompts, reqs, compiled


def test_served_tokens_are_the_references_argmax_to_rounding(engine, served):
    sched, prompts, reqs, compiled = served
    assert sched.metrics.preemptions > 0, "the pool was sized to preempt"
    for p, r in zip(prompts, reqs):
        assert r.state == "finished" and len(r.out_tokens) == \
            r.max_new_tokens
        assert margins(engine, p, np.asarray(r.out_tokens)).max() <= TOL
    assert sched.kv.pool.pages_in_use == 0
    assert 1 <= compiled[0] <= len(sched.horizon_buckets)
    assert 1 <= compiled[1] <= len(sched.prefill_row_buckets)
    out = engine.generate(prompts[1][None], max_new_tokens=12,
                          do_sample=False)
    assert list(np.asarray(out)[0, 19:]) == list(reqs[1].out_tokens)


def test_page_and_ring_bytes_are_the_arithmetic(served):
    """A page is the two FULL layers' alone; a slot carries the five
    window layers' rings where a recurrent model carries its state."""
    sched, prompts, reqs, _ = served
    h, s = sched.health(), sched.summary()
    eng = sched.engine
    # 2 full layers x 8 positions x 2 KV heads x (24 + 16) x 4 bytes
    assert eng.kv_page_bytes(8) == 2 * 8 * 2 * 40 * 4
    assert h["kv_pool_bytes_total"] == 9 * eng.kv_page_bytes(8) == \
        s["kv_pool_bytes"]
    # 5 window layers x 16 positions x 4 KV heads x (24 + 16) x 4 bytes
    per_slot = 5 * 16 * 4 * 40 * 4
    assert eng.state_bytes_per_slot() == per_slot
    assert eng.window_ring() == (16, per_slot)
    # + the six routed layers' counters (5 uint32 each)
    assert h["state_pool_bytes_total"] == 3 * per_slot + 6 * 5 * 4 == \
        s["state_pool_bytes"]
    assert s["kv_paged_bytes_per_token"] == 2 * 2 * 40 * 4
    assert s["kv_window_bytes_per_slot"] == per_slot
    assert h["paged_attention"]["heads"] == [8, 2]
    assert s["state_resets"] == len(reqs) + s["preemptions"]
    assert 0 < s["moe_held_assignments"] < s["moe_assignments"]
    assert s["moe_calls"] % 6 == 0


def test_what_prefill_and_decode_needed_is_counted_exactly(engine):
    """No preemption here.  Decode token i of a request attends over its
    prompt and its i tokens so far — cut to 16 in a window layer; a
    prefill chunk of n columns from position s reads s + n keys and
    scores n s + n (n + 1) / 2 pairs."""
    sched = ServingScheduler(engine, num_slots=3, num_pages=12, page_size=8,
                             max_pages_per_slot=4, prefill_chunk=8,
                             decode_horizon_steps=4)
    rng = np.random.default_rng(4)
    lens = [(5, 9), (21, 6)]
    for n, m in lens:
        sched.submit(rng.integers(0, 256, n).astype(np.int32), m)
    sched.run()
    s = sched.summary()
    assert s["preemptions"] == 0
    assert s["decode_kv_tokens"] == sum(
        (m - 1) * n + m * (m - 1) // 2 for n, m in lens)
    assert s["decode_window_tokens"] == sum(
        min(n + i, 16) for n, m in lens for i in range(1, m))

    def chunks(n):
        return [(at, min(8, n - at)) for at in range(0, n, 8)]
    assert s["prefill_kv_tokens"] == sum(
        at + c for n, _ in lens for at, c in chunks(n))
    assert s["prefill_kv_pairs"] == sum(
        at * c + c * (c + 1) // 2 for n, _ in lens for at, c in chunks(n))
    # the whole lower triangle of each prompt, once
    assert s["prefill_kv_pairs"] == sum(n * (n + 1) // 2 for n, _ in lens)


def test_the_prefix_cache_is_refused_with_its_reason(served):
    h = served[0].health()
    assert served[0].prefix_cache is None and h["prefix_cache"] is False
    assert "MiMoV2 keeps a window ring per slot" in h["prefix_cache_refused"]
    assert "cannot be shared by pages" in h["prefix_cache_refused"]


@pytest.mark.parametrize("kwargs,feature", [
    ({"spec_decode": "ngram"}, "spec_decode"),
    ({"seq_parallel_threshold": 64}, "seq_parallel_prefill"),
    ({"on_handoff": lambda *a: None}, "handoff"),
])
def test_what_cannot_carry_a_ring_raises_by_name(engine, kwargs, feature):
    with pytest.raises(ValueError, match=feature) as err:
        ServingScheduler(engine, num_slots=2, num_pages=8, page_size=8,
                         **kwargs)
    assert "MiMoV2 keeps a window ring per slot" in str(err.value)


def test_a_model_with_neither_state_nor_ring_refuses_nothing():
    from deepspeed_tpu.models.llama import Llama, llama_tiny
    eng = deepspeed_tpu.init_inference(Llama(llama_tiny()), dtype="float32")
    assert eng.slot_state is None and eng.window_ring() == (0, 0)
    assert all(eng.slot_state_refusal(f) is None for f in
               ("prefix_cache", "spec_decode", "seq_parallel_prefill",
                "handoff"))


def test_the_axis_rules_on_a_mesh_for_pages_and_rings():
    """8 virtual devices as data=2 x model=2: the full layers' pages
    shard their 2 KV heads over ``model``, the window layers' rings
    their slots over ``data`` and their 4 KV heads over ``model``; the
    served tokens are still the reference's."""
    from jax.sharding import PartitionSpec as P
    eng = build_engine(tensor_parallel={"tp_size": 2},
                       mesh={"data": 2, "model": 2})
    sched = ServingScheduler(eng, num_slots=4, num_pages=16, page_size=8,
                             max_pages_per_slot=6, prefill_chunk=8)

    def specs(pools, i):
        return {n: a.sharding.spec for n, a in pools["layers"][i].items()}
    pages = {"k_pages": P(None, None, "model", None),
             "v_pages": P(None, None, "model", None)}
    rings = {"k_ring": P("data", None, "model", None),
             "v_ring": P("data", None, "model", None), "routing": P(None)}
    assert specs(sched.pools, 0) == pages
    assert specs(sched.pools, 1) == rings
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (11, 20)]
    reqs = [sched.submit(p, 6) for p in prompts]
    sched.run()
    for p, r in zip(prompts, reqs):
        assert margins(eng, p, np.asarray(r.out_tokens)).max() <= TOL
    assert specs(sched.pools, 0) == pages
    assert specs(sched.pools, 1) == rings
