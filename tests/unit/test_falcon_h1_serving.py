"""A tiny Falcon-H1 parallel hybrid (GQA attention with a query group
of 5 AND a Mamba-2 mixer in every block, muP multipliers) through the
normal serving path — ``init_inference`` + ``ServingScheduler`` —
against the plain reference's full forward
(benchmarks/chip/reference_falcon_h1.py, loaded from there).

Logits are compared, never sampled tokens.  Tolerances, float32 on the
CPU, at the tiny preset's logit scale of ~0.25:

* ``TOL`` = 2e-6 absolute on logits: float32 rounding through two
  blocks reads 2e-7 to 6e-7 here (full forward, chunked prefill, paged
  decode alike).  Any one of the fourteen multipliers set to 1 moves a
  logit by 4e-5 (the dt segment's, which the gated norm mostly
  absorbs) to 0.39, a misplaced one by 1e-4 or more: 20 times the
  tolerance at least.  A state rounded to bfloat16 after every token
  moves it by 9e-6, 4.5 times the tolerance: float32 state is held to
  by a smaller factor than the multipliers, and by the test that
  rounds it.
* a served token's logit lies within ``TOL`` of the reference's best.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import falcon_h1
from deepspeed_tpu.models.falcon_h1 import (FalconH1, FalconH1Config,
                                            falcon_h1_tiny)
from deepspeed_tpu.ops.attention import kv_cache, reference as attn_ref
from deepspeed_tpu.ops.attention.decode import (kernel_mode_scope,
                                                paged_decode_attention)
from deepspeed_tpu.serving import ServingScheduler

TOL = 2e-6
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "reference_falcon_h1", os.path.join(REPO, "benchmarks", "chip",
                                        "reference_falcon_h1.py"))
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

SCALARS = ("embedding_multiplier", "attention_in_multiplier",
           "attention_out_multiplier", "key_multiplier",
           "ssm_in_multiplier", "ssm_out_multiplier", "lm_head_multiplier")


def reference_args(cfg):
    args = dict(layers=cfg.num_layers, eps=cfg.rms_eps, heads=cfg.num_heads,
                kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                theta=cfg.rope_base, mamba_heads=cfg.mamba_num_heads,
                mamba_head_dim=cfg.mamba_head_dim, groups=cfg.n_groups,
                state=cfg.ssm_state_size,
                ssm_multipliers=list(cfg.ssm_multipliers),
                mlp_multipliers=list(cfg.mlp_multipliers))
    args.update({name: getattr(cfg, name) for name in SCALARS})
    return args


def build_engine(cfg=None, **kw):
    eng = deepspeed_tpu.init_inference(
        FalconH1(cfg or falcon_h1_tiny()), dtype="float32",
        kv_cache_dtype="float32", **kw)
    eng.init_params(seed=3)
    return eng


@pytest.fixture(scope="module")
def engine():
    return build_engine()


def reference_logits(params, ids, args, ref=REF):
    with jax.default_matmul_precision("highest"):
        hidden = ref.hidden(params, jnp.asarray(ids)[None], **args)
        return np.asarray(ref.logits(params, hidden))[0]


IDS = np.random.default_rng(5).integers(0, 256, 29).astype(np.int32)


@pytest.fixture(scope="module")
def want(engine):
    return reference_logits(engine.params, IDS,
                            reference_args(engine.module.cfg))


def test_the_config_holds_its_multipliers_and_widths_together():
    with pytest.raises(ValueError, match="ssm_multipliers"):
        falcon_h1_tiny(ssm_multipliers=(1.0, 1.0))
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        falcon_h1_tiny(mamba_d_ssm=96)
    cfg = FalconH1Config()          # the 34B's published widths
    assert cfg.conv_dim == 4096 + 2 * 2 * 256
    assert cfg.mup_vector().shape == (4096 + 5120 + 32,)
    # a configuration file hands lists over
    assert falcon_h1_tiny(mlp_multipliers=[0.5, 0.3]).mlp_multipliers == \
        (0.5, 0.3)


def test_full_forward_logits_are_the_references(engine, want):
    got = engine.module.apply({"params": engine.params}, IDS[None])[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def paged_decode_logits(engine, tok, active, table, lengths, pools):
    """One decode step of the MODEL through the paged pools (the
    engine's decode primitives return sampled tokens)."""
    step = kv_cache.decode_step(pools["layers"], jnp.asarray(table),
                                jnp.asarray(lengths), jnp.asarray(active))
    with engine._serving_scope():
        logits, new = engine.module.apply(
            {"params": engine.params}, jnp.asarray(tok)[:, None], cache=step)
    return np.asarray(logits[:, 0]), new.pools, np.asarray(new.lengths)


@pytest.mark.parametrize("kernel", ["auto", "force"])
@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_prefill_then_paged_decode_are_the_full_forward(
        want, chunk, kernel):
    """20 prompt tokens in chunks of ``chunk`` into slot 2 (every
    chunk's boundary logits are the reference's there, so the carried
    conv tail and state are the sequence's), then 9 teacher-forced
    decode steps through the page pool AND the state pool of the same
    layer.  ``force`` runs the Pallas kernels (group of 5) in interpret
    mode, ``auto`` the jnp paths."""
    engine = build_engine(paged_kernel=kernel)
    pools = engine.init_paged_cache(8, 8, num_slots=3)
    table = np.array([[8] * 4, [6, 7, 8, 8], [0, 1, 2, 3]], np.int32)
    lengths = np.zeros(3, np.int32)
    n_prompt = 20
    for at in range(0, n_prompt, chunk):
        ids = np.zeros((1, chunk), np.int32)
        part = IDS[at:min(at + chunk, n_prompt)]
        ids[0, :len(part)] = part
        logits, pools = engine.prefill_into_slots(
            ids, [2], [len(part)], table, lengths, pools)
        lengths[2] += len(part)
        np.testing.assert_allclose(logits[0], want[lengths[2] - 1],
                                   atol=TOL, rtol=0)
    for t in range(n_prompt, len(IDS)):
        tok = np.zeros(3, np.int32)
        tok[2] = IDS[t]
        logits, pools, new_len = paged_decode_logits(
            engine, tok, [False, False, True], table, lengths, pools)
        assert list(new_len) == [0, 0, lengths[2] + 1]   # advanced ONCE
        lengths = new_len
        np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0)


def test_a_layers_entry_holds_pages_and_state_and_each_side_its_own(engine):
    pools = engine.init_paged_cache(8, 8, num_slots=3)
    cfg = engine.module.cfg
    for entry in pools["layers"]:
        assert set(entry) == {"k_pages", "v_pages", "conv", "ssm"}
        assert entry["ssm"].shape == (3, 8, 8, 16)
        assert entry["ssm"].dtype == jnp.float32
        assert entry["conv"].shape == (3, 3, cfg.conv_dim)
        assert entry["k_pages"].shape == (8, 8, 2, 16)
    step = kv_cache.layer_view(kv_cache.decode_step(
        pools["layers"], np.zeros((3, 4), np.int32), np.zeros(3, np.int32),
        np.ones(3, bool)), 0)
    kv, state = falcon_h1._split_entry(step)
    assert set(kv.layers) == {"k_pages", "v_pages"}
    assert set(state.layers) == {"conv", "ssm"}
    assert kv.mode == state.mode == "decode"
    dense = falcon_h1.init_kv_cache(cfg, 2, max_len=16, dtype=jnp.float32)
    kv, state = falcon_h1._split_entry(dense["layers"][0])
    assert set(kv) == {"k", "v", "index"}
    assert set(state) == {"conv", "ssm"}
    with pytest.raises(ValueError, match="num_slots"):
        engine.init_paged_cache(8, 8)


def test_a_reused_slot_starts_from_zeros(engine, want):
    """Another request's 13 tokens through slot 1, then OUR prompt into
    the same slot from position 0, its pages handed over too: the
    boundary logits are the reference's of our prompt alone."""
    pools = engine.init_paged_cache(8, 8, num_slots=2)
    table = np.array([[8] * 4, [0, 1, 2, 3]], np.int32)
    lengths = np.zeros(2, np.int32)
    other = np.random.default_rng(9).integers(0, 256, (1, 16)).astype(
        np.int32)
    _, pools = engine.prefill_into_slots(other, [1], [13], table, lengths,
                                         pools)
    assert float(jnp.abs(pools["layers"][0]["ssm"][1]).max()) > 0
    ours = IDS[None, :16]
    logits, pools = engine.prefill_into_slots(ours, [1], [16], table,
                                              lengths, pools)
    np.testing.assert_allclose(logits[0], want[15], atol=TOL, rtol=0)


def test_an_idle_slots_state_and_pages_are_bit_identical_after_a_decode_step(
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
        for name in ("conv", "ssm"):
            assert np.array_equal(old[name][1], np.asarray(new[name][1]))
            assert not np.array_equal(old[name][0], np.asarray(new[name][0]))
        for name in ("k_pages", "v_pages"):
            # slot 1's pages (2, 3) and the pages nobody holds
            assert np.array_equal(old[name][2:], np.asarray(new[name][2:]))
            assert not np.array_equal(old[name][1],
                                      np.asarray(new[name][1]))


# ------------------------------------------- every multiplier matters

def _mup_after_activation(REF):
    """The reference's Mamba-2 mixer with ``mup_vector`` over [x | B |
    C] applied AFTER the conv's SiLU instead of before the conv (z and
    dt, which no conv touches, as published): a guessed placement."""
    def mixer(u, w, *, ssm_multipliers, **sizes):
        inner = sizes["mamba_heads"] * sizes["mamba_head_dim"]
        gn = sizes["groups"] * sizes["state"]
        m = ssm_multipliers
        post = jnp.concatenate([jnp.full((n,), v, jnp.float32) for n, v in
                                zip((inner, gn, gn), m[1:4])])
        silu = jax.nn.silu
        jax.nn.silu = lambda a: silu(a) * post \
            if a.shape[-1] == inner + 2 * gn else silu(a)
        try:
            return REF_MIXER(u, w, ssm_multipliers=(m[0], 1.0, 1.0, 1.0,
                                                    m[4]), **sizes)
        finally:
            jax.nn.silu = silu
    return mixer


REF_MIXER = REF.mamba_mixer


def _args_with(cfg, **changes):
    return dict(reference_args(cfg), **changes)


def _list_with(values, i, v):
    out = list(values)
    out[i] = v
    return out


def _variants(cfg):
    """(name, reference args, parameter edit or None, patched mixer)."""
    out = [(name, _args_with(cfg, **{name: 1.0}), None, None)
           for name in SCALARS]
    out += [(f"ssm_multipliers[{i}]", _args_with(
        cfg, ssm_multipliers=_list_with(cfg.ssm_multipliers, i, 1.0)),
        None, None) for i in range(5)]
    out += [(f"mlp_multipliers[{i}]", _args_with(
        cfg, mlp_multipliers=_list_with(cfg.mlp_multipliers, i, 1.0)),
        None, None) for i in range(2)]
    # guessed placements.  (The key multiplier on the scores q.k instead
    # of on k, and mup_vector after the conv but before its bias, are
    # the SAME function as published — both products are linear — so
    # they are no misplacement a test could or should tell apart.)
    out.append(("key_multiplier_on_q_and_k", _args_with(
        cfg, key_multiplier=cfg.key_multiplier ** 2), None, None))
    out.append(("ssm_multipliers_B_and_C_swapped", _args_with(
        cfg, ssm_multipliers=[cfg.ssm_multipliers[i]
                              for i in (0, 1, 3, 2, 4)]), None, None))
    out.append(("gate_multiplier_on_up", _args_with(
        cfg, mlp_multipliers=[1.0, cfg.mlp_multipliers[1]]),
        ("mlp", "w_up", cfg.mlp_multipliers[0]), None))
    out.append(("mup_vector_after_the_conv_activation",
                reference_args(cfg), None, _mup_after_activation))
    return out


VARIANTS = _variants(falcon_h1_tiny())


def test_the_tiny_preset_gives_every_multiplier_a_value_of_its_own():
    cfg = falcon_h1_tiny()
    values = [getattr(cfg, n) for n in SCALARS] + \
        list(cfg.ssm_multipliers) + list(cfg.mlp_multipliers)
    assert len(values) == 14 == len(set(values)) and 1.0 not in values


@pytest.mark.parametrize("variant", VARIANTS, ids=[v[0] for v in VARIANTS])
def test_a_reference_with_one_multiplier_dropped_or_misplaced_fails(
        engine, want, variant, monkeypatch):
    _, args, edit, mixer = variant
    params = engine.params
    if edit is not None:
        sub, name, factor = edit
        params = jax.tree.map(lambda a: a, params)
        for i in range(engine.module.cfg.num_layers):
            kernel = params[f"layers_{i}"][sub][name]
            kernel["kernel"] = kernel["kernel"] * factor
    if mixer is not None:
        monkeypatch.setattr(REF, "mamba_mixer", mixer(REF))
    wrong = reference_logits(params, IDS, args)
    assert np.abs(want - wrong).max() > 10 * TOL


def test_a_bfloat16_state_fails_the_comparison(engine, want):
    """The recurrent state rounded to bfloat16 after the prefill chunk
    and after every decode step (what a bf16 state pool would hold)
    misses the tolerance; left in float32 it holds it."""
    def serve(rounded):
        pools = engine.init_paged_cache(8, 8, num_slots=1)
        table = np.array([[0, 1, 2, 3]], np.int32)
        lengths = np.zeros(1, np.int32)
        _, pools = engine.prefill_into_slots(IDS[None, :8], [0], [8], table,
                                             lengths, pools)
        lengths[0], worst = 8, 0.0
        for t in range(8, len(IDS)):
            if rounded:
                pools = {"layers": [
                    dict(e, ssm=e["ssm"].astype(jnp.bfloat16)
                         .astype(jnp.float32)) for e in pools["layers"]]}
            logits, pools, lengths = paged_decode_logits(
                engine, IDS[t:t + 1], [True], table, lengths, pools)
            worst = max(worst, np.abs(logits[0] - want[t]).max())
        return worst
    assert serve(rounded=False) <= TOL
    assert serve(rounded=True) > 3 * TOL


# ----------------------------------- a query group of 5 in the kernels

def _gathered(pools, table):
    ps = pools["k_pages"].shape[1]
    return tuple(pools[n][table].reshape(table.shape[0], -1,
                                         *pools[n].shape[2:])
                 for n in ("k_pages", "v_pages")), ps


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_group_of_five_through_the_paged_decode_kernel(dtype):
    """20 query heads over 4 KV heads, d 128 (the published geometry):
    the kernel in interpret mode against ops/attention/reference.py
    over the gathered pages."""
    rng = np.random.default_rng(0)
    h, kv_h, d, ps, slots = 20, 4, 128, 16, 3
    pools = {n: jnp.asarray(rng.standard_normal((12, ps, kv_h, d)), dtype)
             for n in ("k_pages", "v_pages")}
    table = jnp.asarray(rng.permutation(12)[:9].reshape(slots, 3), jnp.int32)
    pos = jnp.asarray([0, 17, 47], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, 1, h, d)), dtype)
    got = paged_decode_attention(q, pools["k_pages"], pools["v_pages"],
                                 table, pos, force_kernel=True,
                                 interpret=True)
    (k, v), _ = _gathered(pools, table)
    want = attn_ref.decode_attention_reference(
        q, jnp.repeat(k, h // kv_h, 2), jnp.repeat(v, h // kv_h, 2), pos + 1)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    # float32: accumulation order; bf16: each side rounds its output once
    tol = 1e-5 if dtype == jnp.float32 else 2 * 2.0 ** -8 * np.abs(want).max()
    assert np.abs(got - want).max() <= tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_group_of_five_through_the_paged_prefill_kernel(dtype):
    rng = np.random.default_rng(1)
    h, kv_h, d, ps, l = 20, 4, 128, 16, 8
    pools = {n: jnp.asarray(rng.standard_normal((12, ps, kv_h, d)), dtype)
             for n in ("k_pages", "v_pages")}
    table = jnp.asarray(rng.permutation(12)[:9].reshape(3, 3), jnp.int32)
    lengths = jnp.asarray([0, 21, ps], jnp.int32)
    rows = jnp.asarray([1, 2, 0], jnp.int32)
    count = jnp.asarray([l, l - 3, 1], jnp.int32)
    step = kv_cache.prefill_step(pools, table, lengths, rows, count)
    q, k, v = (jnp.asarray(rng.standard_normal((3, l, n, d)), dtype)
               for n in (h, kv_h, kv_h))
    pos = kv_cache.positions(step, 3, l)
    with kernel_mode_scope("force"):
        got, new = jax.jit(lambda q, k, v: kv_cache._paged_multi(
            q, k, v, pos, step, None))(q, k, v)
    (k_all, v_all), _ = _gathered(new, table[rows])
    k_pos = jnp.arange(k_all.shape[1])
    bias = jnp.where(k_pos[None, None, :] <= pos[:, :, None], 0.0,
                     jnp.finfo(jnp.float32).min)[:, None]
    want = attn_ref.mha_reference(
        q, jnp.repeat(k_all, h // kv_h, 2), jnp.repeat(v_all, h // kv_h, 2),
        causal=False, bias=bias)
    valid = np.arange(l)[None, :] < np.asarray(count)[:, None]
    got, want = (np.asarray(a, np.float32)[valid] for a in (got, want))
    tol = 1e-5 if dtype == jnp.float32 else 2 * 2.0 ** -8 * np.abs(want).max()
    assert np.abs(got - want).max() <= tol


# ------------------------------------------------- a vocabulary slice

def test_a_vocabulary_slice_is_a_smaller_vocabulary(engine, want):
    """Rows 0-31 of both tables (an eighth, as the benchmark's cut) are
    a model of vocabulary 32: ids drawn from the slice, logits [.., 32]
    and equal to the whole model's over the slice."""
    cfg = dataclasses.replace(engine.module.cfg, vocab_size=32)
    sliced = build_engine(cfg)
    params = jax.tree.map(lambda a: a, engine.params)
    params["embed_tokens"] = params["embed_tokens"][:32]
    params["lm_head"] = {"kernel": params["lm_head"]["kernel"][:, :32]}
    sliced.set_params(params)
    ids = np.random.default_rng(2).integers(0, 32, 21).astype(np.int32)
    whole = reference_logits(engine.params, ids,
                             reference_args(engine.module.cfg))
    got = reference_logits(sliced.params, ids, reference_args(cfg))
    assert got.shape == (21, 32)
    np.testing.assert_allclose(got, whole[:, :32], atol=TOL, rtol=0)
    sched = ServingScheduler(sliced, num_slots=2, num_pages=8, page_size=8,
                             prefill_chunk=8)
    req = sched.submit(ids[:12], 6)
    sched.run()
    out = np.asarray(req.out_tokens)
    assert out.max() < 32
    lg = reference_logits(sliced.params, np.concatenate([ids[:12], out]),
                          reference_args(cfg))[11:17]
    assert (lg.max(-1) - lg[np.arange(6), out]).max() <= TOL


# ------------------------------------------------ the normal serving path

def margins(engine, prompt, out_tokens):
    ids = np.concatenate([prompt, out_tokens]).astype(np.int32)
    lg = reference_logits(engine.params, ids,
                          reference_args(engine.module.cfg))
    pos = len(prompt) - 1 + np.arange(len(out_tokens))
    return lg[pos].max(-1) - lg[pos, out_tokens]


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


def test_both_pools_and_what_decode_needed_are_reported(served):
    sched, prompts, reqs, _ = served
    h, s = sched.health(), sched.summary()
    cfg = sched.engine.module.cfg
    per_slot = sched.engine.state_bytes_per_slot()
    assert per_slot == 2 * (3 * cfg.conv_dim * 4 + 8 * 8 * 16 * 4)
    # the two pools of ONE layer, counted side by side
    assert h["state_pool_bytes_total"] == 3 * per_slot == \
        s["state_pool_bytes"]
    assert h["kv_pool_bytes_total"] == 2 * 2 * 9 * 8 * 2 * 16 * 4 == \
        9 * sched.engine.kv_page_bytes(8) == s["kv_pool_bytes"]
    assert h["paged_attention"]["heads"] == [10, 2]
    assert s["state_resets"] == len(reqs) + s["preemptions"]
    # every token came from a decode step but a request's first and
    # the one the re-prefill after a recompute-preemption yields
    by_prefill = s["tokens_emitted"] - s["decode_live_rows"]
    assert len(reqs) <= by_prefill <= len(reqs) + s["preemptions"]
    assert s["decode_steps"] * 3 >= s["decode_live_rows"]


def test_what_the_decode_steps_needed_is_counted_exactly(engine):
    """No preemption here: decode token i of a request attends over its
    prompt and its i tokens so far."""
    sched = ServingScheduler(engine, num_slots=3, num_pages=12, page_size=8,
                             max_pages_per_slot=4, prefill_chunk=8,
                             decode_horizon_steps=4)
    rng = np.random.default_rng(4)
    lens = [(5, 9), (11, 6)]
    for n, m in lens:
        sched.submit(rng.integers(0, 256, n).astype(np.int32), m)
    sched.run()
    s = sched.summary()
    assert s["preemptions"] == 0
    assert s["decode_live_rows"] == sum(m - 1 for _, m in lens)
    assert s["decode_kv_tokens"] == sum(
        (m - 1) * n + m * (m - 1) // 2 for n, m in lens)
    assert max(m - 1 for _, m in lens) <= s["decode_steps"] <= \
        s["decode_live_rows"] + 8


def test_the_prefix_cache_is_refused_with_its_reason(served):
    h = served[0].health()
    assert served[0].prefix_cache is None and h["prefix_cache"] is False
    assert "FalconH1 keeps recurrent state" in h["prefix_cache_refused"]


@pytest.mark.parametrize("kwargs,feature", [
    ({"spec_decode": "ngram"}, "spec_decode"),
    ({"seq_parallel_threshold": 64}, "seq_parallel_prefill"),
    ({"on_handoff": lambda *a: None}, "handoff"),
])
def test_what_cannot_carry_a_state_raises_by_name(engine, kwargs, feature):
    with pytest.raises(ValueError, match=feature) as err:
        ServingScheduler(engine, num_slots=2, num_pages=8, page_size=8,
                         **kwargs)
    assert "FalconH1 keeps recurrent state" in str(err.value)


def test_the_axis_rules_on_a_mesh_for_an_entry_of_both_kinds():
    """8 virtual devices as data=2 x model=2: one layer's entry holds
    page leaves (KV heads over ``model``) and per-slot leaves (slots
    over ``data``, state heads over ``model``: 8 heads in 2 groups, a
    group a shard) together; the served tokens are still the
    reference's."""
    from jax.sharding import PartitionSpec as P
    eng = build_engine(tensor_parallel={"tp_size": 2},
                       mesh={"data": 2, "model": 2})
    sched = ServingScheduler(eng, num_slots=4, num_pages=16, page_size=8,
                             max_pages_per_slot=6, prefill_chunk=8)
    axes = sched.health()["serving_axes"]
    assert axes["slots"] == "data" and axes["kv_heads"] == "model"
    assert axes["ssm_heads"] == "model"

    def specs(pools):
        return {n: a.sharding.spec for n, a in pools["layers"][1].items()}
    pinned = {"ssm": P("data", "model", None, None),
              "conv": P("data", None, None),
              "k_pages": P(None, None, "model", None),
              "v_pages": P(None, None, "model", None)}
    assert specs(sched.pools) == pinned
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (11, 20)]
    reqs = [sched.submit(p, 6) for p in prompts]
    sched.run()
    for p, r in zip(prompts, reqs):
        assert margins(eng, p, np.asarray(r.out_tokens)).max() <= TOL
    assert specs(sched.pools) == pinned
