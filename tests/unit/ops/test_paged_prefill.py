"""The paged flash-prefill kernel (ops/attention/paged_prefill.py) held
against the reference path of ``kv_cache._paged_multi``.

Both run through ``_paged_multi`` itself, in interpret mode on the CPU:
``kernel_mode_scope("force")`` takes the kernel, ``"reference"`` the
gather + mask + jnp attention the kernel replaces.  The write is the
same code on both sides, so the pools must agree to the bit; outputs
agree on every VALID column (a padding column sees no page past the
row's last written position in the kernel, every page in the
reference, and nothing reads it).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.ops.attention.decode import (kernel_mode_scope,
                                                paged_kernel_decision)
from deepspeed_tpu.ops.attention.paged_prefill import _tile_cols
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.serving import ServingScheduler

SLOTS, MAXP, PAGES = 4, 6, 40
GQA, MHA = (32, 8, 128), (4, 4, 64)      # heads, kv heads, head dim


def _pools(rng, kv_h, d, ps, dtype):
    """One layer's pools, full of history (a float pool: normal draws;
    an int8 pool: payload and per-row scales)."""
    pools = kv_cache.init_paged(1, PAGES, ps, kv_h, d, dtype)["layers"][0]
    out = {}
    for name, a in pools.items():
        if a.dtype == jnp.int8:
            out[name] = jnp.asarray(rng.integers(-127, 128, a.shape),
                                    jnp.int8)
        elif name.endswith("scale"):
            out[name] = jnp.asarray(rng.uniform(0.005, 0.02, a.shape),
                                    jnp.float32)
        else:
            out[name] = jnp.asarray(rng.standard_normal(a.shape), a.dtype)
    return out


def _step(pools, mode, l, ps, rng):
    """Rows at start 0, a page boundary, mid-page (a prefix-cache hit's
    boundary) and deep into the table; counts full, short, one and — a
    padding row — zero."""
    pt = jnp.asarray(rng.permutation(PAGES - 1)[:SLOTS * MAXP]
                     .reshape(SLOTS, MAXP) + 1, jnp.int32)
    lengths = jnp.asarray([0, 2 * ps, ps + 5, 2 * ps + 5], jnp.int32)
    if mode == "verify":        # row r IS slot r
        count = jnp.asarray([l, 0, l - 2, 1], jnp.int32)
        return kv_cache.verify_step(pools, pt, lengths, count)
    rows = jnp.asarray([2, 0, 3, 1, 0], jnp.int32)
    count = jnp.asarray([l, l - 3, 1, l, 0], jnp.int32)
    return kv_cache.prefill_step(pools, pt, lengths, rows, count)


def _both_paths(step, h, kv_h, d, l, dtype, rng):
    b = SLOTS if step.rows is None else step.rows.shape[0]
    q, k, v = (jnp.asarray(rng.standard_normal((b, l, n, d)), dtype)
               for n in (h, kv_h, kv_h))
    pos = kv_cache.positions(step, b, l)
    out = {}
    for mode in ("reference", "force"):
        with kernel_mode_scope(mode):
            out[mode] = jax.jit(lambda q, k, v: kv_cache._paged_multi(
                q, k, v, pos, step, None))(q, k, v)
    valid = np.arange(l)[None, :] < np.asarray(step.count)[:, None]
    return out["reference"], out["force"], valid


def _assert_same(ref, got, valid, dtype):
    (o_ref, p_ref), (o_got, p_got) = ref, got
    for name in p_ref:
        assert np.array_equal(np.asarray(p_ref[name]),
                              np.asarray(p_got[name])), name
    o_ref, o_got = (np.asarray(o, np.float32) for o in (o_ref, o_got))
    assert np.isfinite(o_got).all()      # padding rows and columns too
    # float32: test_batched_prefill's 1e-5; bf16: two ulps of the
    # largest output (each side rounds its output once)
    tol = 1e-5 if dtype == jnp.float32 else \
        2 * 2.0 ** -8 * np.abs(o_ref[valid]).max()
    assert np.abs(o_ref - o_got)[valid].max() <= tol


@pytest.mark.parametrize("mode", ["prefill", "verify"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("geometry", [GQA, MHA], ids=["gqa32x8", "mha4"])
def test_kernel_matches_the_reference_path(geometry, dtype, mode):
    h, kv_h, d = geometry
    rng = np.random.default_rng(0)
    l = 9 if mode == "verify" else 8        # K + 1 pads to a tile
    step = _step(_pools(rng, kv_h, d, 16, dtype), mode, l, 16, rng)
    ref, got, valid = _both_paths(step, h, kv_h, d, l, dtype, rng)
    _assert_same(ref, got, valid, dtype)


@pytest.mark.parametrize("mode", ["prefill", "verify"])
def test_int8_pools_dequantize_in_the_kernel(mode):
    h, kv_h, d = GQA
    rng = np.random.default_rng(1)
    step = _step(_pools(rng, kv_h, d, 16, "int8"), mode, 8, 16, rng)
    assert "k_scale" in step.layers
    ref, got, valid = _both_paths(step, h, kv_h, d, 8, jnp.bfloat16, rng)
    _assert_same(ref, got, valid, jnp.bfloat16)


@pytest.mark.parametrize("case", ["pages_of_128", "chunk_in_two_q_tiles"])
def test_kernel_at_other_tilings(case):
    """The chip's page size, and a head count at which one chunk's
    scores would outgrow a tile's budget, so the chunk splits over the
    q-tile grid axis (each tile stops at its own last column)."""
    h, kv_h, d, ps, l = (32, 8, 128, 128, 32) if case == "pages_of_128" \
        else (64, 64, 16, 16, 40)
    assert _tile_cols(l, kv_h, h // kv_h) == \
        ((32, 32) if case == "pages_of_128" else (32, 64))
    rng = np.random.default_rng(2)
    step = _step(_pools(rng, kv_h, d, ps, jnp.float32), "prefill", l, ps,
                 rng)
    ref, got, valid = _both_paths(step, h, kv_h, d, l, jnp.float32, rng)
    _assert_same(ref, got, valid, jnp.float32)


@pytest.mark.parametrize("mode", ["prefill", "verify"])
def test_dead_pages_are_never_read(mode):
    """Every page past a row's last WRITTEN position poisoned with NaN:
    the kernel's valid outputs are what they were, to the bit, and
    finite everywhere; the reference, which reads capacity, is not."""
    h, kv_h, d = MHA
    ps, l = 16, 8
    rng = np.random.default_rng(3)
    pools = _pools(rng, kv_h, d, ps, jnp.float32)
    step = _step(pools, mode, l, ps, np.random.default_rng(4))
    clean = _both_paths(step, h, kv_h, d, l, jnp.float32,
                        np.random.default_rng(5))
    slots = np.arange(SLOTS) if step.rows is None else np.asarray(step.rows)
    last = np.asarray(step.lengths)[slots] + np.asarray(step.count) - 1
    live = {0}                                   # the table's null page
    for s, n, c in zip(slots, last, np.asarray(step.count)):
        live |= set(np.asarray(step.page_table)[s, :(n if c else 0) // ps
                                                + 1].tolist())
    dead = np.asarray(sorted(set(range(PAGES)) - live))
    assert dead.size > PAGES // 2
    poisoned = {n: a.at[dead].set(jnp.nan) for n, a in pools.items()}
    ref, got, valid = _both_paths(
        dataclasses.replace(step, layers=poisoned), h, kv_h, d, l,
        jnp.float32, np.random.default_rng(5))
    assert np.isfinite(np.asarray(got[0])).all()
    assert np.array_equal(np.asarray(got[0])[valid],
                          np.asarray(clean[1][0])[valid])
    assert not np.isfinite(np.asarray(ref[0])[valid]).all()


# ------------------------------------------------------- the decision

def _mesh_2x4():
    return make_mesh(MeshConfig(data=4, model=2))


@pytest.mark.parametrize("facts,path,dispatch,reason", [
    (dict(page_size=128, backend="tpu"), "kernel", "direct",
     "paged_prefill kernel over each row's live pages"),
    (dict(page_size=128, backend="tpu", mesh=_mesh_2x4), "kernel",
     "shard_map", "shard_mapped over the mesh"),
    (dict(page_size=128, backend="tpu", has_bias=True), "reference", None,
     "ALiBi"),
    (dict(page_size=64, backend="tpu"), "reference", None, "page_size=64"),
    (dict(page_size=128, backend="tpu", mode="reference"), "reference",
     None, "paged_kernel='reference'"),
    (dict(page_size=128, backend="cpu"), "reference", None,
     "off-TPU backend 'cpu'"),
    (dict(page_size=16, backend="cpu", mode="force"), "kernel", "direct",
     "paged_kernel='force'"),
    (dict(page_size=128, backend="tpu", num_heads=6, num_kv_heads=4),
     "reference", None, "not a multiple"),
], ids=["tpu", "mesh", "alibi", "page64", "mode_reference", "cpu", "force",
        "ragged_groups"])
def test_the_decision_answers_for_the_multi_token_path(facts, path,
                                                       dispatch, reason):
    facts = dict(dict(num_heads=32, num_kv_heads=8), **facts)
    if "mesh" in facts:
        facts["mesh"] = facts["mesh"]()
    multi = paged_kernel_decision(multi_token=True, **facts)
    assert (multi["path"], multi["dispatch"]) == (path, dispatch)
    assert reason in multi["reason"]
    # one rule: the same facts give decode the same path, and only the
    # multi-token answer names the prefill kernel or its fallback
    decode = paged_kernel_decision(**facts)
    assert (decode["path"], decode["dispatch"]) == (path, dispatch)
    assert "prefill and verify" in multi["reason"]
    assert "prefill and verify" not in decode["reason"]
    assert multi.get("blocker") == decode.get("blocker")


@pytest.mark.parametrize("mode,path", [("force", "kernel"),
                                       ("reference", "reference"),
                                       ("auto", "reference")])
def test_served_prefill_takes_the_path_health_reports(mode, path):
    """An engine serves token-exactly against its own ``generate()`` on
    either path; ``health()`` says which, beside decode's, and the
    prefill program holds the kernel's own name exactly when it does."""
    engine = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32", kv_cache_dtype="float32",
        mesh={"data": 1, "model": 1}, paged_kernel=mode)
    engine.init_params()
    sched = ServingScheduler(engine, num_slots=2, num_pages=12,
                             page_size=16, max_pages_per_slot=6,
                             prefill_chunk=8, comm_telemetry=True)
    pa = sched.health()["paged_attention"]
    assert pa["path"] == pa["multi_token"]["path"] == path
    assert set(pa["multi_token"]) >= {"path", "dispatch", "reason"}
    assert engine.paged_kernel_decision(page_size=16)["multi_token"] == \
        pa["multi_token"]
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 200, n).astype(np.int32) for n in (19, 5)]
    reqs = [sched.submit(p, max_new_tokens=4) for p in prompts]
    got = sched.run()
    for p, r in zip(prompts, reqs):
        want = engine.generate(p[None], max_new_tokens=4, do_sample=False)
        assert list(got[r.rid]) == list(np.asarray(want)[0, len(p):])
    texts = _prefill_program_texts(engine)
    assert texts and all(("paged_prefill" in t) == (path == "kernel")
                         for t in texts)


def _prefill_program_texts(engine):
    """The lowered text of every prefill signature the engine
    dispatched, from its comm-ledger capture."""
    out = []
    for (name, _, _), (fn, specs, statics) in engine._comm_capture.items():
        if name == "prefill":
            with engine._serving_scope():
                out.append(getattr(engine, fn).lower(*specs, *statics)
                           .as_text())
    return out
