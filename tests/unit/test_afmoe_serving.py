"""A tiny AFMoE through the normal serving path -- ``init_inference`` +
``ServingScheduler``: one full layer over the page pool, four window
layers over rings of ``window + 2 pages`` rows that the paged path reads
through a derived table (ops/attention/window.py ``page_view``), routed
blocks with a shared expert -- against the plain reference's full
forward (benchmarks/chip/reference_afmoe.py).

Logits are compared wherever the engine hands them over (chunked
prefill's boundary rows, the model's decode step); through the
scheduler, which hands over tokens, each served token's MARGIN under the
reference's best logit.  ``TOL`` = 2e-6 absolute at a logit scale of
~0.7, as tests/unit/test_afmoe.py reads it (4e-7 of float32 rounding).
The window is 32, its ring 64 rows in pages of 16, the pool's pages 8:
the prompts are longer than the window, the served sequences than the
ring.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import afmoe
from deepspeed_tpu.models.afmoe import AFMoE, afmoe_tiny
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.serving import ServingScheduler

from test_afmoe import (IDS, TOL, drawn_params, reference_args,
                        reference_logits)
from test_serving_ride import hold_walls


def build_engine(**kw):
    eng = deepspeed_tpu.init_inference(
        AFMoE(afmoe_tiny()), dtype="float32", kv_cache_dtype="float32", **kw)
    eng.set_params(drawn_params(afmoe_tiny()))
    return eng


@pytest.fixture(scope="module")
def engine():
    return build_engine()


@pytest.fixture(scope="module")
def want(engine):
    return reference_logits(engine.params, IDS,
                            reference_args(engine.module.cfg))


_DECODE = {}


def decode_logits(engine, tok, active, table, lengths, pools):
    """One decode step of the MODEL through pools and rings (the
    engine's decode primitives return sampled tokens), one trace an
    engine."""
    def run(params, tok, active, table, lengths, layers):
        step = kv_cache.decode_step(layers, table, lengths, active)
        logits, new = engine.module.apply({"params": params}, tok[:, None],
                                          cache=step)
        return logits[:, 0], new.pools, new.lengths
    if id(engine) not in _DECODE:
        _DECODE[id(engine)] = jax.jit(run)
    with engine._serving_scope():
        logits, pools, lengths = _DECODE[id(engine)](
            engine.params, jnp.asarray(tok), jnp.asarray(active),
            jnp.asarray(table), jnp.asarray(lengths), pools["layers"])
    return np.asarray(logits), pools, np.asarray(lengths)


@pytest.mark.parametrize("chunk,kernel,n_decode", [
    (8, "auto", 15), (12, "auto", 4), (18, "auto", 2), (18, "force", 2)])
def test_chunked_prefill_then_decode_through_pool_and_ring(
        engine, want, chunk, kernel, n_decode):
    """60 prompt tokens -- nearly two windows -- in chunks of ``chunk``
    into slot 2 (12 straddles a ring page at every other chunk, 18 at
    every one and is the longest the ring takes), every chunk's boundary
    logits the reference's; then teacher-forced decode steps, past the
    ring's 64 rows.  ``force`` runs both paged Pallas kernels in interpret mode,
    with and without a window."""
    eng = engine if kernel == "auto" else build_engine(paged_kernel=kernel)
    pools = eng.init_paged_cache(12, 8, num_slots=3)
    table = np.array([[12] * 10, [12] * 10,
                      [0, 1, 2, 3, 4, 5, 6, 7, 8, 9]], np.int32)
    lengths = np.zeros(3, np.int32)
    n_prompt = 60
    for at in range(0, n_prompt, chunk):
        ids = np.zeros((1, chunk), np.int32)
        part = IDS[at:min(at + chunk, n_prompt)]
        ids[0, :len(part)] = part
        logits, pools = eng.prefill_into_slots(
            ids, [2], [len(part)], table, lengths, pools)
        lengths[2] += len(part)
        np.testing.assert_allclose(logits[0], want[lengths[2] - 1],
                                   atol=TOL, rtol=0)
    for t in range(n_prompt, n_prompt + n_decode):
        tok = np.zeros(3, np.int32)
        tok[2] = IDS[t]
        logits, pools, new_len = decode_logits(
            eng, tok, [False, False, True], table, lengths, pools)
        assert list(new_len) == [0, 0, lengths[2] + 1]   # advanced ONCE
        lengths = new_len
        np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0)


def test_a_chunk_longer_than_the_ring_takes_says_so(engine):
    pools = engine.init_paged_cache(12, 8, num_slots=1)
    with pytest.raises(ValueError, match="at most 18 columns"):
        engine.prefill_into_slots(
            np.zeros((1, 19), np.int32), [0], [19],
            np.arange(10, dtype=np.int32)[None], np.zeros(1, np.int32),
            pools)


def test_a_slot_reused_by_a_shorter_request_sees_nothing_of_the_last(
        engine, want):
    """Another request's 70 tokens through slot 1 (its rings wrapped),
    then OUR first 11 tokens into the same slot from position 0: the
    rings are NOT cleared, and the boundary logits are the reference's
    of our prompt alone."""
    pools = engine.init_paged_cache(10, 8, num_slots=2)
    table = np.array([[10] * 10, list(range(10))], np.int32)
    lengths = np.zeros(2, np.int32)
    other = np.random.default_rng(9).integers(0, 256, 70).astype(np.int32)
    for at in range(0, 70, 14):
        _, pools = engine.prefill_into_slots(
            other[None, at:at + 14], [1], [14], table, lengths, pools)
        lengths[1] += 14
    held = np.asarray(pools["layers"][0]["k_ring"][1])
    assert (np.abs(held).max(axis=(1, 2)) > 0).all()     # every row used
    lengths[1] = 0
    logits, pools = engine.prefill_into_slots(IDS[None, :14], [1], [11],
                                              table, lengths, pools)
    np.testing.assert_allclose(logits[0], want[10], atol=TOL, rtol=0)
    # rows 11 on still hold the last tenant's keys: masked, not cleared
    assert np.array_equal(held[11:],
                          np.asarray(pools["layers"][0]["k_ring"][1, 11:]))


# ------------------------------------------------ the normal serving path

def margins(engine, prompt, out_tokens):
    ids = np.concatenate([prompt, out_tokens]).astype(np.int32)
    lg = reference_logits(engine.params, ids,
                          reference_args(engine.module.cfg))
    pos = len(prompt) - 1 + np.arange(len(out_tokens))
    return lg[pos].max(-1) - lg[pos, out_tokens]


# (prompt, new): prompts past the window, sequences past the ring
LENS = [(40, 30), (5, 9), (61, 12), (33, 40), (8, 14), (52, 9)]


@pytest.fixture(scope="module")
def served(engine):
    """Six requests over 3 slots: chunked prefill beside decode, fused
    horizons, riding decode rows while requests wait, slots reused by
    shorter requests."""
    rng = np.random.default_rng(0)
    sched = ServingScheduler(engine, num_slots=3, num_pages=40, page_size=8,
                             max_pages_per_slot=13, prefill_chunk=8,
                             decode_horizon_steps=4)
    # a waiting request makes the decoding slots RIDE the prefill
    # dispatch, and a horizon follows
    hold_walls(sched, 1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n, _ in LENS]
    reqs = [sched.submit(p, m) for p, (_, m) in zip(prompts, LENS)]
    sched.run()
    return sched, prompts, reqs


def test_served_tokens_are_the_references_argmax_to_rounding(engine, served):
    sched, prompts, reqs = served
    s = sched.summary()
    assert s["ride_rows"] > 0 and s["decode_steps"] > 0
    assert s["state_resets"] == len(reqs) and s["preemptions"] == 0
    for p, r in zip(prompts, reqs):
        assert r.state == "finished" and len(r.out_tokens) == \
            r.max_new_tokens
        assert margins(engine, p, np.asarray(r.out_tokens)).max() <= TOL
    assert sched.kv.pool.pages_in_use == 0


def test_page_and_ring_bytes_are_the_arithmetic(served):
    """A page is the one FULL layer's alone; a slot carries the four
    window layers' rings of 64 rows."""
    sched, _, _ = served
    h, s = sched.health(), sched.summary()
    eng = sched.engine
    # 1 full layer x 8 positions x 2 KV heads x (16 + 16) x 4 bytes
    assert eng.kv_page_bytes(8) == 8 * 2 * 32 * 4
    assert h["kv_pool_bytes_total"] == 40 * eng.kv_page_bytes(8)
    # 4 window layers x 64 rows x 2 KV heads x (16 + 16) x 4 bytes
    per_slot = 4 * 64 * 2 * 32 * 4
    assert eng.state_bytes_per_slot() == per_slot
    assert eng.window_ring() == (32, per_slot)
    # + the four routed layers' counters (5 uint32 each)
    assert h["state_pool_bytes_total"] == 3 * per_slot + 4 * 5 * 4
    assert s["kv_paged_bytes_per_token"] == 2 * 32 * 4
    assert s["kv_window_bytes_per_slot"] == per_slot
    assert 0 < s["moe_held_assignments"] < s["moe_assignments"]
    assert s["moe_calls"] % 4 == 0


def test_what_the_window_layers_needed_is_counted_exactly(engine):
    """No rider here (nothing waits).  Decode token i of a request
    attends over its prompt and its i tokens so far, cut to 32 in a
    window layer; a prefill chunk of n columns from position s reads,
    there, the window of its first query and itself, and column j
    scores the last 32 of s + j + 1 keys."""
    sched = ServingScheduler(engine, num_slots=3, num_pages=30, page_size=8,
                             max_pages_per_slot=10, prefill_chunk=8,
                             decode_horizon_steps=4)
    rng = np.random.default_rng(4)
    lens = [(5, 9), (61, 6)]
    for n, m in lens:
        sched.submit(rng.integers(0, 256, n).astype(np.int32), m)
    sched.run()
    s = sched.summary()
    assert s["preemptions"] == 0 and s["ride_rows"] == 0
    assert s["decode_window_tokens"] == sum(
        min(n + i, 32) for n, m in lens for i in range(1, m))

    def chunks(n):
        return [(at, min(8, n - at)) for at in range(0, n, 8)]
    assert s["prefill_window_tokens"] == sum(
        min(at + c, 32 + c - 1) for n, _ in lens for at, c in chunks(n))
    assert s["prefill_window_pairs"] == sum(
        min(p + 1, 32) for n, _ in lens for p in range(n))
    assert s["prefill_window_pairs"] < s["prefill_kv_pairs"] == sum(
        n * (n + 1) // 2 for n, _ in lens)


def test_the_prefix_cache_is_refused_with_its_reason(engine):
    sched = ServingScheduler(engine, num_slots=2, num_pages=8, page_size=8,
                             prefix_cache=True)
    h = sched.health()
    assert sched.prefix_cache is None and h["prefix_cache"] is False
    assert "AFMoE keeps a window ring per slot" in h["prefix_cache_refused"]


@pytest.mark.parametrize("kwargs,feature", [
    ({"spec_decode": "ngram"}, "spec_decode"),
    ({"seq_parallel_threshold": 64}, "seq_parallel_prefill"),
    ({"on_handoff": lambda *a: None}, "handoff"),
])
def test_what_cannot_carry_a_ring_raises_by_name(engine, kwargs, feature):
    with pytest.raises(ValueError, match=feature) as err:
        ServingScheduler(engine, num_slots=2, num_pages=8, page_size=8,
                         **kwargs)
    assert "AFMoE keeps a window ring per slot" in str(err.value)
    assert afmoe.AFMoE.slot_state == "a window ring"
