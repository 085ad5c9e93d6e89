"""The attention seam (ops/attention/kv_cache.py) held on its own.

Two oracles.  A decoder that exists only in this file — its own
projections, nothing of models/gpt2.py or models/llama.py, no import
from ``serving`` — reaches every cache through the seam's calls, and
served through ``InferenceEngine`` + ``ServingScheduler`` (batched
prefill with a padding row, a fused decode horizon, speculative verify)
it emits the tokens of its own ``generate()``.  And the seam's
multi-token and single-token paged paths reproduce a plain float32
gather-and-softmax in ``jax.numpy`` over MHA / GQA and bf16 / int8
pools.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.ops.quant.kv import paged_gather
from deepspeed_tpu.serving import ServingScheduler


# ------------------------------------------------- the test-local family

@dataclasses.dataclass(unsafe_hash=True)
class SeamConfig:
    vocab_size: int = 128
    hidden_size: int = 32
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    max_seq_len: int = 128

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


class SeamDecoder(nn.Module):
    """Grouped-query attention with a learned position table and a
    residual; no MLP, no norm: only what needs a cache."""
    cfg: SeamConfig

    @nn.compact
    def __call__(self, ids, positions=None, cache=None):
        cfg = self.cfg
        b, l = ids.shape
        h, kv_h, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if positions is None:
            positions = kv_cache.positions(cache, b, l)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="tok")(ids) + \
            nn.Embed(cfg.max_seq_len, cfg.hidden_size, name="pos")(positions)
        layers = []
        for i in range(cfg.num_layers):
            dense = lambda n, name: nn.Dense(n, use_bias=False,
                                             name=f"{name}_{i}")
            out, new = kv_cache.attend(
                dense(h * d, "q")(x).reshape(b, l, h, d),
                dense(kv_h * d, "k")(x).reshape(b, l, kv_h, d),
                dense(kv_h * d, "v")(x).reshape(b, l, kv_h, d),
                positions, kv_cache.layer_view(cache, i))
            x = x + dense(cfg.hidden_size, "o")(out.reshape(b, l, h * d))
            layers.append(new)
        logits = nn.Dense(cfg.vocab_size, name="head")(
            kv_cache.head_rows(cache, x))
        if cache is None:
            return logits
        return logits, kv_cache.advance(cache, layers)


# the engine finds a family's caches in the file of its class
def init_kv_cache(cfg, batch_size, max_len=None, dtype=jnp.bfloat16):
    return kv_cache.init_dense(cfg.num_layers, batch_size,
                               max_len or cfg.max_seq_len, cfg.num_kv_heads,
                               cfg.head_dim, dtype)


def init_paged_kv_cache(cfg, num_pages, page_size, dtype=jnp.bfloat16):
    return kv_cache.init_paged(cfg.num_layers, num_pages, page_size,
                               cfg.num_kv_heads, cfg.head_dim, dtype)


SERVE = dict(num_slots=4, num_pages=24, page_size=16, max_pages_per_slot=6,
             prefill_chunk=8, audit_every=1)


@pytest.fixture(scope="module")
def engine():
    eng = deepspeed_tpu.init_inference(
        model=SeamDecoder(SeamConfig()), dtype="float32",
        kv_cache_dtype="float32", mesh={"data": 1, "model": 1})
    eng.init_params()
    return eng


def _prompts():
    rng = np.random.default_rng(7)
    motif = np.asarray([5, 9, 13, 7] * 5, np.int32)   # the drafter proposes
    return [rng.integers(0, 128, n).astype(np.int32)
            for n in (3, 11, 21)] + [motif]


@pytest.mark.parametrize("spec", [None, "ngram"])
def test_test_local_decoder_serves_its_own_generate(engine, spec,
                                                    monkeypatch):
    prompts, max_new = _prompts(), [6, 9, 5, 12]
    want = [[int(t) for t in engine.generate(
        p[None], max_new_tokens=m, do_sample=False)[0, len(p):]]
        for p, m in zip(prompts, max_new)]
    rows = []
    real = engine.prefill_into_slots

    def spy(ids, slot, n_valid, *a, **kw):
        rows.append(np.asarray(n_valid).reshape(-1).tolist())
        return real(ids, slot, n_valid, *a, **kw)
    monkeypatch.setattr(engine, "prefill_into_slots", spy)
    kw = dict(spec_decode=spec, spec_k=4) if spec else {}
    sched = ServingScheduler(engine, decode_horizon_steps=8, **SERVE, **kw)
    reqs = [sched.submit(p, m) for p, m in zip(prompts, max_new)]
    done = sched.run()
    assert [list(done[r.rid]) for r in reqs] == want
    # three prompts longer than a chunk prefill together: rows pad to
    # the bucket of four, and a padding row rides with n_valid == 0
    assert any(len(r) == 4 and 0 in r for r in rows)
    assert engine.serving_decode_multi_compile_count() >= 1
    if spec:
        assert engine.serving_verify_compile_count() >= 1
        assert sched.health()["spec_draft_tokens"] > 0


# -------------------------------------- the module against plain float32

def _plain(q, k_all, v_all, pos):
    """q [b, l, h, d] at absolute positions pos [b, l] over contiguous
    float32 keys/values [b, n, kv_h, d]: causal softmax, query head i
    reads kv head i // group."""
    group = q.shape[2] // k_all.shape[2]
    k = jnp.repeat(k_all, group, axis=2)
    v = jnp.repeat(v_all, group, axis=2)
    s = jnp.einsum("blhd,bnhd->bhln", q, k) / np.sqrt(q.shape[-1])
    seen = jnp.arange(k.shape[1])[None, None, :] <= pos[:, :, None]
    s = jnp.where(seen[:, None], s, -jnp.inf)
    return jnp.einsum("bhln,bnhd->blhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, "int8"],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("mode", ["prefill", "verify", "decode"])
def test_paged_paths_match_plain_attention(kv_heads, kv_dtype, mode):
    h, d, ps, pages, maxp, slots = 4, 16, 8, 12, 3, 3
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s, np.float32))
    pools = kv_cache.init_paged(1, pages, ps, kv_heads, d,
                                kv_dtype)["layers"][0]
    table = jnp.asarray(rng.permutation(pages)[:slots * maxp]
                        .reshape(slots, maxp), jnp.int32)
    lengths = jnp.asarray([5, 0, 11], jnp.int32)

    # history: each slot's first lengths[s] positions, written through
    # the seam as a verify step of that many columns
    hist_k, hist_v = f32(slots, 12, kv_heads, d), f32(slots, 12, kv_heads, d)
    zeros = jnp.zeros((slots,), jnp.int32)
    fill = kv_cache.verify_step(pools, table, zeros, lengths)
    _, pools = kv_cache.attend(f32(slots, 12, h, d), hist_k, hist_v,
                               kv_cache.positions(fill, slots, 12), fill)

    if mode == "prefill":     # rows: slot 2, slot 0, a padding row
        rows = jnp.asarray([2, 0, 0], jnp.int32)
        count = jnp.asarray([4, 3, 0], jnp.int32)
        step = kv_cache.prefill_step(pools, table, lengths, rows, count)
        l = 4
    elif mode == "verify":
        rows, count = jnp.arange(slots), jnp.asarray([3, 0, 2], jnp.int32)
        step = kv_cache.verify_step(pools, table, lengths, count)
        l = 3
    else:
        rows, active = jnp.arange(slots), jnp.asarray([True, False, True])
        count = active.astype(jnp.int32)
        step = kv_cache.decode_step(pools, table, lengths, active)
        l = 1
    b = rows.shape[0]
    q, k, v = f32(b, l, h, d), f32(b, l, kv_heads, d), f32(b, l, kv_heads, d)
    pos = kv_cache.positions(step, b, l)
    np.testing.assert_array_equal(
        np.asarray(pos), np.asarray(lengths)[np.asarray(rows)][:, None]
        + np.arange(l)[None])
    out, new_pools = kv_cache.attend(q, k, v, pos, step)
    adv = kv_cache.advance(step, [new_pools])
    want_len = np.asarray(lengths).copy()
    np.add.at(want_len, np.asarray(rows), np.asarray(count))
    np.testing.assert_array_equal(np.asarray(adv.lengths), want_len)

    # the plain side: history + this call's valid columns, contiguous
    tol = 2e-2 if kv_dtype == "int8" else 1e-2      # 8-bit rows / bf16
    got_k, got_v = paged_gather(new_pools, table, jnp.float32)
    for r in range(b):
        s, n, start = int(rows[r]), int(count[r]), int(lengths[rows[r]])
        if n == 0:
            continue          # padding / inactive: output unused
        k_all = jnp.concatenate([hist_k[s, :start], k[r, :n]])[None]
        v_all = jnp.concatenate([hist_v[s, :start], v[r, :n]])[None]
        np.testing.assert_allclose(      # what landed in the pages
            np.asarray(got_k[s, :start + n]), np.asarray(k_all[0]),
            atol=tol * 4, rtol=tol)
        want = _plain(q[r:r + 1, :n], k_all, v_all, pos[r:r + 1, :n])
        np.testing.assert_allclose(np.asarray(out[r, :n]),
                                   np.asarray(want[0]), atol=tol * 4,
                                   rtol=tol)
