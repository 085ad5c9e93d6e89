"""Mamba-2 mathematics (ops/ssm/mamba2.py) and the recurrent-state
contract (ops/ssm/state.py) against the plain recurrence, float32 on
the CPU.

Every tolerance here is 2e-5 absolute on values of order 1: float32
rounding through a few hundred multiply-adds and one exp.  The same
comparison with the state or the decays held in bfloat16 misses by
about 4e-3 (``test_bfloat16_state_would_fail`` shows it), so a path that
computed them in a lower precision than float32 would fail here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.ops.ssm import mamba2, state

TOL = 2e-5
HEADS, P, G, N, K = 4, 8, 2, 16, 4
DIMS = dict(heads=HEADS, head_dim=P, groups=G, state=N, inner=HEADS * P,
            conv_dim=HEADS * P + 2 * G * N, chunk=8, eps=1e-5)
WIDTH = DIMS["inner"] + DIMS["conv_dim"] + HEADS


def weights(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"conv_w": jax.random.uniform(k[0], (K, DIMS["conv_dim"]),
                                         minval=-0.5, maxval=0.5),
            "conv_b": jax.random.uniform(k[1], (DIMS["conv_dim"],),
                                         minval=-0.5, maxval=0.5),
            "dt_bias": jax.random.normal(k[2], (HEADS,)) - 2.0,
            "A_log": jnp.log(jnp.arange(1, HEADS + 1, dtype=jnp.float32)),
            "D": 1.0 + 0.1 * jax.random.normal(k[3], (HEADS,)),
            "norm": 1.0 + 0.1 * jax.random.normal(k[4], (DIMS["inner"],))}


def inputs(b, l, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, l, WIDTH))


def zeros(b):
    return (jnp.zeros((b, K - 1, DIMS["conv_dim"])),
            jnp.zeros((b, HEADS, P, N)))


def plain(zxbcdt, w, state_dtype=jnp.float32):
    """The recurrence written token by token, one row at a time."""
    inner, gn = DIMS["inner"], G * N
    outs = []
    for row in np.asarray(zxbcdt):
        z, xbc, dt = (row[:, :inner], row[:, inner:inner + DIMS["conv_dim"]],
                      row[:, inner + DIMS["conv_dim"]:])
        pad = np.concatenate([np.zeros((K - 1, xbc.shape[1])), xbc])
        conv = sum(pad[j:j + len(row)] * np.asarray(w["conv_w"])[j]
                   for j in range(K)) + np.asarray(w["conv_b"])
        act = conv / (1 + np.exp(-conv))
        x = act[:, :inner].reshape(-1, HEADS, P)
        bm = np.repeat(act[:, inner:inner + gn].reshape(-1, G, N),
                       HEADS // G, 1)
        cm = np.repeat(act[:, inner + gn:].reshape(-1, G, N), HEADS // G, 1)
        dt = np.log1p(np.exp(dt + np.asarray(w["dt_bias"])))
        a = -np.exp(np.asarray(w["A_log"]))
        h = jnp.zeros((HEADS, P, N), state_dtype)
        ys = []
        for t in range(len(row)):
            h = (jnp.exp(dt[t] * a).astype(state_dtype)[:, None, None] * h +
                 ((dt[t][:, None] * x[t])[:, :, None] *
                  bm[t][:, None, :]).astype(state_dtype))
            ys.append(np.sum(np.asarray(h, np.float32) * cm[t][:, None, :],
                             -1) + np.asarray(w["D"])[:, None] * x[t])
        y = np.stack(ys).reshape(len(row), inner) * (z / (1 + np.exp(-z)))
        y = y.reshape(len(row), G, -1)
        y = y / np.sqrt(np.mean(y * y, -1, keepdims=True) + DIMS["eps"])
        outs.append(y.reshape(len(row), inner) * np.asarray(w["norm"]))
    return np.stack(outs)


@pytest.mark.parametrize("l", [1, 7, 8, 19, 32])
def test_chunked_scan_is_the_plain_recurrence_at_lengths_off_the_block(l):
    w, u = weights(), inputs(2, l)
    y, _, _ = mamba2.mixer_sequence(u, w, DIMS, *zeros(2))
    np.testing.assert_allclose(y, plain(u, w), atol=TOL, rtol=0)


def test_bfloat16_state_would_fail():
    """The tolerance is tight enough: the plain recurrence with its
    state and decays in bfloat16 is outside it."""
    w, u = weights(), inputs(2, 19)
    err = np.abs(plain(u, w, jnp.bfloat16) - plain(u, w)).max()
    assert err > 20 * TOL


@pytest.mark.parametrize("chunk", [4, 32])
def test_chunks_from_a_carried_state_are_the_whole_sequence(chunk):
    """Chunked prefill: pieces of ``chunk`` columns, the last one padded,
    each continuing from the tail and state the one before left."""
    w, u = weights(), inputs(3, 23)
    whole, tail_w, h_w = mamba2.mixer_sequence(u, w, DIMS, *zeros(3))
    tail, h = zeros(3)
    got = []
    for s in range(0, 23, chunk):
        piece = u[:, s:s + chunk]
        n = piece.shape[1]
        piece = jnp.pad(piece, ((0, 0), (0, chunk - n), (0, 0)),
                        constant_values=7.0)      # padding is not zeros
        y, tail, h = mamba2.mixer_sequence(piece, w, DIMS, tail, h,
                                           jnp.full((3,), n))
        got.append(y[:, :n])
    np.testing.assert_allclose(jnp.concatenate(got, 1), whole, atol=TOL,
                               rtol=0)
    np.testing.assert_allclose(h, h_w, atol=TOL, rtol=0)
    np.testing.assert_array_equal(tail, tail_w)


def test_token_by_token_decode_is_the_whole_sequence():
    w, u = weights(), inputs(2, 13)
    whole, _, h_w = mamba2.mixer_sequence(u, w, DIMS, *zeros(2))
    tail, h = zeros(2)
    got = []
    for t in range(13):
        y, tail, h = mamba2.mixer_token(u[:, t:t + 1], w, DIMS, tail, h)
        got.append(y)
    np.testing.assert_allclose(jnp.concatenate(got, 1), whole, atol=TOL,
                               rtol=0)
    np.testing.assert_allclose(h, h_w, atol=TOL, rtol=0)


# ------------------------------------------------ the state contract

def pool(slots=4, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed))
    return {"conv": jax.random.normal(k[0], (slots, K - 1, DIMS["conv_dim"])
                                      ).astype(jnp.bfloat16),
            "ssm": jax.random.normal(k[1], (slots, HEADS, P, N))}


def test_a_row_starting_at_position_0_ignores_what_the_slot_held():
    entry = pool()
    lengths = jnp.array([0, 5, 0, 9], jnp.int32)
    step = kv_cache.prefill_step(entry, None, lengths, jnp.array([2, 1]),
                                 jnp.array([3, 3]))
    tail, h = state.read(entry, step)
    assert not np.asarray(tail[0]).any() and not np.asarray(h[0]).any()
    np.testing.assert_array_equal(tail[1], entry["conv"][1])
    np.testing.assert_array_equal(h[1], entry["ssm"][1])


def test_padding_rows_and_inactive_slots_leave_state_bit_identical():
    entry = pool()
    lengths = jnp.array([4, 5, 0, 9], jnp.int32)
    new_tail = jnp.ones((2, K - 1, DIMS["conv_dim"]))
    new_h = jnp.ones((2, HEADS, P, N))
    # row 1 is padding and names the live slot of row 0 (as the
    # scheduler's padding rows do)
    step = kv_cache.prefill_step(entry, None, lengths, jnp.array([3, 3]),
                                 jnp.array([2, 0]))
    out = state.write(entry, step, new_tail, new_h)
    for name in ("conv", "ssm"):
        np.testing.assert_array_equal(out[name][:3], entry[name][:3])
        assert np.asarray(out[name][3] == 1).all()
    step = kv_cache.decode_step(entry, None, lengths,
                                jnp.array([True, False, True, False]))
    out = state.write(entry, step, jnp.ones((4, K - 1, DIMS["conv_dim"])),
                      jnp.ones((4, HEADS, P, N)))
    for name in ("conv", "ssm"):
        np.testing.assert_array_equal(out[name][1], entry[name][1])
        np.testing.assert_array_equal(out[name][3], entry[name][3])
        assert np.asarray(out[name][0] == 1).all()


def test_a_verify_step_is_refused_by_name():
    entry = pool()
    step = kv_cache.verify_step(entry, None, jnp.zeros(4, jnp.int32),
                                jnp.zeros(4, jnp.int32))
    with pytest.raises(NotImplementedError, match="verify"):
        state.read(entry, step)


def test_bytes_per_slot_agrees_with_the_allocated_leaves():
    entry = state.init_state(5, K, DIMS["conv_dim"], HEADS, P, N,
                             jnp.bfloat16)
    total = sum(int(a.nbytes) for a in entry.values())
    assert total == 5 * state.bytes_per_slot(K, DIMS["conv_dim"], HEADS, P,
                                             N, jnp.bfloat16)
