"""Ahead-of-time case builders for the paged kernels given ``window=``
over a sliding-window layer's RING read as a page pool
(ops/attention/window.py ``page_view``): ``slots`` rings of
``ring_pages`` pages of ``page_size`` rows each -- the window and two
pages -- reshaped ``[slots x ring_pages, page_size, kv_heads, head_dim]``,
and a table ``ring_pages`` wide a row.  The builders of
test_chip_bench_aot.py pass no window (test_chip_bench_aot.
kernel_builder loads this file by the name a case gives)."""

import jax.numpy as jnp


def _pool(c, spec, dt):
    return spec((c["slots"] * c["ring_pages"], c["page_size"],
                 c["kv_heads"], c["head_dim"]), dt)


def paged_decode_window(c, spec):
    from deepspeed_tpu.ops.attention.decode import _paged_decode_pallas
    dt = jnp.dtype(c["dtype"])
    args = (spec((c["slots"], 1, c["heads"], c["head_dim"]), dt),
            _pool(c, spec, dt), _pool(c, spec, dt),
            spec((c["slots"], c["ring_pages"]), jnp.int32),
            spec((c["slots"],), jnp.int32))

    def f(q, k, v, table, pos):
        return _paged_decode_pallas(
            q, k, v, table, pos, scale=c["head_dim"] ** -0.5,
            interpret=False, window=c["window"])
    return f, args, 1


def paged_prefill_window(c, spec):
    from deepspeed_tpu.ops.attention.paged_prefill import paged_prefill
    dt = jnp.dtype(c["dtype"])
    args = (spec((c["rows"], c["chunk"], c["heads"], c["head_dim"]), dt),
            _pool(c, spec, dt), _pool(c, spec, dt),
            spec((c["rows"], c["ring_pages"]), jnp.int32),
            spec((c["rows"],), jnp.int32), spec((c["rows"],), jnp.int32))

    def f(q, k, v, table, start, count):
        return paged_prefill(q, k, v, None, None, table, start, count,
                             scale=c["head_dim"] ** -0.5, interpret=False,
                             window=c["window"])
    return f, args, 1
