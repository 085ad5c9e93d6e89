"""Ahead-of-time case builders for the paged kernels' SHARED READ over a
latent page pool (multi-head latent attention): ``heads`` query heads on
ONE latent head, the pool's one leaf ``[pages, page_size, stored]``, the
value the leading ``value_dim`` features of the key block.  The builders
of test_chip_bench_aot.py hand the kernels two pools; these hand them
one and ``v_pages=None`` (test_chip_bench_aot.kernel_builder loads this
file by the name a case gives)."""

import jax.numpy as jnp


def _pool(c, spec, dt):
    return spec((c["pages"], c["page_size"], c["stored_dim"]), dt)


def paged_decode_latent(c, spec):
    from deepspeed_tpu.ops.attention.decode import _paged_decode_pallas
    dt = jnp.dtype(c["dtype"])
    args = (spec((c["slots"], 1, c["heads"], c["stored_dim"]), dt),
            _pool(c, spec, dt),
            spec((c["slots"], c["max_pages"]), jnp.int32),
            spec((c["slots"],), jnp.int32))

    def f(q, pool, table, pos):
        return _paged_decode_pallas(
            q, pool, None, table, pos, scale=c["scale_dim"] ** -0.5,
            interpret=False, value_dim=c["value_dim"])
    return f, args, 1


def paged_prefill_latent(c, spec):
    from deepspeed_tpu.ops.attention.paged_prefill import paged_prefill
    dt = jnp.dtype(c["dtype"])
    args = (spec((c["rows"], c["chunk"], c["heads"], c["stored_dim"]), dt),
            _pool(c, spec, dt),
            spec((c["rows"], c["max_pages"]), jnp.int32),
            spec((c["rows"],), jnp.int32), spec((c["rows"],), jnp.int32))

    def f(q, pool, table, start, count):
        return paged_prefill(q, pool, None, None, None, table, start, count,
                             scale=c["scale_dim"] ** -0.5, interpret=False,
                             value_dim=c["value_dim"])
    return f, args, 1
