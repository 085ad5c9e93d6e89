"""A kernel case builder brought as a file: what a PR that adds a kind
of kernel puts beside the tests, named ``"aot_kernels_opt:<function>"``
by its case under aot/ (test_chip_bench_aot.kernel_builder).  A builder
takes the case and ``spec(shape, dtype)`` and returns the function to
compile, its arguments and the least number of Mosaic calls the compiled
text must hold.  This one is a stand-in: the paged prefill kernel over
one row of a small MHA pool, under a name the harness has never seen."""

import jax.numpy as jnp


def paged_prefill_rows(c, spec):
    from deepspeed_tpu.ops.attention.paged_prefill import paged_prefill
    dt = jnp.dtype(c["dtype"])
    pool = (c["pages"], c["page_size"], c["heads"], c["head_dim"])
    args = (spec((c["rows"], c["chunk"], c["heads"], c["head_dim"]), dt),
            spec(pool, dt), spec(pool, dt),
            spec((c["rows"], c["max_pages"]), jnp.int32),
            spec((c["rows"],), jnp.int32), spec((c["rows"],), jnp.int32))

    def f(q, k, v, table, start, count):
        return paged_prefill(q, k, v, None, None, table, start, count,
                             scale=c["head_dim"] ** -0.5, interpret=False)
    return f, args, 1
