"""Decode-kernel perf regression guard (VERDICT r2 #8).

Times the Pallas KV-decode kernel against the jnp reference at serving
shapes on the real chip and FAILS (exit 1) if the kernel is slower —
the guard that keeps the `softmax_context`-equivalent kernel earning
its keep. Prints one JSON line per shape.

Run on TPU: python benchmarks/decode_guard.py
(off-TPU it reports interpret-mode numbers and skips the assertion).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SHAPES = [
    # (batch, heads, kv_heads, head_dim, cache_len)  — serving shapes
    (1, 12, 12, 64, 1024),     # gpt2-small single stream
    (8, 12, 12, 64, 1024),     # small batch serving
    (1, 32, 8, 128, 2048),     # llama-7B-ish GQA
]


def time_fn(fn, args, iters=50):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    # fence through a host transfer of a value derived from the output
    float(jax.device_get(out.sum()))
    return (time.time() - t0) / iters


def main():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention.decode import decode_attention
    from deepspeed_tpu.ops.attention.reference import mha_reference
    from deepspeed_tpu.ops.attention.decode import _repeat_kv

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)
    ok = True
    for b, h, kv_h, d, L in SHAPES:
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, L, kv_h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, L, kv_h, d)), jnp.bfloat16)
        # validity mask for a 3/4-full cache
        pos = np.zeros((b, 1, 1, L), np.float32)
        pos[..., 3 * L // 4:] = -1e30
        bias = jnp.asarray(pos)

        # force_kernel: off-TPU decode_attention now routes interpret
        # mode to the jnp reference (serving hot path); this guard
        # exists to time the KERNEL, so pin it explicitly
        kernel = jax.jit(lambda q, k, v, bias: decode_attention(
            q, k, v, bias=bias, force_kernel=True))

        def ref(q, k, v, bias):
            kf = _repeat_kv(k, h // kv_h)
            vf = _repeat_kv(v, h // kv_h)
            return mha_reference(q, kf, vf, causal=False, bias=bias)

        ref_j = jax.jit(ref)
        t_kernel = time_fn(kernel, (q, k, v, bias))
        t_ref = time_fn(ref_j, (q, k, v, bias))
        speedup = t_ref / t_kernel
        row = {"metric": "decode_kernel_speedup_vs_jnp",
               "value": round(speedup, 3), "unit": "x",
               "extra": {"shape": [b, h, kv_h, d, L],
                         "kernel_us": round(t_kernel * 1e6, 1),
                         "jnp_us": round(t_ref * 1e6, 1),
                         "platform": jax.default_backend()}}
        print(json.dumps(row))
        if on_tpu and speedup < 1.0:
            ok = False
    if on_tpu and not ok:
        print("FAIL: decode kernel slower than the jnp reference",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
