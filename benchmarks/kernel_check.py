"""Compile-and-compare every Pallas kernel variant ``auto`` selects on a
TPU, at the head geometries of the models the repo ships.

Each case is one kernel at one geometry, with a plain float32 jnp
reference.  On the chip (``python benchmarks/kernel_check.py``) every
case is compiled by Mosaic, run, and compared with its reference; one
JSON verdict row per case goes to stdout and
``chiprun_out/kernel_check.json``, and the exit code is nonzero unless
every case compiled and agreed.  Without a TPU it exits nonzero naming
the backend it found.

``tests/unit/ops/test_tpu_lowering.py`` cross-lowers the same cases for
the TPU platform on CPU, so a BlockSpec that Mosaic's lowering refuses
is caught before chip time is spent.

Geometries: GPT-2 small (12 heads, d 64, context 1024), Llama-2-7B (32
heads, d 128), and Llama-2-7B heads with 8 kv heads for GQA (Mistral-7B's
too: its prefill case has the benchmark cell's 16 rows x 33 pages); pages
are 128 tokens (the TPU default of ``serving/page_manager.py``).
"""

import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.ops.attention.decode import (  # noqa: E402
    _repeat_kv, decode_attention, gather_pages, paged_decode_attention)
from deepspeed_tpu.ops.attention.flash import flash_attention  # noqa: E402
from deepspeed_tpu.ops.attention.paged_prefill import paged_prefill  # noqa: E402
from deepspeed_tpu.ops.attention.reference import mha_reference  # noqa: E402
from deepspeed_tpu.ops.quant.kernels import int8_matmul  # noqa: E402

# max |kernel - reference| <= TOL * max |reference|: bf16 keeps 8
# mantissa bits (2^-8 = 0.4% per rounding) and a kernel rounds its
# probabilities and its output once each
TOL = 2e-2
PAGE, PAGES, MAXP = 128, 64, 8


@dataclasses.dataclass
class Case:
    name: str
    fn: callable        # the kernel, interpret=False
    ref: callable       # float32 jnp reference, same signature
    args: list          # [(kind or draw(rng, shape), shape, dtype)] — see _make

    def specs(self):
        return [jax.ShapeDtypeStruct(shape, dtype)
                for _, shape, dtype in self.args]

    def make_args(self, seed=0):
        rng = np.random.default_rng(seed)
        return [_make(rng, *a) for a in self.args]


def _make(rng, kind, shape, dtype):
    if callable(kind):
        return jnp.asarray(kind(rng, shape), dtype)
    if kind == "normal":
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)
    if kind == "int8":
        return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    if kind == "scale":
        return jnp.asarray(rng.uniform(0.005, 0.02, shape), jnp.float32)
    if kind == "table":
        return jnp.asarray(rng.integers(0, PAGES, shape), jnp.int32)
    if kind == "positions":     # first slot empty-but-one, second full
        pos = rng.integers(0, MAXP * PAGE, shape)
        pos[0], pos[1] = 0, MAXP * PAGE - 1
        return jnp.asarray(pos, jnp.int32)
    if kind == "mask_bias":     # [b, 1, 1, max_len] validity mask
        live = rng.integers(1, shape[-1] + 1, (shape[0], 1, 1, 1))
        return jnp.where(np.arange(shape[-1]) < live, 0.0,
                         jnp.finfo(jnp.float32).min).astype(jnp.float32)
    raise ValueError(kind)


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


def _flash_case(name, b, l, h, d, dtype, grad):
    x = ("normal", (b, l, h, d), dtype)

    def fwd(attn):
        if not grad:
            return attn
        # cotangent g: every output element gets its own weight
        return jax.grad(lambda q, k, v, g: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * g), argnums=(0, 1, 2))
    kernel = fwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))
    ref = fwd(lambda q, k, v: mha_reference(*_f32(q, k, v), causal=True))
    args = [x, x, x] + ([("normal", (b, l, h, d), jnp.float32)]
                        if grad else [])
    return Case(name, kernel, ref, args)


def _gathered_f32(kp, vp, pt, ks, vs):
    """Each row's whole table gathered to float32 K/V, dequantized where
    the pool carries scales."""
    k, v = (gather_pages(x, pt).astype(jnp.float32) for x in (kp, vp))
    if ks is not None:
        k, v = k * gather_pages(ks, pt), v * gather_pages(vs, pt)
    return k, v


def _paged_case(name, h, kv_h, d, q_dtype, kv_dtype, slots=8):
    quant = kv_dtype == jnp.int8
    pool = ("int8" if quant else "normal", (PAGES, PAGE, kv_h, d), kv_dtype)
    args = [("normal", (slots, 1, h, d), q_dtype), pool, pool,
            ("table", (slots, MAXP), jnp.int32),
            ("positions", (slots,), jnp.int32)]
    if quant:
        args += [("scale", (PAGES, PAGE, kv_h, 1), jnp.float32)] * 2

    def kernel(q, kp, vp, pt, pos, ks=None, vs=None):
        return paged_decode_attention(
            q, kp, vp, pt, pos, interpret=False, force_kernel=True,
            k_scale=ks, v_scale=vs)

    def ref(q, kp, vp, pt, pos, ks=None, vs=None):
        k, v = _gathered_f32(kp, vp, pt, ks, vs)
        live = jnp.arange(MAXP * PAGE)[None, None, None, :] <= \
            pos[:, None, None, None]
        bias = jnp.where(live, 0.0, jnp.finfo(jnp.float32).min)
        return mha_reference(q.astype(jnp.float32),
                             _repeat_kv(k, h // kv_h),
                             _repeat_kv(v, h // kv_h), causal=False,
                             bias=bias)
    return Case(name, kernel, ref, args)


def _prefill_case(name, h, kv_h, d, q_dtype, kv_dtype, rows=16, l=32,
                  maxp=MAXP):
    """The paged flash-prefill kernel: ``rows`` chunks of ``l`` tokens,
    each at its own start (0, page-aligned, mid-page, the table's end)
    with its own count of valid columns (one padding row).  Padding
    columns are zeroed on both sides: the kernel reads no page past a
    row's last written position, the reference all of them."""
    quant = kv_dtype == jnp.int8
    pool = ("int8" if quant else "normal", (PAGES, PAGE, kv_h, d), kv_dtype)

    def starts(rng, shape):
        st = rng.integers(0, maxp * PAGE - l + 1, shape)
        st[:4] = 0, PAGE, PAGE + 37, maxp * PAGE - l
        return st

    def counts(rng, shape):
        ct = rng.integers(1, l + 1, shape)
        ct[:4] = l, l, 0, l
        return ct
    args = [("normal", (rows, l, h, d), q_dtype), pool, pool,
            ("table", (rows, maxp), jnp.int32),
            (starts, (rows,), jnp.int32), (counts, (rows,), jnp.int32)]
    if quant:
        args += [("scale", (PAGES, PAGE, kv_h, 1), jnp.float32)] * 2

    def valid(out, ct):
        return jnp.where((jnp.arange(l)[None, :] < ct[:, None])
                         [:, :, None, None], out, 0)

    def kernel(q, kp, vp, pt, st, ct, ks=None, vs=None):
        return valid(paged_prefill(q, kp, vp, ks, vs, pt, st, ct,
                                   scale=d ** -0.5, interpret=False), ct)

    def ref(q, kp, vp, pt, st, ct, ks=None, vs=None):
        k, v = _gathered_f32(kp, vp, pt, ks, vs)
        live = jnp.arange(maxp * PAGE)[None, None, None, :] <= \
            (st[:, None] + jnp.arange(l)[None, :])[:, None, :, None]
        bias = jnp.where(live, 0.0, jnp.finfo(jnp.float32).min)
        return valid(mha_reference(q.astype(jnp.float32),
                                   _repeat_kv(k, h // kv_h),
                                   _repeat_kv(v, h // kv_h), causal=False,
                                   bias=bias), ct)
    return Case(name, kernel, ref, args)


def _decode_case(name, h, kv_h, d, dtype, b=4, max_len=1024):
    args = [("normal", (b, 1, h, d), dtype),
            ("normal", (b, max_len, kv_h, d), dtype),
            ("normal", (b, max_len, kv_h, d), dtype),
            ("mask_bias", (b, 1, 1, max_len), jnp.float32)]

    def kernel(q, k, v, bias):
        return decode_attention(q, k, v, bias=bias, interpret=False,
                                force_kernel=True)

    def ref(q, k, v, bias):
        q, k, v = _f32(q, k, v)
        return mha_reference(q, _repeat_kv(k, h // kv_h),
                             _repeat_kv(v, h // kv_h), causal=False,
                             bias=bias)
    return Case(name, kernel, ref, args)


def _int8_matmul_case(name, m, k, n, group=128):
    args = [("normal", (m, k), jnp.bfloat16), ("int8", (k, n), jnp.int8),
            ("scale", (k // group, n), jnp.float32)]

    def ref(x, q, s):
        w = q.astype(jnp.float32) * jnp.repeat(s, group, axis=0)
        return x.astype(jnp.float32) @ w
    return Case(name, lambda x, q, s: int8_matmul(x, q, s, interpret=False),
                ref, args)


bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
CASES = [
    # the smoke's own kernels: GPT-2 small
    _flash_case("flash_fwd_h12_d64_L1024_bf16", 8, 1024, 12, 64, bf16,
                grad=False),
    _flash_case("flash_fwd_bwd_h12_d64_L1024_bf16", 8, 1024, 12, 64, bf16,
                grad=True),
    _paged_case("paged_mha_h12_d64_bf16kv", 12, 12, 64, bf16, bf16),
    _paged_case("paged_mha_h12_d64_f32kv", 12, 12, 64, f32, f32),
    # what auto picks for the other models the repo ships
    _paged_case("paged_mha_h12_d64_int8kv", 12, 12, 64, bf16, i8),
    _paged_case("paged_mha_h32_d128_bf16kv", 32, 32, 128, bf16, bf16),
    _paged_case("paged_mha_h32_d128_int8kv", 32, 32, 128, bf16, i8),
    _paged_case("paged_gqa_h32_kv8_d128_bf16kv", 32, 8, 128, bf16, bf16),
    _paged_case("paged_gqa_h32_kv8_d128_int8kv", 32, 8, 128, bf16, i8),
    # chunked prefill and verify: Mistral-7B's cell geometry (16 rows x
    # 33 pages, chunk 32), GPT-2's MHA d 64, a verify of K+1 = 9
    _prefill_case("prefill_gqa_h32_kv8_d128_r16_p33_bf16kv", 32, 8, 128,
                  bf16, bf16, maxp=33),
    _prefill_case("prefill_gqa_h32_kv8_d128_int8kv", 32, 8, 128, bf16, i8),
    _prefill_case("prefill_mha_h12_d64_bf16kv", 12, 12, 64, bf16, bf16),
    _prefill_case("prefill_mha_h12_d64_f32kv", 12, 12, 64, f32, f32),
    _prefill_case("verify_gqa_h32_kv8_d128_l9_bf16kv", 32, 8, 128, bf16,
                  bf16, rows=32, l=9),
    _decode_case("decode_h12_d64_bf16", 12, 12, 64, bf16),
    _decode_case("decode_h32_d128_bf16", 32, 32, 128, bf16),
    _decode_case("decode_gqa_h32_kv8_d128_bf16", 32, 8, 128, bf16),
    _int8_matmul_case("int8_matmul_m8_k768_n3072", 8, 768, 3072),
    _int8_matmul_case("int8_matmul_m8_k4096_n11008", 8, 4096, 11008),
]


def run_case(case):
    """Compile, run and compare one case on the chip -> verdict row."""
    row = {"name": case.name}
    args = case.make_args()
    try:
        t0 = time.monotonic()
        compiled = jax.jit(case.fn).lower(*args).compile()
        row["compile_s"] = round(time.monotonic() - t0, 2)
        row["mosaic_calls"] = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        got = compiled(*args)
    except Exception as e:   # the verdict table must cover every case
        traceback.print_exc()
        row.update(verdict="refused", error=f"{type(e).__name__}: "
                   f"{str(e)[:400]}")
        return row
    with jax.default_matmul_precision("highest"):
        want = jax.jit(case.ref)(*args)
    errs = [float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)) /
                  jnp.max(jnp.abs(w)))
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    row["rel_err"] = round(max(errs), 5)
    finite = all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                 for g in jax.tree.leaves(got))
    ok = finite and row["mosaic_calls"] > 0 and row["rel_err"] <= TOL
    row["verdict"] = "ok" if ok else "mismatch"
    return row


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"kernel_check: needs a TPU; JAX found backend "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    rows = []
    for case in CASES:
        rows.append(run_case(case))
        print(json.dumps(rows[-1]), flush=True)
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_check.json"), "w") as f:
        json.dump({"device": device, "tol": TOL, "cases": rows}, f,
                  indent=1)
    bad = [r["name"] for r in rows if r["verdict"] != "ok"]
    print(json.dumps({"ok": not bad, "failed": bad, "device": device}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
