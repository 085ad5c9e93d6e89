"""Ring-attention long-context benchmark (the capability claim of
SURVEY.md §5.7: context length scales with the `sequence` mesh axis).

Compares, at a given total sequence length:
  * full flash attention on one device (memory O(L), compute O(L^2));
  * ring attention with L sharded over the sequence axis (per-device
    memory O(L/P); k/v chunks hop the ring in input dtype).

On the 1-chip TPU env the ring degenerates (P=1), so the headline row is
the single-chip flash at 32k — the ring rows need a multi-device mesh
(CI runs the 8-device virtual CPU mesh at reduced size; a pod runs the
real thing over ICI).

Usage: python benchmarks/ring_bench.py [--seq 32768] [--heads 4]
       [--dim 64] [--cpu-devices 0] [--json out.json]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fence(x):
    import jax
    import jax.numpy as jnp
    return float(jax.device_get(jnp.sum(x.astype(jnp.float32))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=32768)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--trials", type=int, default=5)
    p.add_argument("--cpu-devices", type=int, default=0,
                   help="force an N-device virtual CPU mesh")
    p.add_argument("--sparse-seqs", default="8192,16384,32768",
                   help="sequence lengths for the sparse-vs-dense sweep "
                        "('' disables)")
    p.add_argument("--json", default=None)
    args = p.parse_args()

    import jax
    if args.cpu_devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)
    import jax.numpy as jnp
    from deepspeed_tpu import comm as dist
    from deepspeed_tpu.ops.attention import flash_attention
    from deepspeed_tpu.ops.attention.ring import ring_attention_sharded
    from deepspeed_tpu.parallel.topology import make_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    n_dev = len(jax.devices())
    L, h, d = args.seq, args.heads, args.dim
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(1, L, h, d)) * 0.3, dtype)
    q, k, v = mk(), mk(), mk()
    results = []

    def bench(f, *xs, n1=10 * args.trials, n2=60 * args.trials):
        """Chained two-point measurement: the kernel runs inside ONE
        jitted fori_loop per window (iteration i+1 consumes iteration
        i's output), so per-dispatch overhead — enough to swamp a
        sub-ms sparse kernel if each call were its own dispatch —
        amortizes over the whole chain; the n2-n1
        difference then cancels the remaining per-window constant."""
        import functools

        @functools.partial(jax.jit, static_argnums=(1,))
        def run(x, n):
            return jax.lax.fori_loop(
                0, n, lambda i, x: f(x, *xs[1:]), x)

        fence(run(xs[0], n1))
        fence(run(xs[0], n2))

        def window(n):
            t0 = time.time()
            out = run(xs[0], n)
            fence(out)
            return time.time() - t0
        ds = []
        for _ in range(3):
            t1, t2 = window(n1), window(n2)
            ds.append((t2 - t1) / (n2 - n1))
        return float(np.median(ds)) * 1e3

    full = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    t_full = bench(full, q, k, v)
    row = {"metric": "full_flash_attention", "seq": L, "heads": h,
           "latency_ms": round(t_full, 2), "n_devices": 1,
           "platform": jax.default_backend()}
    results.append(row)
    print(json.dumps(row))

    if n_dev > 1:
        mesh = make_mesh(MeshConfig(sequence=n_dev))
        dist.set_mesh(mesh)
        ring = jax.jit(lambda q, k, v: ring_attention_sharded(
            q, k, v, mesh, causal=True))
        t_ring = bench(ring, q, k, v)
        err = float(jnp.max(jnp.abs(
            (ring(q, k, v) - full(q, k, v)).astype(jnp.float32))))
        row = {"metric": "ring_attention", "seq": L, "heads": h,
               "latency_ms": round(t_ring, 2), "n_devices": n_dev,
               "chunk": L // n_dev, "max_err_vs_full": round(err, 5),
               "platform": jax.default_backend()}
        # static HLO comm ledger of the compiled ring kernel: the k/v
        # chunks really hopping the sequence axis, in the same
        # (op, bytes, algbw/busbw) vocabulary as run_all.py and the
        # runtime serving ledger — bench and telemetry numbers are
        # directly comparable
        from deepspeed_tpu.profiling.comm_ledger import ledger_for
        led = ledger_for(ring, q, k, v, mesh=mesh)
        t_s = max(t_ring * 1e-3, 1e-9)
        row["comm"] = {"bytes": led["bytes"],
                       "wire_bytes": led["wire_bytes"],
                       "per_axis": led["per_axis"]}
        results.append(row)
        print(json.dumps(row))
        for op, d in sorted(led["per_op"].items()):
            crow = {"metric": "ring_comm", "op": op,
                    "bytes": d["bytes"], "wire_bytes": d["wire_bytes"],
                    "count": d["count"],
                    "latency_ms": round(t_ring, 2),
                    "algbw_gbps": round(d["bytes"] / t_s / 1e9, 3),
                    "busbw_gbps": round(d["wire_bytes"] / t_s / 1e9, 3),
                    "n": n_dev, "axis": "sequence"}
            results.append(crow)
            print(json.dumps(crow))

    # ---- block-sparse vs dense at long sequence (the measured speedup
    # backing BASELINE.md's sparse-attention row: the reference claims
    # up to ~6x over dense at long seq,
    # docs/_posts/2020-09-09-sparse-attention.md). Grid steps exist only
    # for active blocks, so latency should scale ~ layout density.
    if args.sparse_seqs:
        from deepspeed_tpu.ops.sparse_attention import (
            BigBirdSparsityConfig, BSLongformerSparsityConfig)
        for L2 in [int(s) for s in args.sparse_seqs.split(",") if s]:
            qs = jnp.asarray(rng.normal(size=(1, L2, h, d)) * 0.3, dtype)
            dense = jax.jit(
                lambda q, k, v: flash_attention(q, k, v, causal=True))
            t_dense = bench(dense, qs, qs, qs)
            row = {"metric": "dense_flash", "seq": L2,
                   "latency_ms": round(t_dense, 2),
                   "tokens_per_sec": round(L2 / t_dense * 1e3, 1)}
            results.append(row)
            print(json.dumps(row))
            # two granularities: block 128 keeps the reference patterns'
            # fine resolution (per-step overhead bound on TPU); block
            # 512 is the MXU-native tile — comparable token coverage
            # (~1.5k-token window vs HF BigBird's ~512), and the grid
            # steps are big enough to run at the layout's density
            for name, cfg in [
                ("bigbird", BigBirdSparsityConfig(
                    num_heads=h, block=128, num_random_blocks=1,
                    num_sliding_window_blocks=3, num_global_blocks=1)),
                ("bigbird_b512", BigBirdSparsityConfig(
                    num_heads=h, block=512, num_random_blocks=1,
                    num_sliding_window_blocks=1, num_global_blocks=1)),
                ("longformer", BSLongformerSparsityConfig(
                    num_heads=h, block=128,
                    num_sliding_window_blocks=3,
                    global_block_indices=[0])),
                ("longformer_b512", BSLongformerSparsityConfig(
                    num_heads=h, block=512,
                    num_sliding_window_blocks=1,
                    global_block_indices=[0])),
            ]:
                # the kernel runs causal=True, which trils the layout:
                # the EXECUTED density (and so the admissible speedup)
                # is the lower-triangle's
                layout = np.tril(np.asarray(cfg.make_layout(L2)))
                density = float(layout.mean())
                sp = jax.jit(lambda q, k, v, c=cfg: flash_attention(
                    q, k, v, causal=True, sparsity_config=c))
                t_sp = bench(sp, qs, qs, qs)
                row = {"metric": f"sparse_flash_{name}", "seq": L2,
                       "latency_ms": round(t_sp, 2),
                       "tokens_per_sec": round(L2 / t_sp * 1e3, 1),
                       "layout_density": round(density, 4),
                       "speedup_vs_dense": round(t_dense / t_sp, 2),
                       # causal dense does ~density-0.5 of the square;
                       # the layout admits at most 0.5/density speedup —
                       # how close the kernel gets IS its efficiency
                       "density_ceiling": round(0.5 / density, 2)}
                results.append(row)
                print(json.dumps(row))

    if args.json:
        # comm-ledger schema envelope; committed rounds survive re-runs
        # under previous_committed
        from deepspeed_tpu.comm.telemetry import write_ledger_json
        write_ledger_json(args.json, {"results": results})


if __name__ == "__main__":
    main()
