"""Collective benchmark sweep (reference benchmarks/communication/run_all.py
+ bin/ds_bench): psum / all_gather / reduce_scatter / all_to_all /
ppermute over the active mesh, across message sizes, reporting latency
and algorithmic/bus bandwidth via the comms logger's formulas.

Usage:
    python benchmarks/communication/run_all.py [--axis data]
        [--maxsize 26] [--trials 5] [--dtype float32] [--json out.json]

Runs on whatever devices are visible (one TPU chip -> trivial loopback;
the 8-device virtual CPU mesh exercises real collectives; a TPU pod
exercises ICI).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--axis", default="data")
    p.add_argument("--maxsize", type=int, default=24,
                   help="log2 of the largest message in bytes")
    p.add_argument("--minsize", type=int, default=16)
    p.add_argument("--trials", type=int, default=5)
    p.add_argument("--warmups", type=int, default=2)
    p.add_argument("--dtype", default="float32")
    p.add_argument("--ops", default="all_reduce,all_gather,reduce_scatter,"
                                    "all_to_all,ppermute,"
                                    "compressed_allreduce")
    p.add_argument("--json", default=None)
    args = p.parse_args()

    import jax
    if os.environ.get("DSTPU_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices",
                          int(os.environ.get("DSTPU_BENCH_CPU")))
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu import comm as dist
    from deepspeed_tpu.comm.telemetry import bench_row, write_ledger_json
    from deepspeed_tpu.parallel.topology import make_mesh

    if dist.get_mesh() is None:
        dist.set_mesh(make_mesh())
    mesh = dist.get_mesh()
    ax = args.axis
    n = mesh.shape[ax]
    dtype = jnp.dtype(args.dtype)
    print(f"# mesh={dict(mesh.shape)} axis={ax} n={n} "
          f"platform={jax.default_backend()}", file=sys.stderr)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def compressed(x):
        # 1-bit error-feedback allreduce (runtime/comm/compressed.py):
        # sign bits + one scale per phase on the wire
        from deepspeed_tpu.runtime.comm.compressed import \
            compressed_allreduce
        we = jnp.zeros_like(x)
        pad = (-x.size) % (n * 8)
        se = jnp.zeros((x.size + pad) // n, x.dtype)
        out, _, _ = compressed_allreduce(x, we, se, ax)
        return out

    OPS = {
        "all_reduce": lambda x: lax.psum(x, ax),
        "all_gather": lambda x: lax.all_gather(x, ax, tiled=True),
        "reduce_scatter": lambda x: lax.psum_scatter(x, ax, tiled=True),
        "all_to_all": lambda x: lax.all_to_all(
            x.reshape(n, -1), ax, 0, 0, tiled=False).reshape(-1),
        "ppermute": lambda x: lax.ppermute(x, ax, perm),
        "compressed_allreduce": compressed,
    }
    results = []
    for op_name in args.ops.split(","):
        fn = OPS[op_name]
        size = 1 << args.minsize
        while size <= (1 << args.maxsize):
            elems = max(size // dtype.itemsize, n * n)
            elems -= elems % (n * n)      # per-shard length must also
                                          # divide by n (scatter/all2all)
            x = jnp.asarray(np.random.default_rng(0)
                            .standard_normal(elems), dtype)
            times = []
            for t in range(args.warmups + args.trials):
                t0 = time.time()
                out = dist.eager_collective(fn, x, group=ax,
                                            op_name=op_name)
                jax.block_until_ready(out)
                dt = time.time() - t0
                if t >= args.warmups:
                    times.append(dt)
            lat = float(np.median(times))
            # the canonical comm-ledger row schema (comm/telemetry.py)
            # — bench_row expects the per-rank message size and applies
            # the op's own bw scaling via calc_bw_log
            row = bench_row(
                "all_reduce" if op_name == "compressed_allreduce"
                else op_name, size // max(n, 1), lat, n, axis=ax)
            # keep bench_row's canonical op-scaled bytes so offline
            # rows join runtime ledger_rows exactly; only the op name
            # is restored (compressed_allreduce rides all_reduce's
            # bandwidth formulas)
            row["op"] = op_name
            if op_name == "compressed_allreduce" and n > 1:
                # bytes-on-wire per rank: each rank quantizes its LOCAL
                # shard (eager_collective splits dim 0 over the axis) and
                # ships sign bits in both phases — but all_to_all out and
                # all_gather back each keep 1/n of the payload local, so
                # only (n-1)/n of the sign bits cross the wire per phase,
                # plus the n scales; vs 2*(n-1)/n * shard for a ring
                # allreduce at this dtype. All wire fields are skipped at
                # n == 1 where nothing leaves the chip.
                shard = elems // n
                offchip = (n - 1) / n
                wire = int(2 * offchip * (shard // 8)) \
                    + 2 * (n - 1) * dtype.itemsize
                row["wire_bytes_per_rank"] = wire
                row["uncompressed_allreduce_wire_bytes"] = int(
                    2 * offchip * shard * dtype.itemsize)
                row["compression_x"] = round(
                    row["uncompressed_allreduce_wire_bytes"] / wire, 2)
            results.append(row)
            print(json.dumps(row))
            size <<= 2
    if args.json:
        # committed rounds survive re-runs under previous_committed
        write_ledger_json(args.json, {"mesh": dict(mesh.shape),
                                      "axis": ax, "results": results})
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
