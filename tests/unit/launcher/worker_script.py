"""Worker used by the launcher integration test: rendezvous through
``comm.init_distributed`` and reduce across processes.

Run via the `deepspeed_tpu` CLI (tests/unit/launcher/test_launcher.py);
the launcher provides COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID.
"""

import os
import sys


def main():
    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        import re
        jax.config.update("jax_platforms", "cpu")
        counts = re.findall(r"host_platform_device_count=(\d+)",
                            os.environ.get("XLA_FLAGS", ""))
        if counts:  # last occurrence wins, like XLA's own flag parsing
            jax.config.update("jax_num_cpu_devices", int(counts[-1]))

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu import comm as dist

    out_dir = sys.argv[1]
    dist.init_distributed()
    rank = jax.process_index()
    world = jax.process_count()
    assert world == int(os.environ["NUM_PROCESSES"]), world

    # a real cross-process reduction: each process contributes its local
    # shard (filled with rank+1) of a data-sharded global array
    mesh = dist.get_mesh()
    n_local = len(jax.local_devices())
    n_total = len(jax.devices())
    sharding = NamedSharding(mesh, P(mesh.axis_names))
    x = jax.make_array_from_process_local_data(
        sharding, np.full((n_local,), float(rank + 1), np.float32),
        (n_total,))
    total = float(jax.device_get(jax.jit(jnp.sum, out_shardings=None)(x)))
    expect = n_local * sum(r + 1 for r in range(world))
    assert abs(total - expect) < 1e-6, (total, expect)

    with open(os.path.join(out_dir, f"rank{rank}.txt"), "w") as f:
        f.write(f"{world} {total}\n")
    print(f"rank {rank}/{world} ok total={total}")


if __name__ == "__main__":
    main()
