"""Worker for the elastic-agent integration test: trains a tiny model,
checkpointing every step; on the FIRST launch (DS_ELASTIC_RESTART_COUNT
== 0) rank 1 kills itself mid-run, so the agent must restart the group,
which resumes from `latest` and finishes the remaining steps.

Writes rank{r}.json with the steps this attempt ran and the losses, so
the test can assert loss continuity across the failure.
"""

import json
import os
import sys

TOTAL_STEPS = 6
KILL_AT_STEP = 3    # global_steps value at which rank 1 dies (attempt 0)


def main():
    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        import re
        jax.config.update("jax_platforms", "cpu")
        counts = re.findall(r"host_platform_device_count=(\d+)",
                            os.environ.get("XLA_FLAGS", ""))
        if counts:  # last occurrence wins, like XLA's own flag parsing
            jax.config.update("jax_num_cpu_devices", int(counts[-1]))

    import numpy as np
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu import comm as dist

    out_dir = sys.argv[1]
    ckpt_dir = os.path.join(out_dir, "ckpt")
    attempt = int(os.environ.get("DS_ELASTIC_RESTART_COUNT", "0"))

    dist.init_distributed()
    rank = jax.process_index()

    from tests.unit.simple_model import SimpleModel, simple_loss_fn
    model = SimpleModel()
    n_dev = len(jax.devices())
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 5e-2}},
        "mesh": {"data": n_dev},
        "steps_per_print": 1000000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=config, loss_fn=simple_loss_fn(model))

    rng = np.random.default_rng(0)
    batch = {"x": rng.normal(size=(4 * n_dev, 16)).astype(np.float32),
             "y": rng.normal(size=(4 * n_dev, 8)).astype(np.float32)}

    # resume (no-op on the very first launch: no `latest` pointer yet)
    engine.load_checkpoint(ckpt_dir, example_batch=batch)
    start = engine.global_steps

    losses = []
    while engine.global_steps < TOTAL_STEPS:
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
        engine.save_checkpoint(ckpt_dir)
        if attempt == 0 and rank == 1 and \
                engine.global_steps == KILL_AT_STEP:
            os._exit(17)   # simulated worker crash (preemption)

    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({"attempt": attempt, "start_step": start,
                   "end_step": engine.global_steps,
                   "losses": losses}, f)
    print(f"rank {rank} done: attempt={attempt} steps "
          f"{start}->{engine.global_steps}")


if __name__ == "__main__":
    main()
