"""Benchmark: GPT-2-small training throughput on the available TPU chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline anchor (BASELINE.md): the reference's ZeRO-3 Offload sustained
50 TFlops/GPU on V100 = 40% MFU (50/125 fp16 peak). vs_baseline is
our_MFU / 0.40, so 1.0 == matching the reference's best published
utilization on its own hardware class.
"""

import json
import os
import time

import numpy as np

def guess_peak(device):
    # the per-chip peak table lives with the profiler now (the live MFU
    # gauge in resilience/supervisor.py reads the same numbers)
    from deepspeed_tpu.profiling.flops_profiler.profiler import (
        peak_flops_per_device)
    return peak_flops_per_device(device)


def run_config(gas, batch, seq, n_dev):
    """Train GPT-2-small for a timed window; returns (tokens/s, loss).
    gas>1 uses the engine's scan-fused window (one dispatch per
    optimizer step), with micro = batch // gas so tokens/step is the
    same in every configuration."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2, GPTConfig

    on_tpu = jax.devices()[0].platform == "tpu"
    micro = batch // gas
    cfg = GPTConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=seq, dtype=jnp.bfloat16)
    model = GPT2(cfg)
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4,
                                                  "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "mesh": {"data": n_dev},
        "steps_per_print": 1000000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)

    rng = np.random.default_rng(0)
    micros = [{"input_ids": rng.integers(
        0, cfg.vocab_size,
        size=(micro * n_dev, seq)).astype(np.int32)} for _ in range(gas)]

    # Both configs drive the scan-over-steps fused loop (train_loop):
    # `span` complete optimizer steps (fused gas windows at gas>1) per
    # dispatch. Identical math to per-step forward/backward/step
    # (tests/unit/test_engine.py asserts the trajectories match); it
    # amortizes the per-dispatch host overhead over the span.
    span = 5
    micros_rep = micros * span   # span whole windows per dispatch

    def step():
        return engine.train_loop(micros_rep, sync=False)

    def fence():
        # a host transfer of a value derived from the params cannot
        # complete before every prior async-dispatched step has finished
        leaf = jax.tree.leaves(engine.state.params)[0]
        return float(jax.device_get(jnp.sum(leaf)))

    # warmup (compile); collect losses so the loss-after-23-steps stat
    # stays comparable with earlier rounds' 23-dispatch protocol
    all_losses = []
    for _ in range(3):
        all_losses.append(step())
    fence()

    n_calls = 20 if on_tpu else 3
    n_steps = n_calls * span
    t0 = time.time()
    for _ in range(n_calls):
        all_losses.append(step())
    fence()
    dt = time.time() - t0
    loss23 = np.concatenate([jax.device_get(l) for l in all_losses])[22] \
        if on_tpu else float(jax.device_get(all_losses[-1][-1]))

    profile = None
    if gas == 1 and os.environ.get("DS_BENCH_PROFILE"):
        # per-module measured breakdown on THE SAME engine/config the
        # numbers above came from (engine.module_profile): the full
        # table goes to stderr, the top HBM-traffic consumers ride the
        # JSON line so a step-time regression carries its own diagnosis
        import sys
        from deepspeed_tpu.profiling.module_profiler import (
            top_traffic_consumers)
        records, table = engine.module_profile(micros[0], depth=3)
        print(table, file=sys.stderr)
        profile = [
            {k: (round(v, 3) if isinstance(v, float) else v)
             for k, v in t.items()}
            for t in top_traffic_consumers(records)]

    tokens_per_step = batch * n_dev * seq
    tokens_per_sec = tokens_per_step * n_steps / dt
    loss = float(loss23)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(engine.state.params))
    # 6N per token (fwd+bwd) + attention term 12*L*hidden*seq
    flops_per_token = 6 * n_params + \
        12 * cfg.num_layers * cfg.hidden_size * seq
    return tokens_per_sec, loss, flops_per_token, profile


def main():
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    batch, seq = (8, 1024) if on_tpu else (2, 128)
    n_dev = len(jax.devices())
    tokens_per_sec, loss, flops_per_token, profile = \
        run_config(1, batch, seq, n_dev)
    gas4_tps, gas4_loss = (run_config(4, batch, seq, n_dev)[:2]
                           if batch % 4 == 0 else (None, None))

    achieved = tokens_per_sec * flops_per_token
    peak = guess_peak(jax.devices()[0]) * n_dev
    mfu = achieved / peak
    vs_baseline = mfu / 0.40

    extra = {"mfu": round(mfu, 4), "n_devices": n_dev,
             "platform": jax.devices()[0].platform,
             "device_kind": jax.devices()[0].device_kind,
             "batch": batch * n_dev, "seq": seq,
             "final_loss": loss}
    if profile is not None:
        extra["top_traffic"] = profile
    if gas4_tps is not None:
        extra["gas4_tokens_per_sec"] = round(gas4_tps, 1)
        # remaining gas4 gap is the fp32 grad accumulator's HBM traffic
        # (3 read+add+write passes over a params-sized tree per window)
        # plus micro-batch-2 matmul shapes; both shrink as micro batch
        # grows on real workloads
        extra["gas4_over_gas1"] = round(gas4_tps / tokens_per_sec, 4)
        extra["gas4_final_loss"] = gas4_loss
    print(json.dumps({
        "metric": "gpt2_small_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 4),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()
