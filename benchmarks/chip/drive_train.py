"""Kind "train": ``deepspeed_tpu.initialize`` -> ``engine.train_loop``
over fresh seeded batches, ``steps_per_dispatch`` optimizer steps to a
dispatch so the host's clock spans a quarter of a second or more.
"""

import math
import time

import numpy as np

import loadgen
import reference
from drive_serve import build_module, program_field

# the engine's step-0 loss (bf16 compute, float32 master weights) against
# the plain float32 reference on the same batch and the same initial
# weights.  At random init the logits are near 0 and the loss near
# ln(vocab); bf16's 2**-8 relative rounding of activations moves the
# mean over 8,192 tokens by 5e-4 at most (14 runs on the chip, PR 25).
# Ten times that: a wrong shift, mask or head moves the loss by tenths.
LOSS_TOLERANCE = 0.005


def on_all_devices(tree, devices):
    import jax
    want = set(devices)
    return all({s.device for s in leaf.addressable_shards} >= want
               for leaf in jax.tree.leaves(tree))


def run(ctx):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    config, mix = ctx.config, ctx.traffic
    train = config["train"]
    seq, vocab = mix["seq_len"], config["vocab_size"]
    dp = int(train["mesh"].get("data", 1))
    batch = train["micro_batch_per_chip"] * dp
    k = int(mix["steps_per_dispatch"])
    tokens = loadgen.ZipfTokens(vocab, mix["zipf_exponent"])
    rng = np.random.default_rng(loadgen.seed_words(ctx.seed))

    def chunk():
        return [{"input_ids": tokens.batch(rng, batch, seq)}
                for _ in range(k)]

    ds_config = {
        "train_micro_batch_size_per_gpu": train["micro_batch_per_chip"],
        "gradient_accumulation_steps": 1,
        "optimizer": train["optimizer"],
        "bf16": {"enabled": config["dtype"] == "bfloat16"},
        "zero_optimization": {"stage": train["zero_stage"]},
        "mesh": train["mesh"],
        "steps_per_print": 10 ** 9,
    }
    first = chunk()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=build_module(config, dtype=jnp.dtype(config["dtype"])),
        config=ds_config,
        example_batch=first[0], seed=int(ctx.seed) & 0x7FFFFFFF)
    init_params = jax.tree.map(jnp.copy, engine.state.params)
    warm_losses = np.asarray(engine.train_loop(first, sync=True))
    ctx.memory("warm")

    ann = jax.profiler.TraceAnnotation
    losses, steps, traced_steps = [], 0, 0
    ctx.begin_window()
    t0 = time.monotonic()
    while True:
        now = time.monotonic() - t0
        if now >= ctx.seconds:
            break
        ctx.tick(now)
        batches = chunk()
        with ann("bench.train_loop"):
            out = engine.train_loop(batches, sync=True)
        losses.extend(np.asarray(out).tolist())
        steps += k
        traced_steps += k if ctx.tracing() else 0
    window = time.monotonic() - t0
    ctx.tick(window, end=True)
    ctx.end_window()

    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(engine.state.params))
    tenth = max(1, len(losses) // 10)
    ref = config["reference"]
    with jax.default_matmul_precision("highest"):
        ref_loss = float(getattr(reference, ref["loss"])(
            init_params, jnp.asarray(first[0]["input_ids"]),
            **{a: config[b] for a, b in ref["args"].items()}))
    del init_params
    compiled = {n: c for n, c in engine.train_compile_counts().items() if c}
    hlo = engine.compiled_step_text(first[0])
    checks = {
        "finite": bool(np.all(np.isfinite(losses))
                       and np.all(np.isfinite(warm_losses))),
        "step0_is_ln_vocab": abs(float(warm_losses[0]) - math.log(vocab))
        <= 0.3,
        "step0_matches_reference": abs(float(warm_losses[0]) - ref_loss)
        <= LOSS_TOLERANCE,
        "loss_falls": float(np.mean(losses[-tenth:]))
        < float(np.mean(losses[:tenth])),
        "one_step_loop_compile": compiled == {"step_loop": 1},
        "mosaic_in_step": 'custom_call_target="tpu_custom_call"' in hlo
        or train.get("expect_mosaic", True) is False,
    }
    if dp > 1:
        checks["params_on_all_chips"] = on_all_devices(
            engine.state.params, ctx.devices)
    heads, hidden, layers = (program_field(config, f) for f in (
        "num_heads", "hidden_size", "num_layers"))
    notes = {"loss_step0": float(warm_losses[0]), "loss_reference": ref_loss,
             "loss_first_tenth": float(np.mean(losses[:tenth])),
             "loss_last_tenth": float(np.mean(losses[-tenth:])),
             "steps": steps, "window_s": window, "compiled": compiled,
             "n_params": n_params}
    return {
        "checks": checks, "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s": steps * batch * seq / window},
        "counters": {"traced_steps": traced_steps, "steps": steps},
        "static": {
            "train": {"n_params": n_params, "layers": layers,
                      "hidden": hidden, "seq": seq},
            "flash": {"batch_per_chip": train["micro_batch_per_chip"],
                      "heads": heads, "seq": seq,
                      "head_dim": hidden // heads, "layers": layers}},
        "notes": notes}
