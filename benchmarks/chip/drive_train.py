"""Kind "train": ``deepspeed_tpu.initialize`` -> ``engine.train_loop``
over fresh seeded batches, ``steps_per_dispatch`` optimizer steps to a
dispatch so the host's clock spans a quarter of a second or more.
"""

import math
import time

import numpy as np

import loadgen
from drive_serve import build_module, numeric_items, program_field

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
    reference_loss = ctx.reference("loss")
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
    mesh = None
    if len(ctx.devices) < len(jax.devices()):
        # a host with more devices than the cell takes (the 8 virtual
        # CPU devices of the tests): the program's own mesh builder over
        # the cell's devices; otherwise the engine builds it itself
        from deepspeed_tpu.parallel.topology import make_mesh
        from deepspeed_tpu.runtime.config import MeshConfig
        mesh = make_mesh(MeshConfig(**train["mesh"]), devices=ctx.devices)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=build_module(config, dtype=jnp.dtype(config["dtype"])),
        config=ds_config, mesh=mesh,
        example_batch=first[0], seed=int(ctx.seed) & 0x7FFFFFFF)
    # the initial parameters, for the step-0 reference after the window:
    # a second copy on the device, or on the host where the sharded
    # state leaves the step program no room beside one
    on_host = train.get("initial_params", "device") == "host"
    if on_host:
        shardings = jax.tree.map(lambda a: a.sharding, engine.state.params)
        init_params = jax.device_get(engine.state.params)
    else:
        init_params = jax.tree.map(jnp.copy, engine.state.params)
    ctx.mark("engine_built")
    warm_losses = np.asarray(engine.train_loop(first, sync=True))
    # a mix with few steps to a dispatch warms more of them: the engine's
    # throughput timer syncs (and compiles a scalar add) when its step
    # count reaches 2, which must not fall inside the window
    for _ in range(int(mix.get("warm_dispatches", 1)) - 1):
        engine.train_loop(chunk(), sync=True)
    ctx.memory("warm")
    ctx.mark("warm_up_done")

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
    if on_host:
        init_params = jax.device_put(init_params, shardings)
    with jax.default_matmul_precision("highest"):
        ref_loss = float(reference_loss(
            init_params, jnp.asarray(first[0]["input_ids"]),
            **ctx.reference_args()))
    del init_params
    compiled = {n: c for n, c in engine.train_compile_counts().items() if c}
    # at random init the logits' variance is hidden x 0.02**2 and the loss
    # sits half of that over ln(vocab): a wider model states its own room
    ln_vocab_limit = train.get("step0_ln_vocab_limit", 0.3)
    off_ln_vocab = abs(float(warm_losses[0]) - math.log(vocab))
    off_reference = abs(float(warm_losses[0]) - ref_loss)
    first_tenth = float(np.mean(losses[:tenth]))
    last_tenth = float(np.mean(losses[-tenth:]))
    hlo = engine.compiled_step_text(first[0])
    checks = {
        "finite": bool(np.all(np.isfinite(losses))
                       and np.all(np.isfinite(warm_losses))),
        "step0_is_ln_vocab": off_ln_vocab <= ln_vocab_limit,
        "step0_matches_reference": off_reference <= LOSS_TOLERANCE,
        "loss_falls": last_tenth < first_tenth,
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
             "loss_first_tenth": first_tenth, "loss_last_tenth": last_tenth,
             "steps": steps, "window_s": window, "compiled": compiled,
             "n_params": n_params}
    return {
        "checks": checks, "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s": steps * batch * seq / window},
        # the train engine keeps no counters object of its own: its
        # compile counts per jitted callable are what it can be asked
        "counters": dict(numeric_items(compiled, "compiled."),
                         traced_steps=traced_steps, steps=steps),
        "compared": {
            "loss_step0_vs_ln_vocab": [off_ln_vocab, ln_vocab_limit],
            "loss_step0_vs_reference": [off_reference, LOSS_TOLERANCE],
            "loss_last_tenth_vs_first": [last_tenth, first_tenth]},
        "static": {
            "train": {"n_params": n_params, "layers": layers,
                      "hidden": hidden, "seq": seq},
            "flash": {"batch_per_chip": train["micro_batch_per_chip"],
                      "heads": heads, "seq": seq,
                      "head_dim": hidden // heads, "layers": layers}},
        "notes": notes}
