"""Chip smoke: the serve and train main paths, once, on a real TPU.

Drives the two normal entry points at the full width of GPT-2 small
(12 layers, hidden 768, 12 heads, vocab 50257, context 1024; bf16,
random weights from a seed) in ONE process, and checks what comes out:

* serve — the ``bin/ds_serve`` code path (``parse_args`` ->
  ``build_engine`` -> ``serve_single`` -> ``ServingScheduler``) with the
  CLI's defaults: every row ``finished``, the paged Pallas kernel on the
  path, compile counts inside their bucket bounds, a prefix-cache hit,
  and every served token an eps-argmax of a float32 teacher-forced
  reference forward over the served stream;
* train — ``deepspeed_tpu.initialize`` -> ``engine.train_loop`` on
  ``bench.py``'s configuration: finite falling loss from ln(vocab), and
  the Pallas flash kernel inside the compiled step.

With >= 4 devices both phases run again over a ``model=2 x data=2``
mesh (serve under the shard_map kernel dispatch, train under ZeRO-3).

There are no CPU shapes and no interpret mode here: without a TPU the
script exits nonzero naming the backend it found.  A failed check
raises; nothing wraps a phase.  The last stdout line is one JSON object
``{"ok": true, "device": {...}}``; exit code 0 only if every check
passed.

    python chip_smoke.py
"""

import importlib.machinery
import importlib.util
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SERVE_MODEL = "gpt2-small"
CONTEXT = 1024      # GPT-2's context: train sequences, reference padding
TRAIN_BATCH, TRAIN_STEPS = 8, 10
# the serve check accepts a token whose float32-reference logit is
# within EPS_ULPS bf16 ulps (2^-8 relative) of the reference maximum,
# at the observed logit scale: random-init logits are nearly flat
# (std 0.55, max ~3), so a batched bf16 paged kernel and a batch-1
# float32 forward flip near-ties.  Measured on one v5e chip (PR 22):
# worst margin 0.0185 at scale 3.155 = 1.5 ulps, 602 of 616 tokens the
# exact argmax; a token from ANOTHER request's stream misses by about
# the whole logit range, 30x this bound
EPS_ULPS = 8
# train: AdamW lr 1e-4 on one repeated batch; measured 10.975 -> 8.887
# in 10 steps on one v5e chip (PR 22)
TRAIN_MIN_DROP = 1.0


def check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


def load_ds_serve():
    path = os.path.join(REPO, "bin", "ds_serve")
    loader = importlib.machinery.SourceFileLoader("ds_serve_cli", path)
    spec = importlib.util.spec_from_loader("ds_serve_cli", loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


class CompileClock:
    """Seconds XLA spent compiling (or loading executables from the
    persistent cache), from jax.monitoring's own duration events —
    the part of a cold phase that a warm compile cache takes away.
    Tracing and lowering are host Python time and stay in the wall."""

    def __init__(self):
        import jax
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def lap(self):
        secs, self.secs = self.secs, 0.0
        return secs


def make_requests(vocab):
    """A dozen seeded requests over 8 slots: prompts from under one
    prefill chunk (32) to several 128-token pages, 32-64 new tokens.

    One scheduler step advances every prefilling slot by one chunk and
    every decoding slot by one 8-token horizon, so a request lives
    about len/32 + new/8 steps.  Pages are donated to the prefix cache
    at retirement, and request 11 is admitted at the 4th retirement:
    the donor (rid 0, ~9 steps) sits in the first wave among requests
    of >= 13 steps, and the short prompts queue behind it.  Donor and
    sharer have 150 tokens in common (> one page)."""
    rng = np.random.default_rng(0)
    shape = [(160, 32), (16, 64), (700, 32), (200, 64), (257, 64),
             (320, 48), (512, 40), (400, 56),
             (48, 48), (64, 64), (24, 64), (420, 48)]
    rows = [{"prompt": rng.integers(0, vocab, n).tolist(),
             "max_new_tokens": new} for n, new in shape]
    rows[11]["prompt"][:150] = rows[0]["prompt"][:150]
    return rows


def serve_phase(clock, mesh=None):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2, gpt2_small

    ds = load_ds_serve()
    tag = f"serve[{mesh or 'default mesh'}]"
    rows_in = make_requests(50257)
    with tempfile.TemporaryDirectory() as tmp:
        outp = os.path.join(tmp, "out.jsonl")
        argv = ["--model", SERVE_MODEL, "--dtype", "bfloat16",
                "--kv-dtype", "bfloat16", "--output", outp]
        if mesh:
            argv += ["--mesh", mesh]
        args = ds.parse_args(argv)
        t0 = time.monotonic()
        engine = ds.build_engine(args)

        def serve(rows):
            sched, _ = ds.serve_single(
                args, engine, [json.dumps(r) for r in rows])
            with open(outp) as f:
                out = [json.loads(ln) for ln in f]
            return sched, out[:-1], out[-1]

        # probe: request 1 alone, to learn its stream and pick an eos id
        # whose FIRST occurrence is mid-horizon.  Token 0 comes from the
        # prefill boundary and tokens 1-8, 9-16, ... from 8-step
        # horizons, so index i is step (i - 1) % 8 of its horizon;
        # random-init greedy streams are runs of repeats, so take the
        # first new token that starts strictly inside a horizon
        _, probe, _ = serve([rows_in[1]])
        stream = probe[0]["tokens"]
        check(probe[0]["status"] == "finished" and len(stream) == 64,
              f"{tag}: probe request: {probe[0]}")
        inside = [i for i in range(2, 64) if stream[i] not in stream[:i]
                  and 1 <= (i - 1) % 8 <= 6]
        check(inside, f"{tag}: no token of the probe stream first occurs "
              f"mid-horizon; change the seed: {stream}")
        eos_at = next((i for i in inside if i >= 9), inside[0])
        rows_in[1]["eos_token_id"] = stream[eos_at]
        probe_wall, probe_compile = time.monotonic() - t0, clock.lap()

        t0 = time.monotonic()
        sched, rows, summary = serve(rows_in)
        wall, compile_s = time.monotonic() - t0, clock.lap()
    health = summary["health"]
    print(f"{tag}: build + 1-request probe {probe_wall:.1f}s "
          f"(XLA compile {probe_compile:.1f}s); 12 requests {wall:.1f}s "
          f"(XLA compile {compile_s:.1f}s)")

    # every row finished; none failed or shed
    states = [r["status"] for r in rows]
    check(all(s == "finished" for s in states),
          f"{tag}: rows not finished: {states} "
          f"{[r.get('error') for r in rows if r.get('error')]}")
    for r, q in zip(rows, rows_in):
        n = len(r["tokens"])
        want = eos_at + 1 if "eos_token_id" in q else q["max_new_tokens"]
        check(n == want, f"{tag}: rid {r['rid']} emitted {n} tokens, "
              f"expected {want}")
    # batched among 7 other slots, request 1 replays its solo stream
    # up to and including the eos, and stops there
    check(rows[1]["tokens"] == stream[:eos_at + 1],
          f"{tag}: eos at index {eos_at} did not cut request 1's stream")

    # the kernel path, as health() reports it
    pa = health["paged_attention"]
    want = "shard_map" if engine.mesh.size > 1 else "direct"
    check(pa["path"] == "kernel" and pa["dispatch"] == want,
          f"{tag}: paged_attention {pa}")
    n_multi = engine.serving_decode_multi_compile_count()
    check(1 <= n_multi <= len(sched.horizon_buckets),
          f"{tag}: decode_multi compiled {n_multi} signatures for "
          f"buckets {sched.horizon_buckets}")
    check(rows[11]["cached_prefix_tokens"] > 0,
          f"{tag}: prefix sharer got no cached tokens")
    print(f"{tag}: 12/12 finished, paged_attention={pa['path']}/"
          f"{pa['dispatch']}, decode_multi signatures={n_multi} "
          f"(buckets {list(sched.horizon_buckets)}), prefix hit "
          f"{rows[11]['cached_prefix_tokens']} tokens, eos at token "
          f"{eos_at}, {sum(len(r['tokens']) for r in rows)} tokens served")

    # eps-argmax against the float32 reference: the SAME weights upcast,
    # the non-paged forward with jnp attention, true float32 matmuls
    t0 = time.monotonic()
    ref = deepspeed_tpu.init_inference(
        GPT2(gpt2_small(dtype=jnp.float32, param_dtype=jnp.float32,
                        attn_impl="reference")),
        dtype="float32", mesh_obj=engine.mesh)
    ref.set_params(jax.tree.map(lambda x: x.astype(jnp.float32),
                                engine.params))

    @jax.jit
    def margins(logits, ids):
        # logits[t] scores ids[t + 1]
        lg = logits[0, :-1].astype(jnp.float32)
        got = jnp.take_along_axis(lg, ids[0, 1:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - got, jnp.max(jnp.abs(lg), axis=-1), \
            jnp.argmax(lg, axis=-1) == ids[0, 1:]

    worst, scale, agree, total = 0.0, 0.0, 0, 0
    with jax.default_matmul_precision("highest"):
        for r, q in zip(rows, rows_in):
            p, toks = q["prompt"], r["tokens"]
            ids = np.zeros((1, CONTEXT), np.int32)
            ids[0, :len(p) + len(toks)] = p + toks
            m, s, eq = margins(ref.forward(ids), jnp.asarray(ids))
            span = slice(len(p) - 1, len(p) + len(toks) - 1)
            m, s, eq = (np.asarray(x)[span] for x in (m, s, eq))
            check(np.all(np.isfinite(m)), f"{tag}: non-finite reference")
            worst, scale = max(worst, float(m.max())), \
                max(scale, float(s.max()))
            agree, total = agree + int(eq.sum()), total + len(toks)
    eps = EPS_ULPS * 2.0 ** -8 * scale
    print(f"{tag}: float32 reference {time.monotonic() - t0:.1f}s "
          f"(XLA compile {clock.lap():.1f}s): worst margin {worst:.4f} vs "
          f"eps {eps:.4f} ({EPS_ULPS} bf16 ulps at logit scale "
          f"{scale:.3f}); exact argmax agreement {agree}/{total}")
    check(worst <= eps, f"{tag}: a served token is {worst:.4f} below the "
          f"float32 reference maximum (eps {eps:.4f})")
    return engine, sched, health


def train_phase(clock, mesh=None, zero_stage=1):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2, GPTConfig

    # bench.py's mesh: data-parallel over every visible device
    mesh = mesh or {"data": len(jax.devices())}
    tag = f"train[{mesh}, zero{zero_stage}]"
    cfg = GPTConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=CONTEXT,
                    dtype=jnp.bfloat16)
    config = {
        "train_micro_batch_size_per_gpu": TRAIN_BATCH,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4,
                                                  "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": zero_stage},
        "mesh": mesh,
        "steps_per_print": 1000000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2(cfg),
                                               config=config)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size,
        size=(TRAIN_BATCH * mesh.get("data", 1), CONTEXT))
        .astype(np.int32)}

    clock.lap()
    t0 = time.monotonic()
    first = engine.train_loop([batch] * TRAIN_STEPS, sync=True)
    cold, compile_s = time.monotonic() - t0, clock.lap()
    t0 = time.monotonic()
    second = engine.train_loop([batch] * TRAIN_STEPS, sync=True)
    run = time.monotonic() - t0
    losses = np.concatenate([np.asarray(first), np.asarray(second)])
    print(f"{tag}: first train_loop({TRAIN_STEPS} steps) {cold:.1f}s "
          f"(XLA compile {compile_s:.1f}s), second {run:.2f}s; loss "
          f"{losses[0]:.3f} -> {losses[TRAIN_STEPS - 1]:.3f} -> "
          f"{losses[-1]:.3f}")
    check(np.all(np.isfinite(losses)), f"{tag}: non-finite loss {losses}")
    check(abs(losses[0] - math.log(cfg.vocab_size)) <= 0.3,
          f"{tag}: first loss {losses[0]:.3f} is not ln(vocab) = "
          f"{math.log(cfg.vocab_size):.2f} +- 0.3")
    check(losses[TRAIN_STEPS - 1] < losses[0] - TRAIN_MIN_DROP,
          f"{tag}: loss fell {losses[0] - losses[TRAIN_STEPS - 1]:.3f} in "
          f"{TRAIN_STEPS} steps, need {TRAIN_MIN_DROP}")
    compiled = {k: n for k, n in engine.train_compile_counts().items() if n}
    check(compiled == {"step_loop": 1}, f"{tag}: compile counts {compiled}")

    # attn_impl=auto must have taken the Pallas flash kernel, not
    # mha_reference: the compiled step carries Mosaic custom calls
    hlo = engine.compiled_step_text(batch)
    n_mosaic = hlo.count('custom_call_target="tpu_custom_call"')
    check(n_mosaic > 0, f"{tag}: no Mosaic custom call in the compiled "
          "train step (flash kernel not on the path)")
    print(f"{tag}: {n_mosaic} Mosaic custom calls in the compiled step "
          f"(XLA compile of the single-step executable {clock.lap():.1f}s)")
    return engine


def multichip_checks(serve_engine, sched, health, train_engine):
    import jax
    devs = jax.devices()[:4]
    total, per = health["kv_pool_bytes_total"], \
        health["kv_pool_bytes_per_device"]
    check(per * 2 == total, f"kv pool per-device {per} != total {total}/2")

    def on_all(tree, what):
        for leaf in jax.tree.leaves(tree):
            got = {s.device for s in leaf.addressable_shards}
            check(got >= set(devs), f"{what}: a leaf lives on "
                  f"{sorted(d.id for d in got)} only")
    on_all(serve_engine.params, "serve params")
    on_all(sched.pools, "kv pools")
    on_all(train_engine.state.params, "train params")
    in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
    check(min(in_use) > 64 << 20,
          f"a device holds almost nothing: bytes_in_use {in_use}")
    print(f"4 chips: kv pool {per / 1e6:.1f} MB/device of "
          f"{total / 1e6:.1f} MB, bytes_in_use per device "
          f"{[round(b / 1e6, 1) for b in in_use]} MB")


def main():
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: devices {device}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found backend "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    clock = CompileClock()
    t0 = time.monotonic()
    serve_phase(clock)
    train_phase(clock)
    if device["count"] >= 4:
        s_engine, sched, health = serve_phase(clock, mesh="model=2,data=2")
        t_engine = train_phase(clock, mesh={"data": 2, "model": 2},
                               zero_stage=3)
        multichip_checks(s_engine, sched, health, t_engine)
    print(f"chip_smoke: all checks passed in {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
