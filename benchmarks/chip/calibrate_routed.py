"""Where the routed serving rule's limits come from (drive_serve.py:
ROUTED_*): a toy routed stack in plain ``jax.numpy`` (nothing of the
program is imported), run on the chip in its own bf16 arithmetic
against float32 at ``"highest"``.

    chiprun --chips 1 --timeout 3300 -- python3 \
        benchmarks/chip/calibrate_routed.py --draws 320 \
        --out chiprun_out/calibrate_routed.jsonl
    chiprun --chips 1 --timeout 2400 -- python3 \
        benchmarks/chip/calibrate_routed.py --draws 240 --base-seed 32 \
        --out chiprun_out/calibrate_routed_seed32.jsonl

are the two calls of PR 31 (TPU v5 lite; 1,060 s and 962 s): the limits
were fitted on the first and met 240 fresh draws of the second without
a failure, then set from all 560.  To calibrate at another width, add a
family to FAMILIES (or sizes to DEPTHS / HELD), run it the same way and
read ``--summary`` of the file it wrote.

A draw is (seed, family, depth, experts held, sequence length, mixer,
score function, router precision).  A family is a router of the
catalog's rows at its published widths (FAMILIES); a stack is ``depth``
pairs of a causal mixing layer (attention, or a gated linear recurrence
with a float32 state) and a routed feed-forward layer, pre-norm
residual, N(0, 0.02) weights rounded to bf16, a head of 16,384 rows.
The router scores all of its experts; the stack holds the first
``held`` of them and leaves the others' part out, in every path alike.

Every path of a draw reads the same token ids.  The float32 path is the
reference; each other path stands where the served program would, and
its greedy token at every checked position (the later half of each
sequence, as a request's served tokens follow its prompt) is held
against the reference exactly as ``drive_serve.check_outputs`` holds a
served token: margin = the reference's best logit minus its logit of
that token, eps = EPS_ULPS bf16 ulps at the reference's logit scale.

    bf16        faultless: bf16 weights and activations, float32
                router on the bf16 input, its own routing
    forced      bf16 with the reference's routing (what is left is
                rounding; the dense rule's world)
    fp8         every sublayer's normed input rounded to e4m3
    kminus1     per_token - 1 experts a token
    no_routed   the routed part left out, shared expert only
    swap_all    held experts 0 and 1 trade weights, in every layer
    swap_one    the same in the middle layer alone
    other       the faultless tokens of the neighbouring sequence

One JSON line a draw goes to ``--out``; ``--summary`` reads such a file
back and prints the tables PERF.md holds.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

EPS_ULPS = 8            # drive_serve.EPS_ULPS; the dense rule's room
STD = 0.02
TOKENS = 2048           # batch x sequence length of every draw
# what a family does not state: the head's rows, the attention mixer's
# heads, the recurrent mixer's channels
SIZES = {"vocab": 16384, "heads": 8, "head_dim": 128, "state": 1024}

# router width, experts per token, widths, activation and scaling as
# published (model-configs catalog; the row named beside each)
FAMILIES = {
    # NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
    "e128k6": dict(experts=128, per_token=6, hidden=2688, expert_width=1856,
                   shared_width=3712, act="relu2", scale=2.5),
    # JoyAI-LLM-Flash (DeepSeek-V3's router at a width that fits a draw)
    "e256k8": dict(experts=256, per_token=8, hidden=2048, expert_width=768,
                   shared_width=768, act="swiglu", scale=2.5),
    # Laguna-S-2.1
    "e256k10": dict(experts=256, per_token=10, hidden=3072,
                    expert_width=1024, shared_width=1024, act="swiglu",
                    scale=2.5),
}
DEPTHS = (4, 6, 8, 10, 12)
HELD = (8, 16, 32, 64)
SEQ_LENS = (256, 512, 1024)
MIXERS = ("attn", "ssm")
SCORES = ("sigmoid", "softmax")
ROUTER_PRECISIONS = ("highest", "default")
PATHS = ("bf16", "forced", "fp8", "kminus1", "no_routed", "swap_all",
         "swap_one")
OVER = (1, 2, 3, 4, 5, 6, 8, 10, 12, 0.5, 0.25)   # multiples of eps counted


def rms_norm(x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), -1, keepdims=True)
    return (x32 * jnp.reciprocal(jnp.sqrt(var + eps))).astype(x.dtype)


def dot(a, b, dtype, spec=None):
    """A matmul as a path of ``dtype`` does it: bf16 operands with
    float32 accumulation, rounded to bf16; float32 at "highest"."""
    prec = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    a, b = a.astype(dtype), b.astype(dtype)
    out = jnp.matmul(a, b, precision=prec,
                     preferred_element_type=jnp.float32) if spec is None \
        else jnp.einsum(spec, a, b, precision=prec,
                        preferred_element_type=jnp.float32)
    return out.astype(dtype)


def normed_input(x, fp8):
    n = rms_norm(x)
    return n.astype(jnp.float8_e4m3fn).astype(x.dtype) if fp8 else n


def activation(h, act):
    if act == "relu2":
        return jnp.square(jax.nn.relu(h))
    gate, up = jnp.split(h, 2, -1)
    return jax.nn.silu(gate) * up


def size(fam, name):
    return fam.get(name, SIZES[name])


def up_width(width, act):
    return 2 * width if act == "swiglu" else width


def layer_weights(key, fam, held, mixer):
    """One mixer + routed pair, N(0, 0.02) rounded to bf16."""
    d, f, s = fam["hidden"], fam["expert_width"], fam["shared_width"]
    shapes = {
        "router": (d, fam["experts"]),
        "up": (held, d, up_width(f, fam["act"])), "down": (held, f, d),
        "shared_up": (d, up_width(s, fam["act"])), "shared_down": (s, d)}
    if mixer == "attn":
        wide = size(fam, "heads") * size(fam, "head_dim")
        shapes.update(wq=(d, wide), wk=(d, wide), wv=(d, wide), wo=(wide, d))
    else:
        shapes.update(w_in=(d, 2 * size(fam, "state")),
                      w_out=(size(fam, "state"), d))
    keys = jax.random.split(key, len(shapes) + 1)
    w = {n: (STD * jax.random.normal(k, shape, jnp.float32)
             ).astype(jnp.bfloat16)
         for k, (n, shape) in zip(keys, sorted(shapes.items()))}
    if mixer == "ssm":
        # per-channel decay, memory of 5 to 1,000 positions
        w["decay"] = 1.0 - 10.0 ** jax.random.uniform(
            keys[-1], (size(fam, "state"),), jnp.float32, -3.0, -0.7)
    return w


def attn_mixer(x, w, dtype, fp8=False, head_dim=SIZES["head_dim"]):
    """x [b, t, d]: causal softmax attention over heads of head_dim."""
    b, t, _ = x.shape
    n = normed_input(x, fp8)
    q, k, v = (dot(n, w[m], dtype).reshape(b, t, -1, head_dim)
               for m in ("wq", "wk", "wv"))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision=jax.lax.Precision.HIGHEST
                    if dtype == jnp.float32 else None,
                    preferred_element_type=jnp.float32) / np.sqrt(head_dim)
    sc = jnp.where(jnp.tril(jnp.ones((t, t), bool)), sc, -jnp.inf)
    p = jax.nn.softmax(sc, -1)
    o = dot(p, v, dtype, "bhqk,bkhd->bqhd").reshape(b, t, -1)
    return x + dot(o, w["wo"], dtype)


def ssm_mixer(x, w, dtype, fp8=False):
    """x [b, t, d]: h_t = a h_{t-1} + (1 - a) u_t per channel, the state
    in float32 in every path, gated by silu(z)."""
    n = normed_input(x, fp8)
    u, z = jnp.split(dot(n, w["w_in"], dtype), 2, -1)
    a = jnp.broadcast_to(w["decay"], u.shape)
    drive = (1.0 - w["decay"]) * u.astype(jnp.float32)

    def step(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]
    _, h = jax.lax.associative_scan(step, (a, drive), axis=1)
    y = h.astype(dtype) * jax.nn.silu(z)
    return x + dot(y, w["w_out"], dtype)


MIXER_FNS = {"attn": attn_mixer, "ssm": ssm_mixer}


def routed_layer(x, w, fam, dtype, *, score="sigmoid", per_token=None,
                 router_precision="highest", forced=None, use_routed=True,
                 fp8=False):
    """x [tokens, d] -> (x + routed + shared, the experts chosen
    [tokens, per_token]).  The router is float32 on the path's own
    normed input and scores every expert; the part of the experts that
    are not held is left out."""
    k = fam["per_token"] if per_token is None else per_token
    n = normed_input(x, fp8)
    prec = jax.lax.Precision.HIGHEST if router_precision == "highest" \
        else jax.lax.Precision.DEFAULT
    logits = jnp.matmul(n.astype(jnp.float32),
                        w["router"].astype(jnp.float32), precision=prec)
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(s, k)
    if forced is not None:
        idx = forced
        top = jnp.take_along_axis(s, idx, -1)
    gate = fam["scale"] * top / jnp.sum(top, -1, keepdims=True)
    out = dot(activation(dot(n, w["shared_up"], dtype), fam["act"]),
              w["shared_down"], dtype)
    if use_routed:
        held = w["up"].shape[0]
        dense_gate = jnp.sum(
            gate[..., None] * jax.nn.one_hot(idx, held, dtype=jnp.float32),
            -2)
        h = activation(dot(n, w["up"], dtype, "td,edf->tef"), fam["act"])
        h = h * dense_gate[..., None].astype(dtype)
        out = out + dot(h, w["down"], dtype, "tef,efd->td")
    return x + out, idx


def swapped(w):
    """Held experts 0 and 1 trade places."""
    perm = jnp.arange(w["up"].shape[0]).at[:2].set(jnp.array([1, 0]))
    return dict(w, up=w["up"][perm], down=w["down"][perm])


def head_weights(key, fam):
    ke, kh = jax.random.split(key)
    d, v = fam["hidden"], size(fam, "vocab")
    return {"embed": (STD * jax.random.normal(ke, (v, d), jnp.float32)
                      ).astype(jnp.bfloat16),
            "head": (STD * jax.random.normal(kh, (d, v), jnp.float32)
                     ).astype(jnp.bfloat16)}


def head_logits(x, w_head, dtype):
    """Logits of hidden rows [.., d]: final norm and head, rounded to
    the path's type and returned as float32."""
    return dot(rms_norm(x), w_head, dtype).astype(jnp.float32)


def margins(ref_logits, tokens):
    """What ``check_outputs`` compares: how far each token's logit lies
    under the reference's best, and the reference's logit scale."""
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return jnp.max(ref_logits, -1) - got, jnp.max(jnp.abs(ref_logits))


def held_only(chosen, held):
    """The held experts among the sorted chosen ones, the others as -1."""
    return jnp.sort(jnp.where(chosen < held, chosen, -1), -1)


class Stack:
    """The jitted layers of one family, shared by its draws."""

    def __init__(self, fam):
        self.fam = fam
        self.mixer = {m: jax.jit(fn, static_argnames=("dtype", "fp8"))
                      for m, fn in MIXER_FNS.items()}
        self.routed = jax.jit(
            lambda x, w, forced=None, **kw: routed_layer(
                x, w, fam, forced=forced, **kw),
            static_argnames=("dtype", "score", "per_token",
                             "router_precision", "use_routed", "fp8"))
        self.weights = jax.jit(
            lambda key, held, mixer: layer_weights(key, fam, held, mixer),
            static_argnames=("held", "mixer"))
        self.swapped = jax.jit(swapped)
        self.head = jax.jit(head_logits, static_argnames=("dtype",))
        self.margins = jax.jit(margins)


def run_draw(stack, draw):
    """Every path of one draw in lockstep, a layer's weights made once.
    Returns the draw's record."""
    fam, f32, bf16 = stack.fam, jnp.float32, jnp.bfloat16
    key = jax.random.fold_in(jax.random.PRNGKey(draw["seed"] & 0x7FFFFFFF),
                             draw["seed"] >> 31)
    t = draw["seq_len"]
    b = TOKENS // t
    kw, kids = jax.random.split(key)
    hw = head_weights(kw, fam)
    ids = jax.random.randint(kids, (b, t), 0, size(fam, "vocab"))
    x0 = hw["embed"][ids]
    xs = {"ref": x0.astype(f32)}
    xs.update({p: x0 for p in PATHS})
    route_kw = dict(score=draw["score"])
    own = dict(route_kw, router_precision=draw["router_precision"])
    diff_sets = diff_held = 0
    for i in range(draw["depth"]):
        mixer = draw["mixers"][i % len(draw["mixers"])]
        w = stack.weights(jax.random.fold_in(kw, i + 1), held=draw["held"],
                          mixer=mixer)
        w_swap = stack.swapped(w)
        mix = stack.mixer[mixer]
        flat = {}
        for p, x in xs.items():
            x = mix(x, w, dtype=f32 if p == "ref" else bf16,
                    fp8=p == "fp8")
            flat[p] = x.reshape(b * t, -1)
        ref, ref_idx = stack.routed(flat["ref"], w, dtype=f32, **route_kw)
        out = {"ref": ref}
        out["bf16"], idx = stack.routed(flat["bf16"], w, dtype=bf16, **own)
        out["forced"], _ = stack.routed(flat["forced"], w, ref_idx,
                                        dtype=bf16, **own)
        out["fp8"], _ = stack.routed(flat["fp8"], w, dtype=bf16, fp8=True,
                                     **own)
        out["kminus1"], _ = stack.routed(
            flat["kminus1"], w, dtype=bf16,
            per_token=fam["per_token"] - 1, **own)
        out["no_routed"], _ = stack.routed(flat["no_routed"], w, dtype=bf16,
                                           use_routed=False, **own)
        out["swap_all"], _ = stack.routed(flat["swap_all"], w_swap,
                                          dtype=bf16, **own)
        out["swap_one"], _ = stack.routed(
            flat["swap_one"], w_swap if i == draw["depth"] // 2 else w,
            dtype=bf16, **own)
        # how often the faultless path chose another set of experts
        a, r = jnp.sort(idx, -1), jnp.sort(ref_idx, -1)
        diff_sets += int(jnp.sum(jnp.any(a != r, -1)))
        diff_held += int(jnp.sum(jnp.any(held_only(a, draw["held"])
                                         != held_only(r, draw["held"]), -1)))
        xs = {p: x.reshape(b, t, -1) for p, x in out.items()}

    checked = slice(t // 2, t)
    n = t - t // 2
    ref_logits = stack.head(xs["ref"][:, checked], hw["head"], dtype=f32)
    rec = dict(draw, positions=b * n, sequences=b, paths={},
               route_sets_differ=diff_sets / (draw["depth"] * b * t),
               route_held_differ=diff_held / (draw["depth"] * b * t))
    tokens = {}
    for p in PATHS:
        lg = stack.head(xs[p][:, checked], hw["head"], dtype=bf16)
        tokens[p] = jnp.argmax(lg, -1)
    tokens["other"] = jnp.roll(tokens["bf16"], 1, 0)
    ref_best = np.asarray(jnp.argmax(ref_logits, -1))
    for p, tok in tokens.items():
        m, scale = stack.margins(ref_logits, tok)
        m, scale = np.asarray(m, np.float64), float(scale)
        eps = EPS_ULPS * 2.0 ** -8 * scale
        rec["eps"], rec["logit_scale"] = eps, scale
        rec["paths"][p] = {
            "finite": bool(np.all(np.isfinite(m))),
            "over": [int((m > c * eps).sum()) for c in OVER],
            "worst": float(m.max() / eps),
            "mean": float(m.mean() / eps),
            "exact": int((np.asarray(tok) == ref_best).sum()),
            # per sequence, and per half of its checked positions: what
            # a smaller sample would have read
            "seq_over": (m > eps).sum(1).tolist(),
            "seq_worst": (m.max(1) / eps).tolist(),
            "late_over": int((m[:, n // 2:] > eps).sum())}
    return rec


def plan(n_draws, base_seed):
    """The draws: the families in turn, every other size drawn from the
    draw's own seed (a large one: seeds over 2**31 are the driver's)."""
    draws = []
    for i in range(n_draws):
        rng = np.random.default_rng([base_seed, i])
        mixers = [str(m) for m in rng.choice(MIXERS, 2)]
        draws.append({
            "seed": int(base_seed * 1000 + i + (2 ** 31 if i % 2 else 0)),
            "family": list(FAMILIES)[i % len(FAMILIES)],
            "depth": int(rng.choice(DEPTHS)), "held": int(rng.choice(HELD)),
            "seq_len": int(rng.choice(SEQ_LENS)), "mixers": mixers,
            "score": str(rng.choice(SCORES)),
            "router_precision": str(rng.choice(ROUTER_PRECISIONS))})
    return draws


def quantiles(values):
    v = np.sort(np.asarray(values, float))
    return {"min": v[0], "p50": float(np.median(v)),
            "p90": float(np.quantile(v, 0.9)), "max": v[-1]}


def fmt(q, pct=False):
    k = 100.0 if pct else 1.0
    return " / ".join(f"{q[n] * k:.2f}" for n in ("min", "p50", "p90",
                                                   "max"))


def summary(records, out=sys.stdout):
    """The distribution of the share over eps and of the worst margin,
    for every path, then the faultless path by family, depth and held
    experts, then what a smaller sample reads."""
    def share(r, p):
        return r["paths"][p]["over"][0] / r["positions"]

    print(f"{len(records)} draws; share over eps in %, worst margin in "
          "eps: min / median / p90 / max", file=out)
    print("| path | share over eps % | worst margin / eps | exact argmax % "
          "| not finite |", file=out)
    print("| --- | --- | --- | --- | --- |", file=out)
    for p in PATHS + ("other",):
        print(f"| {p} | {fmt(quantiles([share(r, p) for r in records]), 1)}"
              f" | {fmt(quantiles([r['paths'][p]['worst'] for r in records]))}"
              f" | {fmt(quantiles([r['paths'][p]['exact'] / r['positions'] for r in records]), 1)}"
              f" | {sum(not r['paths'][p]['finite'] for r in records)} |",
              file=out)
    for key in ("family", "depth", "held", "seq_len", "score",
                "router_precision"):
        print(f"\nfaultless bf16 by {key}:", file=out)
        for val in sorted({r[key] for r in records}, key=str):
            rs = [r for r in records if r[key] == val]
            print(f"| {val} | {len(rs)} draws | share "
                  f"{fmt(quantiles([share(r, 'bf16') for r in rs]), 1)} | "
                  f"worst {fmt(quantiles([r['paths']['bf16']['worst'] for r in rs]))}"
                  f" | sets differ "
                  f"{100 * np.mean([r['route_sets_differ'] for r in rs]):.2f}%"
                  f", held {100 * np.mean([r['route_held_differ'] for r in rs]):.2f}% |",
                  file=out)
    print("\nper single sequence (a smaller sample), share over eps %:",
          file=out)
    for p in ("bf16", "fp8", "kminus1", "swap_all", "swap_one"):
        per = [o / (r["positions"] / r["sequences"])
               for r in records for o in r["paths"][p]["seq_over"]]
        print(f"| {p} | {len(per)} sequences | {fmt(quantiles(per), 1)} |",
              file=out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--draws", type=int, default=240)
    ap.add_argument("--base-seed", type=int, default=31)
    ap.add_argument("--out", default="chiprun_out/calibrate_routed.jsonl")
    ap.add_argument("--max-seconds", type=float, default=2400.0,
                    help="start no draw after this many seconds")
    ap.add_argument("--summary", help="print the tables of a written file")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal of the control flow; its numbers are "
                    "no calibration")
    args = ap.parse_args(argv)
    if args.summary:
        with open(args.summary) as f:
            summary([json.loads(line) for line in f])
        return 0
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"needs a TPU; JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    stacks, records, t0 = {}, [], time.monotonic()
    with open(args.out, "w") as f:
        for i, draw in enumerate(plan(args.draws, args.base_seed)):
            if time.monotonic() - t0 > args.max_seconds:
                print(f"stopped before draw {i}: --max-seconds",
                      file=sys.stderr)
                break
            stack = stacks.setdefault(draw["family"],
                                      Stack(FAMILIES[draw["family"]]))
            rec = dict(run_draw(stack, draw), device=dev.device_kind)
            records.append(rec)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(f"draw {i} {draw['family']} depth {draw['depth']} held "
                  f"{draw['held']} t {draw['seq_len']}: bf16 "
                  f"{rec['paths']['bf16']['over'][0]}/{rec['positions']} "
                  f"worst {rec['paths']['bf16']['worst']:.2f} "
                  f"[{time.monotonic() - t0:.0f} s]", file=sys.stderr)
    summary(records)
    return 0


if __name__ == "__main__":
    sys.exit(main())
