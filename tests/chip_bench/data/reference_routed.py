"""Test-local routed family: pairs of a causal mixing layer and a routed
feed-forward layer (128 sigmoid scores, top 6 normalised and scaled, a
share of the experts held, a shared expert) at tiny widths, built from
the layers of the harness's calibration toy (``calibrate_routed.py``).
The tests drop this file into a copy of the harness; a configuration
names it as ``"reference_routed:<function>"`` and declares its router
under ``reference.routed``.  ``hidden``/``logits`` are the plain float32
reference; ``served`` is the bf16 twin that stands where a program
would, with the faults a test can switch on.  Nothing is imported from
the program under test."""

import jax
import jax.numpy as jnp
import numpy as np

import calibrate_routed as toy

F32 = jnp.float32
FAULTS = ("fp8", "kminus1", "no_routed", "swap_all")


def family(config):
    """The toy's family out of a configuration's own keys."""
    return {"experts": config["n_routed_experts"],
            "per_token": config["num_experts_per_tok"],
            "hidden": config["hidden_size"],
            "expert_width": config["moe_intermediate_size"],
            "shared_width": config["moe_shared_expert_intermediate_size"],
            "act": config["mlp_hidden_act"],
            "scale": config["routed_scaling_factor"],
            "vocab": config["vocab_size"], "heads": 2,
            "head_dim": config["head_dim"], "state": config["ssm_state_size"]}


def init_params(seed, config):
    """Seeded N(0, 0.02) weights rounded to bf16, as a program serves
    them: ``held_experts`` of the router's experts in every layer."""
    fam = family(config)
    key = jax.random.PRNGKey(seed)
    params = toy.head_weights(key, fam)
    for i, mixer in enumerate(config["mixers"]):
        params[f"layer_{i}"] = toy.layer_weights(
            jax.random.fold_in(key, i + 1), fam, config["held_experts"],
            mixer)
    return params


def stack(params, ids, fam, mixers, dtype, fault=None):
    """Final-norm hidden states [b, t, hidden] of token ids [b, t]."""
    b, t = ids.shape
    x = params["embed"][ids].astype(dtype)
    fp8 = fault == "fp8"
    for i, mixer in enumerate(mixers):
        w = params[f"layer_{i}"]
        x = toy.attn_mixer(x, w, dtype, fp8, fam["head_dim"]) \
            if mixer == "attn" else toy.ssm_mixer(x, w, dtype, fp8)
        x, _ = toy.routed_layer(
            x.reshape(b * t, -1), toy.swapped(w) if fault == "swap_all"
            else w, fam, dtype, fp8=fp8,
            per_token=fam["per_token"] - (fault == "kminus1"),
            use_routed=fault != "no_routed")
        x = x.reshape(b, t, -1)
    return toy.rms_norm(x)


def hidden(params, ids, *, mixers, **sizes):
    return stack(params, ids, family(sizes), mixers, F32)


def logits(params, rows):
    return jnp.matmul(rows, params["head"].astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def served(params, prompts, n_new, config, fault=None):
    """Greedy tokens of the bf16 twin: ``n_new`` tokens after each of
    the equally long prompts [n, p], every step a whole forward pass."""
    fam, mixers = family(config), tuple(config["mixers"])
    n, p = prompts.shape

    @jax.jit
    def next_token(ids, at):
        h = stack(params, ids, fam, mixers, jnp.bfloat16, fault)
        row = jax.lax.dynamic_index_in_dim(h, at, 1, keepdims=False)
        return jnp.argmax(toy.dot(row, params["head"], jnp.bfloat16), -1)
    ids = jnp.zeros((n, p + n_new), jnp.int32).at[:, :p].set(prompts)
    for s in range(n_new):
        ids = ids.at[:, p + s].set(next_token(ids, p - 1 + s))
    return np.asarray(ids[:, p:])
