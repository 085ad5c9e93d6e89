"""Roofline shares of a multi-head-latent-attention (``deepseek_v3``)
model's layers — both paged kernels over a LATENT page pool, SwiGLU
experts over a held share — from a traced run: the least time the chip
could take for the bytes and FLOPs the work NEEDS, over the time the
matched device events took.

What attention needs is counted in the ABSORBED form's terms, whatever
implements it: a cached token of one layer is ONE vector of
``kv_lora_rank + qk_rope_head_dim`` values (the published 1,152 B in
bfloat16, never the width the pool pads it to), read once; a (query,
cached token) pair of one head is one multiply-add over that vector
(the score) and one over its leading ``kv_lora_rank`` features (the
weighted sum).  The counts come from the published widths in the cell's
configuration file (``ctx["config"]``) and from the program's own
counters (``ServingMetrics``' host-side sums: ``decode_steps``,
``decode_kv_tokens``, ``prefill_dispatches``, ``prefill_kv_tokens``,
``prefill_kv_pairs``, ``moe_calls``, ``moe_held_assignments``), never
from the kernels' tiling.  A reader returns None where there is nothing
to read: no trace, no matching event, a configuration of another
family, or a program without the counters.
"""

from readers_mimo_v2 import _share, experts_needed, mean_over

KV_BYTES = 2          # the cell's bfloat16 cache and weights


def is_family(config):
    return "kv_lora_rank" in config


def latent_bytes_per_token_layer(config, kv_bytes=KV_BYTES):
    """Bytes of one token's cached vector in ONE layer, as published."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * kv_bytes


def pair_flops(config):
    """FLOPs one (query, cached token) pair costs over all heads: a
    multiply-add a feature of the cached vector (the score) and one a
    feature of the latent (the weighted sum)."""
    return config["num_attention_heads"] * 2 * (
        2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])


def least_seconds(peaks, nbytes, flops):
    return max(nbytes / peaks["bytes_per_s"], flops / peaks["flops_per_s"])


def decode_needed(config, tokens):
    """(bytes, FLOPs) one layer's decode attention of one step needs
    over ``tokens`` cached tokens (every live slot's length, summed):
    each cached vector read once, each a pair with its slot's one query
    token.  At 32 heads that is 60 FLOPs a byte: memory bound on a chip
    of 240 FLOPs a byte."""
    return latent_bytes_per_token_layer(config) * tokens, \
        pair_flops(config) * tokens


def paged_decode_roofline(ctx, substrs=(), heads=(), all_of=()):
    """Every matched event is one layer's paged decode attention of one
    decode step over the window's mean ``decode_kv_tokens`` /
    ``decode_steps`` cached tokens."""
    tokens = mean_over(ctx["counters"], "decode_kv_tokens", "decode_steps")
    if tokens is None or not is_family(ctx["config"]):
        return None
    least = least_seconds(ctx["peaks"], *decode_needed(ctx["config"],
                                                       tokens))
    return _share(ctx, least, substrs, heads, all_of)


def prefill_needed(config, kv_tokens, kv_pairs):
    """(bytes, FLOPs) one layer's attention of one prefill dispatch
    needs: the cached vectors of the ``kv_tokens`` keys its chunks read,
    once; ``kv_pairs`` (query, key) pairs."""
    return latent_bytes_per_token_layer(config) * kv_tokens, \
        pair_flops(config) * kv_pairs


def paged_prefill_roofline(ctx, substrs=(), heads=(), all_of=()):
    """Every matched event is one layer's ``paged_prefill`` call of one
    prefill dispatch (the window's mean dispatch: ``prefill_kv_tokens``
    and ``prefill_kv_pairs`` over ``prefill_dispatches``).  Compute
    bound at a 32-token chunk: 32 x 60 FLOPs a byte."""
    c = ctx["counters"]
    tokens = mean_over(c, "prefill_kv_tokens", "prefill_dispatches")
    pairs = mean_over(c, "prefill_kv_pairs", "prefill_dispatches")
    if tokens is None or pairs is None or not is_family(ctx["config"]):
        return None
    least = least_seconds(ctx["peaks"], *prefill_needed(ctx["config"],
                                                        tokens, pairs))
    return _share(ctx, least, substrs, heads, all_of)


def experts_roofline(ctx, substrs=(), heads=(), all_of=(),
                     events_per_call=2):
    """A routed-layer call is ``events_per_call`` matched events; it
    computes the window's mean held pairs a call (``moe_held_
    assignments`` / ``moe_calls``) on SwiGLU experts of three matrices
    each (``readers_mimo_v2.experts_needed``, which reads this family's
    widths under the same keys)."""
    pairs = mean_over(ctx["counters"], "moe_held_assignments", "moe_calls")
    if pairs is None or not is_family(ctx["config"]):
        return None
    least = least_seconds(ctx["peaks"], *experts_needed(ctx["config"],
                                                        pairs))
    return _share(ctx, least / events_per_call, substrs, heads, all_of)


def kv_bytes_per_live_token(ctx):
    """Pool bytes a live token costs AS STORED over all layers (the
    program's ``kv_stored_bytes_per_token``: padding included).  The
    number a latent cache exists to lower: ``published_bytes_per_token``
    is what the model's card promises and ``per_head_bytes_per_token``
    what per-head keys and values would cost."""
    stored = ctx["counters"].get("kv_stored_bytes_per_token")
    return stored if stored and is_family(ctx["config"]) else None


def published_bytes_per_token(config):
    return config["num_hidden_layers"] * latent_bytes_per_token_layer(config)


def per_head_bytes_per_token(config, kv_bytes=KV_BYTES):
    return config["num_hidden_layers"] * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"]) * kv_bytes
