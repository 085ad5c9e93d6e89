"""Roofline shares and cache bytes of a ``mimo_v2_flash`` model — full
attention layers over PAGES, sliding-window layers over a RING a slot,
SwiGLU experts over a held share — from a traced run: the least time
the chip could take for the bytes and FLOPs the work NEEDS, over the
time the matched device events took.

Every count of what is needed comes from the published widths in the
cell's configuration file (``ctx["config"]``, MiMo-V2's key names) and
from the program's own counters (``ServingMetrics``' host-side sums:
``decode_steps``, ``decode_kv_tokens``, ``decode_window_tokens``,
``decode_live_rows``, ``prefill_dispatches``, ``prefill_kv_tokens``,
``prefill_kv_pairs``, ``moe_calls``, ``moe_held_assignments``), never
from the shapes the program happens to compute over.  A reader returns
None where there is nothing to read: no trace, no matching event, a
configuration of another family, or a program without the counters.
"""

import math

from readers_hybrid import matched

KV_BYTES = 2          # the cell's bfloat16 cache and weights


def is_family(config):
    return "swa_num_key_value_heads" in config


def paged_bytes_per_token_layer(config, kv_bytes=KV_BYTES):
    """Bytes of one token's K (``head_dim`` wide) and V (``v_head_dim``
    wide) in ONE full-attention layer."""
    return config["num_key_value_heads"] * \
        (config["head_dim"] + config["v_head_dim"]) * kv_bytes


def ring_bytes_per_token_layer(config, kv_bytes=KV_BYTES):
    """The same in ONE sliding-window layer (its own KV-head count)."""
    return config["swa_num_key_value_heads"] * \
        (config["swa_head_dim"] + config["swa_v_head_dim"]) * kv_bytes


def window_layers(config):
    return sum(1 for kind in config["hybrid_layer_pattern"] if kind)


def mean_over(counters, key, per):
    """``counters[key] / counters[per]``, or None."""
    if not counters.get(per) or counters.get(key) is None:
        return None
    return counters[key] / counters[per]


def _share(ctx, per_event_seconds, substrs, heads, all_of):
    """100 x events x (least seconds an event) / matched seconds."""
    tr = ctx["trace"]
    if tr is None or per_event_seconds is None:
        return None
    events, secs = matched(tr, substrs, heads, all_of)
    if not events or secs <= 0:
        return None
    return 100.0 * events * per_event_seconds / secs


def paged_decode_roofline(ctx, substrs=(), heads=(), all_of=()):
    """Every matched event is one full layer's paged decode attention of
    one decode step; it NEEDS the K and V of every token its live slots
    hold read once (``decode_kv_tokens`` / ``decode_steps`` tokens, the
    window's mean; q and the output are a thousandth of that).  Memory
    bound: a group of 16 query heads is 16 FLOPs a byte."""
    tokens = mean_over(ctx["counters"], "decode_kv_tokens", "decode_steps")
    if tokens is None or not is_family(ctx["config"]):
        return None
    need = paged_bytes_per_token_layer(ctx["config"]) * tokens
    return _share(ctx, need / ctx["peaks"]["bytes_per_s"], substrs, heads,
                  all_of)


def prefill_needed(config, kv_tokens, kv_pairs):
    """(bytes, FLOPs) one full layer's attention of one prefill dispatch
    needs: the K and V of the ``kv_tokens`` keys its chunks read, once;
    for each of ``kv_pairs`` (query, key) pairs and each query head one
    multiply-add over ``head_dim`` (the score) and one over
    ``v_head_dim`` (the weighted sum)."""
    nbytes = paged_bytes_per_token_layer(config) * kv_tokens
    flops = kv_pairs * config["num_attention_heads"] * 2 * \
        (config["head_dim"] + config["v_head_dim"])
    return nbytes, flops


def paged_prefill_roofline(ctx, substrs=(), heads=(), all_of=()):
    """Every matched event is one full layer's ``paged_prefill`` call of
    one prefill dispatch (the window's mean dispatch: ``prefill_kv_
    tokens`` and ``prefill_kv_pairs`` over ``prefill_dispatches``).
    Least time: the larger of its bytes at the memory's rate and its
    FLOPs at the peak."""
    c = ctx["counters"]
    tokens = mean_over(c, "prefill_kv_tokens", "prefill_dispatches")
    pairs = mean_over(c, "prefill_kv_pairs", "prefill_dispatches")
    if tokens is None or pairs is None or not is_family(ctx["config"]):
        return None
    nbytes, flops = prefill_needed(ctx["config"], tokens, pairs)
    least = max(nbytes / ctx["peaks"]["bytes_per_s"],
                flops / ctx["peaks"]["flops_per_s"])
    return _share(ctx, least, substrs, heads, all_of)


def window_decode_roofline(ctx, substrs=(), heads=(), all_of=(),
                           events_per_layer_step=1):
    """``events_per_layer_step`` matched events are one window layer's
    decode attention of one decode step; it NEEDS the ring rows its
    live slots hold read once: ``decode_window_tokens`` /
    ``decode_steps`` tokens (each slot's length cut to the window).
    Memory bound, as the paged decode."""
    tokens = mean_over(ctx["counters"], "decode_window_tokens",
                       "decode_steps")
    if tokens is None or not is_family(ctx["config"]):
        return None
    need = ring_bytes_per_token_layer(ctx["config"]) * tokens
    return _share(ctx, need / ctx["peaks"]["bytes_per_s"]
                  / events_per_layer_step, substrs, heads, all_of)


def experts_needed(config, pairs):
    """(bytes, FLOPs) one routed-layer call needs for ``pairs`` (token,
    choice) pairs on the held SwiGLU experts: gate, up and down matmuls
    of 2 x hidden x inter FLOPs a pair each, and the THREE weight
    matrices of every held expert a pair touches (``held`` experts,
    ``pairs`` spread evenly: held x (1 - exp(-pairs / held)) of them)."""
    hidden, inter = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    touched = held * (1.0 - math.exp(-pairs / held))
    return touched * 3 * hidden * inter * KV_BYTES, \
        pairs * 6 * hidden * inter


def experts_roofline(ctx, substrs=(), heads=(), all_of=(),
                     events_per_call=2):
    """A routed-layer call is ``events_per_call`` matched events (the
    grouped matmul into the packed gate-and-up, the one down); it
    computes the window's mean held pairs a call (``moe_held_
    assignments`` / ``moe_calls``).  Least time a call: the larger of
    its bytes at the memory's rate and its FLOPs at the peak."""
    pairs = mean_over(ctx["counters"], "moe_held_assignments", "moe_calls")
    if pairs is None or not is_family(ctx["config"]):
        return None
    nbytes, flops = experts_needed(ctx["config"], pairs)
    least = max(nbytes / ctx["peaks"]["bytes_per_s"],
                flops / ctx["peaks"]["flops_per_s"])
    return _share(ctx, least / events_per_call, substrs, heads, all_of)


def kv_bytes_per_live_token(ctx):
    """Cache bytes a live token costs: the paged layers' bytes a token
    plus a slot's rings over the tokens the slot holds (the mean context
    of a decoding slot, ``decode_kv_tokens`` / ``decode_live_rows``).
    The number the window : full pattern exists to lower; with the
    window layers paged like the full ones it would read
    ``all_paged_bytes_per_token``."""
    c = ctx["counters"]
    context = mean_over(c, "decode_kv_tokens", "decode_live_rows")
    if context is None or not c.get("kv_paged_bytes_per_token") \
            or c.get("kv_window_bytes_per_slot") is None:
        return None
    return c["kv_paged_bytes_per_token"] + \
        c["kv_window_bytes_per_slot"] / context


def decode_live_rows_per_step(ctx):
    """Slots that emit a token in a decode step, on average (the
    program's ``decode_live_rows`` / ``decode_steps``): the decode
    program computes every slot, so this is what a step is worth."""
    return mean_over(ctx["counters"], "decode_live_rows", "decode_steps")


def all_paged_bytes_per_token(config):
    """What a token would cost with every layer paged (the comparison
    ``kv_bytes_per_live_token`` is read against)."""
    full = len(config["hybrid_layer_pattern"]) - window_layers(config)
    return full * paged_bytes_per_token_layer(config) + \
        window_layers(config) * ring_bytes_per_token_layer(config)
