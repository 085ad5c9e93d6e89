"""Roofline shares of the two caches a parallel hybrid (``falcon_h1``:
attention AND a Mamba-2 mixer in every block) reads at decode, from a
traced run: the least time the chip could take for the bytes the work
NEEDS, over the time the matched device events took.

Both are memory bound (a handful of FLOPs a byte) and both count what is
needed from the published widths in the cell's configuration file
(Falcon-H1's key names) and from the program's own counters
(``decode_steps``, ``decode_live_rows``, ``decode_kv_tokens``: host-side
sums of ``ServingMetrics``), never from the shapes the program happens
to compute over: it updates the state of all its slots and walks every
page of a slot's capacity, and that is its cost, not the work's.  A
reader returns None where there is nothing to read: no trace, no
matching event, a configuration of another family, or a program without
the counters (the parent of the PR that added them).
"""

from readers_hybrid import matched

KV_BYTES = 2          # the cell's bfloat16 cache


def state_bytes_per_slot_layer(config, tail_bytes=KV_BYTES):
    """Bytes of one slot's recurrent state in ONE layer: the float32
    state [heads, head size, state size] and the conv tail [conv kernel
    - 1, heads x head size + 2 x groups x state size] in the cache's
    bfloat16."""
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv_dim = inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return inner * config["mamba_d_state"] * 4 + \
        (config["mamba_d_conv"] - 1) * conv_dim * tail_bytes


def kv_bytes_per_token_layer(config, kv_bytes=KV_BYTES):
    """Bytes of one token's K and V in ONE layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * kv_bytes


def per_step(counters, key):
    """A counter's mean over the window's decode steps, or None."""
    steps = counters.get("decode_steps")
    if not steps or counters.get(key) is None:
        return None
    return counters[key] / steps


def state_update_roofline(ctx, substrs=(), heads=(), all_of=()):
    """Every matched event is one layer's state update of one decode
    step over the slot batch; it NEEDS the state of the slots that emit
    a token at that step read once and written once
    (``decode_live_rows`` / ``decode_steps`` of them, the window's
    mean)."""
    tr = ctx["trace"]
    live = per_step(ctx["counters"], "decode_live_rows")
    if tr is None or live is None or "mamba_d_state" not in ctx["config"]:
        return None
    events, secs = matched(tr, substrs, heads, all_of)
    if not events or secs <= 0:
        return None
    need = events * 2 * state_bytes_per_slot_layer(ctx["config"]) * live
    return 100.0 * need / ctx["peaks"]["bytes_per_s"] / secs


def paged_decode_roofline(ctx, substrs=(), heads=(), all_of=()):
    """Every matched event is one layer's paged decode attention of one
    decode step; it NEEDS the K and V of every token its live slots
    hold read once (``decode_kv_tokens`` / ``decode_steps`` tokens, the
    window's mean; q and the output are a thousandth of that)."""
    tr = ctx["trace"]
    tokens = per_step(ctx["counters"], "decode_kv_tokens")
    if tr is None or tokens is None or "mamba_d_state" not in ctx["config"]:
        return None
    events, secs = matched(tr, substrs, heads, all_of)
    if not events or secs <= 0:
        return None
    need = events * kv_bytes_per_token_layer(ctx["config"]) * tokens
    return 100.0 * need / ctx["peaks"]["bytes_per_s"] / secs
