"""What an ``afmoe`` model (arcee-ai Trinity) adds to the readers: the
roofline share of its sliding-window layers' attention, which the two
paged kernels compute over a ring a slot read as pages.

The kernel calls of the window layers are told from the full layer's by
the scope their operations carry (``.../layers_3/swa/...`` on the
event's ``tf_op`` stat), never by a shape.  What they NEED comes from
the published widths in the cell's configuration file (``ctx["config"]``,
the family's key names) and from the program's own counters
(``ServingMetrics``' host-side sums: ``prefill_dispatches``,
``prefill_window_tokens``, ``prefill_window_pairs``, ``decode_steps``,
``decode_window_tokens``).  A reader returns None where there is
nothing to read: no trace, no such event, a configuration of another
family, or a program without the counters.
"""

import readers
import readers_scopes

KV_BYTES = 2          # the cell's bfloat16 cache


def is_family(config):
    return config.get("model_type") == "afmoe"


def kv_bytes_per_token_layer(config, kv_bytes=KV_BYTES):
    """Bytes of one token's K and V in ONE layer of either kind."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * kv_bytes


def window_needed(config, tokens, pairs):
    """(bytes, FLOPs) one window layer's attention needs for a call
    that reads ``tokens`` key positions and scores ``pairs`` (query,
    key) pairs: each position's K and V once; for each pair and each
    query head one multiply-add over ``head_dim`` for the score and one
    for the weighted sum.  Whole pages, q, the output and the softmax
    are not counted: a lower bound."""
    return kv_bytes_per_token_layer(config) * tokens, \
        pairs * config["num_attention_heads"] * 4 * config["head_dim"]


def least_seconds(ctx, tokens, pairs):
    nbytes, flops = window_needed(ctx["config"], tokens, pairs)
    return max(nbytes / ctx["peaks"]["bytes_per_s"],
               flops / ctx["peaks"]["flops_per_s"])


def scoped_kernel_calls(path, scope):
    """{instruction's own name: (events, seconds)} of the Mosaic kernel
    calls on the "XLA Ops" lines of one ``.xplane.pb`` whose ``tf_op``
    path holds ``scope`` as one of its parts, mean over the file's
    device planes."""
    from deepspeed_tpu.profiling.xplane import read_xspace
    acc, planes = {}, 0
    for plane in read_xspace(path):
        if not plane.name.startswith("/device:TPU:"):
            continue
        planes += 1
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = plane.event_names.get(ev.metadata_id, "")
                if "tpu_custom_call" not in name:
                    continue
                tf_op = plane.event_stats.get(ev.metadata_id, {}) \
                    .get("tf_op") or ""
                if scope not in tf_op.split(";")[0].rstrip(":").split("/"):
                    continue
                n, ps = acc.get(readers.own_name(name), (0, 0))
                acc[readers.own_name(name)] = (n + 1, ps + ev.duration_ps)
    return {k: (n / planes, ps / planes / 1e12)
            for k, (n, ps) in acc.items()}


def window_roofline(ctx, scope="swa", prefill="paged_prefill"):
    """100 x the least time the chip could take for what the window
    layers' kernel calls of the traced window needed, over the time
    they took.  A call whose instruction is named ``prefill`` is one
    window layer's attention of one prefill dispatch, and needs the
    window's mean dispatch (``prefill_window_tokens`` and ``prefill_
    window_pairs`` over ``prefill_dispatches``); every other call under
    ``scope`` is one window layer's attention of one decode step, and
    needs the mean step's (``decode_window_tokens`` / ``decode_steps``
    positions, one query each).  Least time a call: the larger of its
    bytes at the memory's rate and its FLOPs at the peak."""
    c = ctx["counters"]
    if not is_family(ctx["config"]) or ctx["trace"] is None:
        return None
    path = readers_scopes.trace_file(ctx)
    if path is None:
        return None
    least = secs = 0.0
    for name, (events, took) in scoped_kernel_calls(path, scope).items():
        if name == prefill:
            n, keys = c.get("prefill_dispatches"), \
                ("prefill_window_tokens", "prefill_window_pairs")
        else:
            n, keys = c.get("decode_steps"), \
                ("decode_window_tokens", "decode_window_tokens")
        if not n or any(c.get(k) is None for k in keys):
            return None
        least += events * least_seconds(ctx, c[keys[0]] / n, c[keys[1]] / n)
        secs += took
    return 100.0 * least / secs if secs > 0 else None
