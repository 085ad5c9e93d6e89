"""Roofline shares of the two layers a hybrid (state-space + routed
experts) model adds, from a traced run: the least time the chip could
take for the bytes and FLOPs the work NEEDS, whatever implements it,
over the time the matched device events took.

Both count what is needed from the published widths in the cell's
configuration file and from the program's own counters, never from the
shapes the program happens to compute over (it updates the state of all
its slots, occupied or not, and that is its cost, not the work's).
A reader returns None where there is nothing to read: no trace, no
matching event, or a program without the counters.
"""

import math

import readers


def matched(trace, substrs=(), heads=(), all_of=()):
    """(events, seconds) of the device operations that
    ``readers.matches``, averaged over devices."""
    ops = trace.device_ops
    if not ops:
        return 0.0, 0.0
    hits = [e - s for d in ops for n, s, e in ops[d]
            if readers.matches(n, substrs, heads, all_of)]
    return len(hits) / len(ops), sum(hits) / len(ops) / 1e9


def state_bytes_per_slot_layer(config, tail_bytes=2):
    """Bytes of one slot's recurrent state in ONE Mamba-2 layer: the
    float32 state [heads, head_dim, state] and the conv tail
    [conv_kernel - 1, heads * head_dim + 2 * groups * state] in the
    cache's bfloat16."""
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    conv_dim = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    return inner * config["ssm_state_size"] * 4 + \
        (config["conv_kernel"] - 1) * conv_dim * tail_bytes


def state_update_needed_bytes(config, slots_served):
    """One decode step of one layer reads every served slot's state
    once and writes it once."""
    return 2 * state_bytes_per_slot_layer(config) * slots_served


def state_update_roofline(ctx, substrs=(), heads=(), all_of=()):
    """Memory bound (the update is a handful of FLOPs a byte).  Every
    matched event is one layer's state update of one decode step over
    the slot batch; the slots it SERVES are the occupied ones, read off
    the window's mean occupancy (``slot_occupancy``, which counts a
    prefilling slot too: an upper bound on the served share)."""
    tr, occ = ctx["trace"], ctx["counters"].get("slot_occupancy")
    if tr is None or occ is None or "mamba_num_heads" not in ctx["config"]:
        return None
    events, secs = matched(tr, substrs, heads, all_of)
    if not events or secs <= 0:
        return None
    served = occ * ctx["traffic"]["serve"]["num_slots"]
    need = events * state_update_needed_bytes(ctx["config"], served)
    return 100.0 * need / ctx["peaks"]["bytes_per_s"] / secs


def experts_needed(config, pairs):
    """(bytes, FLOPs) one routed-layer call needs for ``pairs`` (token,
    choice) pairs on the held experts: up and down matmuls of 2 x
    hidden x inter FLOPs a pair each, and the two weight matrices of
    every held expert a pair touches (``held`` experts, ``pairs`` spread
    evenly: held x (1 - exp(-pairs / held)) of them are touched)."""
    hidden, inter = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    touched = held * (1.0 - math.exp(-pairs / held))
    return touched * 2 * hidden * inter * 2, pairs * 4 * hidden * inter


def experts_roofline(ctx, substrs=(), heads=(), all_of=(),
                     events_per_call=2):
    """A routed-layer call is ``events_per_call`` matched events (the up
    and the down grouped matmul); it computes the window's mean held
    pairs a call (``moe_held_assignments`` / ``moe_calls``).  Least
    time a call: the larger of its bytes at the memory's rate and its
    FLOPs at the peak."""
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not c.get("moe_calls") or \
            "moe_intermediate_size" not in ctx["config"]:
        return None
    events, secs = matched(tr, substrs, heads, all_of)
    if not events or secs <= 0:
        return None
    nbytes, flops = experts_needed(
        ctx["config"], c["moe_held_assignments"] / c["moe_calls"])
    least = max(nbytes / ctx["peaks"]["bytes_per_s"],
                flops / ctx["peaks"]["flops_per_s"])
    return 100.0 * (events / events_per_call) * least / secs
