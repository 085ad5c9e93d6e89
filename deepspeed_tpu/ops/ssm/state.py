"""The recurrent-state contract: what a state-space layer keeps between
serving dispatches, beside the page pool.

A recurrent layer's entry in the pools pytree is::

    {"conv": [slots, conv_kernel - 1, conv_dim]   the cache dtype (bf16)
     "ssm":  [slots, heads, head_dim, state]      float32}

one row per scheduler SLOT (a state cannot be shared by pages: it is a
function of every token before it).  The layer reads a
``kv_cache.PagedStep`` — ``mode``, ``rows``, ``lengths``, ``count`` —
and nothing else:

* prefill: batch row r is slot ``rows[r]``; a row whose first position
  ``lengths[rows[r]]`` is 0 starts from zeros whatever the slot held.
  That IS the slot reset: admission, and the re-prefill after a
  recompute-preemption, need no host-side clear.  A padding row
  (``count[r] == 0``) writes nothing.
* decode: batch row r is slot r; an inactive slot (``count[r]`` false)
  writes nothing, so its state stays bit-identical.
* verify (speculative decode) would have to roll a state back, which
  nothing here can do: it raises.

The pool is donated to every dispatch and updated in place by the
scatter / select below, never copied.  ``generate()``'s dense cache
holds the same two leaves with a batch row where the slot is.
"""

import jax
import jax.numpy as jnp

# What a model that keeps something per SLOT beside the page pool — a
# recurrent layer's state here, a sliding-window layer's ring in
# ops/attention/window.py — cannot use, and why: the serving layer's ONE
# rule (``InferenceEngine.slot_state_refusal``) ends its sentence
# "<Model> keeps <recurrent state | a window ring> per slot, which ..."
# here, and each ending reads true of either.
SLOT_STATE_REFUSALS = {
    "prefix_cache": "cannot be shared by pages: a cached prefix's pages "
                    "come without what the slot held after its last token",
    "spec_decode": "cannot be rolled back past the drafts a verify step "
                   "rejects",
    "seq_parallel_prefill": "is carried from chunk to chunk in order, not "
                            "over a sequence axis",
    "handoff": "does not travel with a page chain (no snapshot of it "
               "exists to hand over)",
}


def init_state(slots, conv_kernel, conv_dim, heads, head_dim, state_size,
               dtype):
    return {"conv": jnp.zeros((slots, conv_kernel - 1, conv_dim), dtype),
            "ssm": jnp.zeros((slots, heads, head_dim, state_size),
                             jnp.float32)}


def bytes_per_slot(conv_kernel, conv_dim, heads, head_dim, state_size,
                   dtype):
    """Exact bytes one slot's state costs in ONE recurrent layer."""
    return (conv_kernel - 1) * conv_dim * jnp.dtype(dtype).itemsize + \
        heads * head_dim * state_size * 4


def read(entry, step):
    """(conv tail, state) of the step's batch rows."""
    if step.mode == "decode":
        return entry["conv"], entry["ssm"]
    if step.mode != "prefill":
        raise NotImplementedError(
            f"a recurrent-state layer cannot run a {step.mode!r} step: "
            "rejected tokens would have to be rolled out of the state")
    with jax.named_scope("cache"):
        fresh = step.lengths[step.rows] == 0
        conv, ssm = entry["conv"][step.rows], entry["ssm"][step.rows]
        return (jnp.where(fresh[:, None, None], 0, conv),
                jnp.where(fresh[:, None, None, None], 0, ssm))


def write(entry, step, conv, ssm):
    """The entry with the rows' new tail and state; padding rows and
    inactive slots leave theirs as it was."""
    conv = conv.astype(entry["conv"].dtype)
    if step.mode == "decode":
        # a select over the whole pool that XLA fuses into the state
        # update and roots the fusion at: left under the mixer's scope,
        # or the update would read as ``cache`` (PERF.md section 6 PR 60)
        live = step.count.astype(bool)
        return {"conv": jnp.where(live[:, None, None], conv, entry["conv"]),
                "ssm": jnp.where(live[:, None, None, None], ssm,
                                 entry["ssm"])}
    with jax.named_scope("cache"):
        # an out-of-range slot id drops the row's write
        slots = jnp.where(step.count > 0, step.rows, entry["ssm"].shape[0])
        return {"conv": entry["conv"].at[slots].set(conv, mode="drop"),
                "ssm": entry["ssm"].at[slots].set(ssm, mode="drop")}
