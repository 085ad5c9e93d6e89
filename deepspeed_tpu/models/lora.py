"""Per-slot batched LoRA deltas for the paged serving path (S-LoRA /
Punica shape).

The serving tier holds N adapters' low-rank factors as STACKED device
arrays — ``a [n, in, rank]`` / ``b [n, rank, out]`` per injected
projection, rank zero-padded to a power-of-two bucket — and threads a
per-slot ``adapter_ids`` array through the fused decode scan.  Each
batch row gathers ITS adapter's factors and adds
``scale[id] * (x @ A[id] @ B[id])`` to the base projection:

* the gather + two batched einsums are shape-fixed by (slots, rank
  bucket), so adapter churn never changes the jit signature — one
  compiled signature per (horizon, rank-bucket), exactly like the page
  pool fixes the KV signature;
* ``adapter_id == -1`` multiplies the delta by 0.0 — base-only rows in
  a mixed batch stay token-exact vs base-only serving (the whole
  ``adapters`` side input is absent for tenancy-off traffic, which
  keeps that path byte-identical);
* zero-padding the rank adds exact zero columns/rows to A/B, so a
  rank-5 adapter served in an 8-bucket produces bit-identical deltas
  to its unpadded math.

The weight dict a layer sees (the ``adapters`` of its
``kv_cache.layer_view``, which slices the per-layer factor stacks out
of the model-level pack and shares ids/scale) is::

    {"ids":   int32 [num_slots]          (-1 = no adapter),
     "scale": float32 [n],
     <target>: {"a": [n, in, r], "b": [n, r, out]}, ...}

Target names follow the model's projection module names (gpt2:
``qkv``/``proj``/``fc_in``/``fc_out``; llama: ``wq``/``wk``/``wv``/
``wo``/``w_gate``/``w_up``/``w_down``).  A missing target is simply
not injected — adapters may cover any subset.
"""

import jax.numpy as jnp

from deepspeed_tpu.ops.attention import kv_cache


def layer_adapters(cache):
    """(this layer's weight dict, the adapter id of each batch row) out
    of a layer's cache view; (None, None) off the serving path and for
    base-only traffic.  Chunked prefill runs one row per prefilling
    slot, so row r's id is ``ids[slot[r]]``; decode and teacher-forced
    verify run one row per slot, so the ids array maps through
    unchanged."""
    ad, rows = kv_cache.adapters_of(cache)
    if ad is None:
        return None, None
    return ad, ad["ids"] if rows is None else ad["ids"][rows]


def lora_delta(x, pack, rows, scale):
    """Batched per-row LoRA delta: ``scale[rows] * (x @ A[rows] @
    B[rows])``, 0.0 where ``rows < 0``.

    ``x`` is [b, ..., in]; ``pack`` holds the stacked factors
    ``{"a": [n, in, r], "b": [n, r, out]}``; ``rows`` is int32 [b].
    Each batch row's matmul chain is independent of the other rows, so
    a slot's delta is bit-identical whether it shares the batch with 0
    or 7 other adapters — the mixed-batch token-exactness oracle rests
    on this."""
    safe = jnp.maximum(rows, 0)
    a = jnp.take(pack["a"], safe, axis=0)                # [b, in, r]
    bm = jnp.take(pack["b"], safe, axis=0)               # [b, r, out]
    coef = jnp.where(rows >= 0, jnp.take(scale, safe), 0.0)
    h = jnp.einsum("b...i,bir->b...r", x, a.astype(x.dtype))
    d = jnp.einsum("b...r,bro->b...o", h, bm.astype(x.dtype))
    shape = (x.shape[0],) + (1,) * (x.ndim - 1)
    return d * coef.reshape(shape).astype(x.dtype)


def lora_targets(cfg):
    """(in_dim, out_dim, sharded_dim) per injectable projection for a
    model config — the AdapterStore validates adapter checkpoints and
    lays out the stacked device arrays against this table.
    ``sharded_dim`` names which factor dimension sits on the ``model``
    mesh axis in the base kernel ("out" for column-parallel, "in" for
    row-parallel) — the store mirrors that placement when it divides."""
    kind = type(cfg).__name__
    if kind == "GPTConfig":
        hs = cfg.hidden_size
        return {
            "qkv": (hs, 3 * hs, "out"),
            "proj": (hs, hs, "in"),
            "fc_in": (hs, cfg.mlp_ratio * hs, "out"),
            "fc_out": (cfg.mlp_ratio * hs, hs, "in"),
        }
    if kind == "LlamaConfig":
        hs, d = cfg.hidden_size, cfg.head_dim
        return {
            "wq": (hs, cfg.num_heads * d, "out"),
            "wk": (hs, cfg.num_kv_heads * d, "out"),
            "wv": (hs, cfg.num_kv_heads * d, "out"),
            "wo": (cfg.num_heads * d, hs, "in"),
            "w_gate": (hs, cfg.intermediate_size, "out"),
            "w_up": (hs, cfg.intermediate_size, "out"),
            "w_down": (cfg.intermediate_size, hs, "in"),
        }
    raise ValueError(f"no LoRA target table for config type {kind}")
