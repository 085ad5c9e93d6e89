"""Llama-family causal transformer (RMSNorm, RoPE, SwiGLU, GQA), TPU-first.

This is the flagship for the north-star ZeRO-3 target (BASELINE.json:
"Llama-2-70B on v5p-256") and the inference stack. Same logical-axis
partitioning scheme as models/gpt2.py; reference parity targets
deepspeed's Llama policy/containers (module_inject/containers/llama.py
in later snapshots) re-designed as a native flax model.

KV-cache decode is built in: ``__call__(ids, positions=..., cache=...)``
returns ``(logits, new_cache)`` — the cache is a plain pytree updated with
``lax.dynamic_update_slice`` so single-token decode jits to the
``softmax_context`` equivalent (reference csrc/transformer/inference).
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.ops.attention.reference import (apply_rotary_emb,
                                                   decode_attention_reference,
                                                   mha_reference)
from deepspeed_tpu.runtime.zero import gather as zero_gather


@dataclasses.dataclass(unsafe_hash=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32            # < num_heads => GQA
    intermediate_size: int = 11008
    max_seq_len: int = 4096
    rope_base: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "auto"
    tie_embeddings: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu.ops.quant.qdense import shaped_param
        scale = shaped_param(self, "scale", nn.with_partitioning(
            nn.initializers.ones_init(), ("embed",)), (x.shape[-1],),
            jnp.float32)
        scale = scale.value if hasattr(scale, "value") else scale
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        y = x.astype(jnp.float32) * lax.rsqrt(var + self.eps)
        return (y * scale).astype(self.dtype)


def _proj(cfg, features, axes, name):
    from deepspeed_tpu.ops.quant.qdense import QDense
    return QDense(features, use_bias=False, dtype=cfg.dtype,
                  param_dtype=cfg.param_dtype,
                  kernel_init=nn.with_partitioning(
                      nn.initializers.normal(0.02), axes),
                  name=name)


from deepspeed_tpu.ops.attention.decode import _repeat_kv  # GQA expansion


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        b, l, _ = x.shape
        h, kv_h, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        # multi-tenant serving: per-slot LoRA deltas ride the paged
        # cache as a stacked side input (models/lora.py); absent for
        # base-only traffic, so that path's trace is unchanged
        ad = cache.get("adapters") if cache is not None else None
        if ad is not None:
            from deepspeed_tpu.models.lora import adapter_rows, lora_delta
            ad_rows = adapter_rows(ad, cache)
        q = _proj(cfg, h * d, ("embed", "heads"), "wq")(x)
        k = _proj(cfg, kv_h * d, ("embed", "kv"), "wk")(x)
        v = _proj(cfg, kv_h * d, ("embed", "kv"), "wv")(x)
        if ad is not None:
            if "wq" in ad:
                q = q + lora_delta(x, ad["wq"], ad_rows, ad["scale"])
            if "wk" in ad:
                k = k + lora_delta(x, ad["wk"], ad_rows, ad["scale"])
            if "wv" in ad:
                v = v + lora_delta(x, ad["wv"], ad_rows, ad["scale"])
        q = q.reshape(b, l, h, d)
        k = k.reshape(b, l, kv_h, d)
        v = v.reshape(b, l, kv_h, d)
        q = apply_rotary_emb(q, positions, base=cfg.rope_base)
        k = apply_rotary_emb(k, positions, base=cfg.rope_base)

        new_cache = None
        if cache is not None and "k_pages" in cache:
            # paged serving path — same contract as models/gpt2.py:
            # pools [num_pages, page_size, kv_h, d] shared via a per-slot
            # page table; GQA pools stay grouped end to end
            from deepspeed_tpu.ops.attention import (decode_attention,
                                                     paged_decode_attention)
            from deepspeed_tpu.ops.quant.kv import (paged_gather,
                                                    paged_write)
            k_pages, v_pages = cache["k_pages"], cache["v_pages"]
            num_pages, ps = k_pages.shape[0], k_pages.shape[1]
            pt = cache["page_table"]
            max_len = pt.shape[1] * ps
            if "slot" in cache:
                # chunked prefill, one row per prefilling slot: row r
                # carries the next chunk of slot[r] (b == rows, l ==
                # chunk).  Columns past n_valid[r] are padding (a
                # padding ROW has n_valid == 0): their K/V writes drop
                # (out-of-bounds page id) and their outputs are unused.
                # Row r starts at lengths[slot[r]] — a prefix-cache hit
                # seeds it to the cached (possibly mid-page) boundary:
                # rotary offsets follow the positions array, writes
                # never touch shared read-only pages below the
                # boundary, and the copy-on-write tail page's stale
                # region is overwritten-before-gather or masked.
                # paged_write quantizes to int8/fp8 pools (with parallel
                # per-row scale pools) when the cache carries them;
                # float pools take the byte-identical legacy path
                slot = cache["slot"]                     # [rows]
                pos = positions                          # [rows, l]
                valid = jnp.arange(l)[None, :] < cache["n_valid"][:, None]
                page_ids = jnp.where(valid, pt[slot[:, None], pos // ps],
                                     num_pages)
                pools_out = paged_write(cache, page_ids, pos % ps, k, v)
                k_slot, v_slot = paged_gather(pools_out, pt[slot], q.dtype)
                seq_ax = cache.get("seq_axis")
                if seq_ax is not None:
                    # sequence-parallel prefill (static trace-time
                    # marker, same contract as models/gpt2.py; one row):
                    # the write above already landed the chunk's KV in
                    # the standard pool; attention runs distributed over
                    # the sequence axis against the pool gather.  The
                    # distributed transports take full-head k/v, so GQA
                    # pools expand to h heads HERE only — the pool
                    # itself stays grouped
                    assert b == 1, "sequence-parallel prefill is one row"
                    from deepspeed_tpu import comm as dist
                    from deepspeed_tpu.sequence.prefill import (
                        paged_prefill_attention)
                    rep = h // kv_h
                    out = paged_prefill_attention(
                        q, _repeat_kv(k, rep), _repeat_kv(v, rep),
                        _repeat_kv(k_slot, rep), _repeat_kv(v_slot, rep),
                        positions[0, 0], dist.get_mesh(), axis=seq_ax,
                        impl=cache["seq_impl"])
                else:
                    k_pos = jnp.arange(max_len)
                    mask = k_pos[None, None, :] <= pos[:, :, None]
                    bias = jnp.where(mask, 0.0,
                                     jnp.finfo(jnp.float32).min)[:, None]
                    out = decode_attention(q, k_slot, v_slot, bias=bias)
            elif "widths" in cache:
                # teacher-forced multi-token verify (speculative decode):
                # b == slots, l == K+1 candidate tokens; column j of
                # slot s writes position lengths[s] + j when
                # j < widths[s] (0 for inactive slots) and attends
                # causally through the page table in ONE batched
                # forward — same contract as models/gpt2.py. Rotary
                # offsets ride the positions array; GQA pools stay
                # grouped through the gather + decode_attention path.
                widths = cache["widths"]
                pos = positions                          # [slots, l]
                write = jnp.arange(l)[None, :] < widths[:, None]
                page_ids = jnp.where(
                    write, pt[jnp.arange(b)[:, None], pos // ps], num_pages)
                pools_out = paged_write(cache, page_ids, pos % ps, k, v)
                k_slot, v_slot = paged_gather(pools_out, pt, q.dtype)
                k_pos = jnp.arange(max_len)
                mask = k_pos[None, None, :] <= pos[:, :, None]
                bias = jnp.where(mask, 0.0,
                                 jnp.finfo(jnp.float32).min)[:, None]
                out = decode_attention(q, k_slot, v_slot, bias=bias)
            else:                        # continuous-batch decode (l == 1)
                # paged_decode_attention owns the kernel dispatch: GQA
                # pools run the per-kv-head BlockSpec kernel grouped
                # (never expanded), and a multi-device mesh runs it
                # per-shard under shard_map — each device gets its kv
                # shard's q-head group; this call site is topology-blind
                active = cache["active"]
                pos = positions[:, 0]
                page_ids = jnp.where(active,
                                     pt[jnp.arange(b), pos // ps], num_pages)
                pools_out = paged_write(cache, page_ids, pos % ps,
                                        k[:, 0], v[:, 0])
                out = paged_decode_attention(
                    q, pools_out["k_pages"], pools_out["v_pages"], pt,
                    pos, k_scale=pools_out.get("k_scale"),
                    v_scale=pools_out.get("v_scale"))
            # multi-chip serving: pin the pools' kv-head sharding on the
            # updated arrays so GSPMD keeps the scatter/gather split
            # over the `model` axis — GQA pools shard num_kv_heads, so
            # the `model` size must divide it (engine-validated); the
            # quantized scale pools share the payload's axis family
            from deepspeed_tpu.serving.sharding import constrain_kv_pages
            new_cache = {name: constrain_kv_pages(arr)
                         for name, arr in pools_out.items()}
        elif cache is not None:
            # decode: append k/v at cache["index"], attend over valid prefix
            k_cache = lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache["index"], 0, 0))
            v_cache = lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache["index"], 0, 0))
            new_cache = {"k": k_cache, "v": v_cache,
                         "index": cache["index"] + l}
            # attend over the whole cache buffer with a positional mask:
            # slot j is visible to query at absolute position p iff j <= p
            # (cache["index"] is traced, so no dynamic slicing). Single-token
            # steps hit the Pallas softmax_context kernel; GQA caches are
            # consumed grouped, never expanded.
            max_len = k_cache.shape[1]
            k_pos = jnp.arange(max_len)
            mask = k_pos[None, None, :] <= positions[:, :, None]  # [b,l,max]
            bias = jnp.where(mask, 0.0, jnp.finfo(jnp.float32).min)
            from deepspeed_tpu.ops.attention import decode_attention
            out = decode_attention(q, k_cache, v_cache, bias=bias[:, None])

        else:
            k_full = _repeat_kv(k, h // kv_h)
            v_full = _repeat_kv(v, h // kv_h)
            impl = cfg.attn_impl
            if impl == "auto":
                impl = "flash" if (jax.default_backend() == "tpu" and
                                   l % 128 == 0) else "reference"
            if impl == "flash":
                from deepspeed_tpu.ops.attention import flash_attention
                out = flash_attention(q, k_full, v_full, causal=True)
            elif impl in ("ring", "ulysses"):
                from deepspeed_tpu import comm as dist
                from deepspeed_tpu.sequence import DistributedAttention
                mesh = dist.get_mesh()
                assert mesh is not None and \
                    mesh.shape.get("sequence", 1) > 1, \
                    f"attn_impl={impl} needs a sequence mesh axis > 1"
                out = DistributedAttention(mesh, impl=impl)(q, k_full, v_full)
            else:
                out = mha_reference(q, k_full, v_full, causal=True)

        out = out.reshape(b, l, h * d)
        wo_in = out
        out = _proj(cfg, cfg.hidden_size, ("heads", "embed"), "wo")(wo_in)
        if ad is not None and "wo" in ad:
            out = out + lora_delta(wo_in, ad["wo"], ad_rows, ad["scale"])
        return out, new_cache


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, adapters=None, ad_rows=None):
        cfg = self.cfg
        gate = _proj(cfg, cfg.intermediate_size, ("embed", "mlp"), "w_gate")(x)
        up = _proj(cfg, cfg.intermediate_size, ("embed", "mlp"), "w_up")(x)
        if adapters is not None:
            from deepspeed_tpu.models.lora import lora_delta
            if "w_gate" in adapters:
                gate = gate + lora_delta(x, adapters["w_gate"], ad_rows,
                                         adapters["scale"])
            if "w_up" in adapters:
                up = up + lora_delta(x, adapters["w_up"], ad_rows,
                                     adapters["scale"])
        h = nn.silu(gate) * up
        down = _proj(cfg, cfg.hidden_size, ("mlp", "embed"), "w_down")(h)
        if adapters is not None and "w_down" in adapters:
            from deepspeed_tpu.models.lora import lora_delta
            down = down + lora_delta(h, adapters["w_down"], ad_rows,
                                     adapters["scale"])
        return down


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        # ZeRO-3 gather-at-use (runtime/zero/gather.py): the residual
        # stream enters and leaves a block with its batch on `data`
        plan = zero_gather.active() if cache is None else None
        if plan is not None:
            x = plan.pin_batch(x)
        ad = cache.get("adapters") if cache is not None else None
        ad_rows = None
        if ad is not None:
            from deepspeed_tpu.models.lora import adapter_rows
            ad_rows = adapter_rows(ad, cache)
        attn_out, new_cache = LlamaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x),
            positions, cache)
        x = x + attn_out
        x = x + LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x),
            ad, ad_rows)
        if plan is not None:
            x = plan.pin_batch(x)
        return x, new_cache


class Llama(nn.Module):
    """Returns logits [b, l, vocab]; with ``cache`` returns (logits, cache)."""
    cfg: LlamaConfig

    qtensor_params = True   # QDense consumes QTensor kernels (int8 serving)

    @nn.compact
    def __call__(self, input_ids, deterministic=True, positions=None,
                 cache=None):
        cfg = self.cfg
        b, l = input_ids.shape
        paged = cache is not None and "page_table" in cache
        if positions is None:
            if paged:
                lens = cache["lengths"]
                if "slot" in cache:      # chunked prefill (row per slot)
                    positions = lens[cache["slot"]][:, None] + \
                        jnp.arange(l)[None, :]
                elif "widths" in cache:  # teacher-forced verify (l == K+1)
                    positions = lens[:, None] + jnp.arange(l)[None, :]
                else:                    # continuous-batch decode (l == 1)
                    positions = lens[:, None]
                positions = jnp.broadcast_to(positions, (b, l))
            elif cache is not None:
                start = cache["layers"][0]["index"]
                positions = start + jnp.arange(l)[None, :]
                positions = jnp.broadcast_to(positions, (b, l))
            else:
                positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))

        embed = self.param("embed_tokens", nn.with_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        embed_v = embed.value if hasattr(embed, "value") else embed
        # ZeRO-3 gather-at-use: under a plan the engine installed, the
        # table is gathered at the lookup and at a tied head, every
        # QDense kernel at its matmul, and the batch stays on `data`
        plan = zero_gather.active() if cache is None else None
        if plan is not None:
            x = plan.pin_batch(plan.take(
                embed_v.astype(cfg.dtype), input_ids,
                self.path + ("embed_tokens",)))
        else:
            x = embed_v.astype(cfg.dtype)[input_ids]

        block = LlamaBlock
        if cfg.remat and cache is None:
            # cache=None is an empty pytree, safe through remat
            block = nn.remat(LlamaBlock, prevent_cse=False)
        new_layer_caches = []
        for i in range(cfg.num_layers):
            layer_cache = cache["layers"][i] if cache is not None else None
            if paged:
                layer_cache = dict(layer_cache,
                                   page_table=cache["page_table"])
                for key in ("slot", "n_valid", "active", "widths",
                            "seq_axis", "seq_impl"):
                    if key in cache:
                        layer_cache[key] = cache[key]
                if "adapters" in cache:
                    from deepspeed_tpu.models.lora import layer_adapters
                    layer_cache["adapters"] = layer_adapters(cache, i)
            x, new_c = block(cfg, name=f"layers_{i}")(x, positions,
                                                      layer_cache)
            new_layer_caches.append(new_c)

        if paged and "slot" in cache:
            # chunked prefill consumes ONLY each row's boundary position
            # — skip the full-vocab head for the chunk's other positions
            x = jnp.take_along_axis(
                x, jnp.maximum(cache["n_valid"] - 1, 0)[:, None, None],
                axis=1)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x)
        if cfg.tie_embeddings and plan is not None:
            logits = plan.einsum("ble,ve->blv", x, embed_v.astype(cfg.dtype),
                                 self.path + ("embed_tokens",))
        elif cfg.tie_embeddings:
            logits = jnp.einsum("ble,ve->blv", x, embed_v.astype(cfg.dtype))
        else:
            logits = _proj(cfg, cfg.vocab_size, ("embed", "vocab"),
                           "lm_head")(x)
        if plan is not None:
            logits = plan.pin_batch(logits)
        if paged:
            if "slot" in cache:
                lengths = cache["lengths"].at[cache["slot"]].add(
                    cache["n_valid"])
            elif "widths" in cache:
                # verify: widths columns written per slot; the engine's
                # verify primitive rewinds this after acceptance
                lengths = cache["lengths"] + cache["widths"]
            else:
                lengths = cache["lengths"] + \
                    cache["active"].astype(jnp.int32)
            return logits, dict(cache, lengths=lengths,
                                layers=new_layer_caches)
        if cache is not None:
            return logits, {"layers": new_layer_caches}
        return logits


def init_kv_cache(cfg: LlamaConfig, batch_size, max_len=None,
                  dtype=jnp.bfloat16):
    """Empty KV cache pytree (reference inference_context.h workspace)."""
    max_len = max_len or cfg.max_seq_len
    layer = lambda: {
        "k": jnp.zeros((batch_size, max_len, cfg.num_kv_heads, cfg.head_dim),
                       dtype),
        "v": jnp.zeros((batch_size, max_len, cfg.num_kv_heads, cfg.head_dim),
                       dtype),
        "index": jnp.int32(0),
    }
    return {"layers": [layer() for _ in range(cfg.num_layers)]}


def init_paged_kv_cache(cfg: LlamaConfig, num_pages, page_size,
                        dtype=jnp.bfloat16):
    """Per-layer paged KV pools (serving/ subsystem) — GQA pools are
    sized to num_kv_heads and stay grouped through the paged kernel.
    ``dtype`` may be a quantized kv-dtype name ("int8"/"fp8"): int8/fp8
    payload pools plus parallel per-row f32 scale pools
    (ops/quant/kv.py storage contract)."""
    from deepspeed_tpu.ops.quant.kv import paged_pool_layer
    layer = lambda: paged_pool_layer(num_pages, page_size,
                                     cfg.num_kv_heads, cfg.head_dim,
                                     dtype)
    return {"layers": [layer() for _ in range(cfg.num_layers)]}


def llama_tiny(**overrides):
    """Test-fixture scale (reference tests/unit/simple_model.py spirit)."""
    kwargs = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, intermediate_size=128, max_seq_len=128)
    kwargs.update(overrides)
    return LlamaConfig(**kwargs)


def llama2_7b(**overrides):
    return LlamaConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                       num_heads=32, num_kv_heads=32, intermediate_size=11008,
                       max_seq_len=4096, **overrides)


def llama2_70b(**overrides):
    return LlamaConfig(vocab_size=32000, hidden_size=8192, num_layers=80,
                       num_heads=64, num_kv_heads=8, intermediate_size=28672,
                       max_seq_len=4096, **overrides)
