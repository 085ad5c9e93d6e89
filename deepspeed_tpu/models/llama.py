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

from deepspeed_tpu.models.lora import layer_adapters, lora_delta
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.ops.attention.reference import apply_rotary_emb
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
        ad, ad_rows = layer_adapters(cache)
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
        with jax.named_scope("rope"):
            q = apply_rotary_emb(q, positions, base=cfg.rope_base)
            k = apply_rotary_emb(k, positions, base=cfg.rope_base)

        # GQA stays grouped through every cache (ops/attention/kv_cache.py
        # expands it only for flash and the sequence-parallel transports)
        out, new_cache = kv_cache.attend(q, k, v, positions, cache,
                                         impl=cfg.attn_impl)
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
            if "w_gate" in adapters:
                gate = gate + lora_delta(x, adapters["w_gate"], ad_rows,
                                         adapters["scale"])
            if "w_up" in adapters:
                up = up + lora_delta(x, adapters["w_up"], ad_rows,
                                     adapters["scale"])
        h = nn.silu(gate) * up
        down = _proj(cfg, cfg.hidden_size, ("mlp", "embed"), "w_down")(h)
        if adapters is not None and "w_down" in adapters:
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
        ad, ad_rows = layer_adapters(cache)
        attn_out, new_cache = LlamaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x),
            positions, cache)
        with jax.named_scope("residual"):
            x = x + attn_out
        mlp_out = LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x),
            ad, ad_rows)
        with jax.named_scope("residual"):
            x = x + mlp_out
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
        if positions is None:
            positions = kv_cache.positions(cache, b, l)

        embed = self.param("embed_tokens", nn.with_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        embed_v = embed.value if hasattr(embed, "value") else embed
        # ZeRO-3 gather-at-use: under a plan the engine installed, the
        # table is gathered at the lookup and at a tied head, every
        # QDense kernel at its matmul, and the batch stays on `data`
        plan = zero_gather.active() if cache is None else None
        with jax.named_scope("embed"):
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
            x, new_c = block(cfg, name=f"layers_{i}")(
                x, positions, kv_cache.layer_view(cache, i))
            new_layer_caches.append(new_c)

        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(
            kv_cache.head_rows(cache, x))
        if cfg.tie_embeddings:
            with jax.named_scope("head"):
                if plan is not None:
                    logits = plan.einsum(
                        "ble,ve->blv", x, embed_v.astype(cfg.dtype),
                        self.path + ("embed_tokens",))
                else:
                    logits = jnp.einsum("ble,ve->blv", x,
                                        embed_v.astype(cfg.dtype))
        else:
            logits = _proj(cfg, cfg.vocab_size, ("embed", "vocab"),
                           "lm_head")(x)
        if plan is not None:
            logits = plan.pin_batch(logits)
        if cache is None:
            return logits
        return logits, kv_cache.advance(cache, new_layer_caches)


def init_kv_cache(cfg: LlamaConfig, batch_size, max_len=None,
                  dtype=jnp.bfloat16):
    """Empty dense KV cache of ``generate()``, sized to num_kv_heads
    (ops/attention/kv_cache.py holds the contract)."""
    return kv_cache.init_dense(cfg.num_layers, batch_size,
                               max_len or cfg.max_seq_len, cfg.num_kv_heads,
                               cfg.head_dim, dtype)


def init_paged_kv_cache(cfg: LlamaConfig, num_pages, page_size,
                        dtype=jnp.bfloat16):
    """Per-layer paged KV pools of the serving path, sized to
    num_kv_heads; ``dtype`` may be a quantized kv-dtype name."""
    return kv_cache.init_paged(cfg.num_layers, num_pages, page_size,
                               cfg.num_kv_heads, cfg.head_dim, dtype)


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
