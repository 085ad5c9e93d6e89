"""DeepSeek-V3-family decoder (``model_type: deepseek_v3``): multi-head
latent attention (MLA) in every layer, a leading dense SwiGLU MLP, then
sigmoid-routed SwiGLU experts over a held share beside a shared MLP.

Pre-norm residual, every norm an RMSNorm (``rms_eps``), untied head, no
biases::

    x = embed[ids]
    per block i:
      x = x + Attn(RMSNorm(x))
      x = x + FFN_i(RMSNorm(x))        dense for i < first_k_dense_replace
    logits = lm_head(RMSNorm_f(x))

* Attn: ``q = x W_q`` as ``num_heads x (nope + rope)``; ``[c | k_r] = x
  W_kva`` (``kv_lora_rank + rope``); ``c <- RMSNorm(c)`` (own weight);
  rotary on ``k_r`` — ONE rotated key a token that all heads share — and
  on each head's ``q_rope``, on pairs (2i, 2i + 1) where
  ``rope_interleave``, at ``rope_theta``; ``[k_nope,h | v_h] = c W_kvb``
  per head.  ``s_ij,h = (q_nope,i,h . k_nope,j,h + q_rope,i,h . k_r,j) /
  sqrt(nope + rope)`` for ``j <= i``; softmax; ``o_i,h = sum_j p v_j,h``;
  ``wo`` maps ``num_heads x v_head_dim`` back.

  Two forms of the same arithmetic.  PER HEAD (above) wherever no page
  pool is involved — a full forward, ``generate()``'s dense cache, which
  holds per-head keys and values.  ABSORBED on a serving dispatch
  (``kv_cache.PagedStep``): with ``W_kvb`` split a head into ``W_UK``
  and ``W_UV`` [rank, dim], ``q'_h = W_UK,h q_nope,h`` (rank wide) and
  ``s = [q'_h | q_rope,h] . [c | k_r]`` — a dot against the ONE vector
  the page pool holds a token a layer — ``u_h = sum_j p c_j`` (the
  leading ``rank`` features of the same vector) and ``o_h = W_UV,h^T
  u_h``.  The cache never holds a per-head key or value: ``rank + rope``
  values a token a layer (ops/quant/kv.py ``latent_pool_layer``), read
  once a page as key AND value by both paged kernels at a query group of
  ``num_heads``.  A chunk's query is made ONCE, by one contraction a
  head of ``[q_nope | rope(q_rope)]`` (``nope + rope`` wide) with
  :func:`_absorbed_query_map` — ``W_UK,h`` on the nope features, the
  identity on the rope features, zero columns up to the width the pool
  STORES (``kvq.latent_stored_dim``: 576 at 640) — so ``kv_cache.attend``
  is handed ``[q'_h | q_rope,h | 0]`` as the kernels read it and pads
  the chunk's key alone; no concatenation, layout copy or pad of a
  ``[rows, chunk, heads, 576..640]`` tensor stands between the
  contraction and the prefill kernel, which reads the query HEAD-MAJOR,
  the layout a contraction batched over heads writes
  (ops/attention/paged_prefill.py).  The map is ``heads x (nope + rope)
  x stored`` values a layer a program, the query ``tokens x heads x
  stored``: a dispatch of fewer tokens than ``nope + rope`` (a decode
  step of 64 slots against 192) would write more map than query, so it
  makes ``q'`` and ``q_rope`` apart, concatenates them and lets
  ``attend`` pad the few rows (PERF.md section 6, PR 63: the map in
  every decode step cost 2.3% of the Kanana cell's window).  Per
  (query, cached token) pair the absorbed form costs
  ``heads x (2 rank + rope)`` multiply-adds against ``heads x (nope +
  rope + v)`` plus the re-expansion of every cached token through
  ``W_kvb`` a dispatch: cheaper up to a prefill chunk of ~170 tokens at
  the published widths (PERF.md section 7).
* FFN: dense ``down(silu(gate(x)) * up(x))`` at ``intermediate_size``;
  routed (moe/held_experts.py) a float32 sigmoid router over
  ``num_router_experts`` with a choice-only correction bias, top
  ``num_experts_per_tok`` normalised and scaled, each expert the same
  SwiGLU at ``moe_intermediate_size`` (gate and up packed in ``w_up``),
  PLUS one shared SwiGLU MLP at ``n_shared_experts x
  moe_intermediate_size`` on every token, unweighted.  This chip HOLDS
  experts ``first_held_expert .. + num_held_experts`` and computes their
  part of the routed sum; the shared MLP, the router and attention are
  computed whole.

The caches follow the engine's family contract, one entry a block:
``init_kv_cache`` (``generate()``'s dense cache, per-head) and
``init_paged_kv_cache`` — a LATENT page leaf a layer and the routing
counters beside it in a routed block.  A latent page is a page: prefix
cache, speculative verify and preemption run over it as over K/V pages,
so the model declares no ``slot_state``; it declares ``latent_cache``,
which tells the engine that the pool has one head (no ``model`` axis
over it, no page-chain hand-off yet).  The scope names are the
benchmark's: attention is ``attn``, the two absorption einsums sit under
``mla_absorb`` (which never encloses a kernel call), the experts
``experts`` and the router ``router`` (both from held_experts.py), the
shared MLP ``shared``.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.models.mimo_v2 import _split_entry
# ``routing_counters`` is imported for the engine, which looks it up in
# the model's module
from deepspeed_tpu.models.nemotron_h import (  # noqa: F401
    _live_tokens, _value, count_routing, routing_counters, routing_leaves)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.ops.attention.reference import (
    apply_rotary_emb, apply_rotary_emb_interleaved)
from deepspeed_tpu.ops.quant import kv as kvq


@dataclasses.dataclass(unsafe_hash=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_layers: int = 48
    first_k_dense_replace: int = 1     # layers before this are dense
    moe_layer_freq: int = 1            # every later layer is routed
    # attention
    num_heads: int = 32
    q_lora_rank: Optional[int] = None  # null: the query is one projection
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rope_scaling: Any = None
    # feed-forward
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_shared_experts: int = 2
    num_router_experts: int = 128      # the router's width
    num_held_experts: int = 128        # experts this chip holds ...
    first_held_expert: int = 0         # ... starting here
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    max_seq_len: int = 32768
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"

    def __post_init__(self):
        self.rope_theta = float(self.rope_theta)
        if self.q_lora_rank is not None:
            raise ValueError(
                f"q_lora_rank={self.q_lora_rank}: the query's low-rank "
                "projection (q_a_proj, q_a_layernorm, q_b_proj) is not "
                "built; this file computes the query as one projection "
                "(q_lora_rank null)")
        if self.rope_scaling is not None:
            raise ValueError(
                f"rope_scaling={self.rope_scaling!r}: scaled rotary (and "
                "the softmax-scale correction that comes with it) is "
                "not built; this file takes rope_scaling null")
        if self.moe_layer_freq != 1:
            raise ValueError(
                f"moe_layer_freq={self.moe_layer_freq}: every layer from "
                "first_k_dense_replace on is routed here (frequency 1)")
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError(
                f"first_k_dense_replace={self.first_k_dense_replace} is "
                f"not within num_layers={self.num_layers}")
        if not 0 <= self.first_held_expert <= \
                self.num_router_experts - self.num_held_experts:
            raise ValueError(
                f"held experts {self.first_held_expert}..+"
                f"{self.num_held_experts} are not among the router's "
                f"{self.num_router_experts}")

    @property
    def num_kv_heads(self):
        """What the page pool holds a token: ONE latent head (the
        published ``num_key_value_heads`` is unused by this attention)."""
        return 1

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self):
        """The cached vector's width: the latent and the shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def num_routed_layers(self):
        return self.num_layers - self.first_k_dense_replace


def _proj(cfg, features, axes, name):
    # plain normal(0.02) everywhere, as in the MiMo file: silu(gate) *
    # up has zero mean at a zero-mean draw of ``up``, so no vector that
    # every token shares reaches the router
    from deepspeed_tpu.ops.quant.qdense import QDense
    return QDense(features, use_bias=False, dtype=cfg.dtype,
                  param_dtype=cfg.param_dtype,
                  kernel_init=nn.with_partitioning(
                      nn.initializers.normal(0.02), axes), name=name)


def _absorbed_query_map(w_uk, rope_dim):
    """[heads, nope + rope, stored]: a head's map from ``[q_nope |
    q_rope]`` to the query the latent pool is read with, at the pool's
    stored width (``kvq.latent_stored_dim``) -- ``W_UK`` [rank, heads,
    nope] on the nope features, the identity on the rope features, zero
    columns for the pool's padding.  A feature times 1.0 summed with
    zeros is that feature, so the rope features pass through bit for
    bit and the padding is exact zeros."""
    rank, h, dn = w_uk.shape
    stored = kvq.latent_stored_dim(rank + rope_dim)
    passes = jnp.eye(rope_dim, stored, rank, dtype=w_uk.dtype)
    return jnp.concatenate([
        jnp.pad(w_uk.transpose(1, 2, 0), ((0, 0), (0, 0), (0, stored - rank))),
        jnp.broadcast_to(passes, (h, rope_dim, stored))], 1)


class MLAttention(nn.Module):
    """Multi-head latent attention; runs in the block's ``attn`` scope."""
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        b, l, _ = x.shape
        h, rank = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        rope = apply_rotary_emb_interleaved if cfg.rope_interleave \
            else apply_rotary_emb
        q = _proj(cfg, h * (dn + dr), ("embed", "heads"), "wq")(x) \
            .reshape(b, l, h, dn + dr)
        ckr = _proj(cfg, rank + dr, ("embed", None), "wkv_a")(x)
        # the latent is normed BEFORE it is cached, the shared key
        # cached rotated
        with jax.named_scope("attn_proj"):
            c = ckr[..., :rank]
        c = RMSNorm(cfg.rms_eps, cfg.dtype, name="kv_a_norm")(c)
        with jax.named_scope("rope"):
            k_r = rope(ckr[..., None, rank:], positions,
                       base=cfg.rope_theta)
            q_nope = q[..., :dn]
            q_rope = rope(q[..., dn:], positions, base=cfg.rope_theta)
        # [rank, heads, nope | v]: head h's W_UK beside its W_UV
        w_kvb = _value(self.param(
            "wkv_b", nn.with_partitioning(nn.initializers.normal(0.02),
                                          (None, "heads", None)),
            (rank, h, dn + dv), cfg.param_dtype)).astype(cfg.dtype)
        if isinstance(cache, kv_cache.PagedStep):
            # absorbed: queries into the latent space, scores and sums
            # over the cached vectors, values out of it
            if b * l < dn + dr:
                # fewer tokens than a head's query has features (a
                # decode step): the map would be the larger tensor, so
                # q' and q_rope are made apart and attend widens them
                with jax.named_scope("mla_absorb"):
                    q_abs = jnp.einsum("blhd,rhd->blhr", q_nope,
                                       w_kvb[..., :dn])
                with jax.named_scope("attn_proj"):
                    q_lat = jnp.concatenate([q_abs, q_rope], -1)
            else:
                with jax.named_scope("mla_absorb"):
                    q_lat = jnp.einsum(
                        "blhd,hdw->blhw",
                        jnp.concatenate([q_nope, q_rope], -1),
                        _absorbed_query_map(w_kvb[..., :dn], dr))
            with jax.named_scope("attn_proj"):
                k_lat = jnp.concatenate([c, k_r[:, :, 0]], -1)
            u, new_cache = kv_cache.attend(
                q_lat, k_lat, None, positions, cache, value_dim=rank,
                scale=cfg.qk_head_dim ** -0.5)
            with jax.named_scope("mla_absorb"):
                out = jnp.einsum("blhr,rhd->blhd", u, w_kvb[..., dn:])
        else:
            with jax.named_scope("attn_proj"):
                kv = jnp.einsum("blr,rhd->blhd", c, w_kvb)
                k = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(k_r, (b, l, h, dr))],
                    -1)
                q_full, v = jnp.concatenate([q_nope, q_rope], -1), \
                    kv[..., dn:]
            out, new_cache = kv_cache.attend(
                q_full, k, v, positions, cache, impl=cfg.attn_impl)
        out = _proj(cfg, cfg.hidden_size, ("heads", "embed"), "wo")(
            out.reshape(b, l, h * dv))
        return out, new_cache


class SwiGLUMLP(nn.Module):
    cfg: DeepseekV3Config
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _proj(cfg, self.width, ("embed", "mlp"), "w_gate")(x)
        up = _proj(cfg, self.width, ("embed", "mlp"), "w_up")(x)
        return _proj(cfg, cfg.hidden_size, ("mlp", "embed"), "w_down")(
            nn.silu(gate) * up)


class DeepseekMoE(nn.Module):
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x, cache=None):
        """Returns (out, this call's routing counters on a serving
        dispatch, else None)."""
        cfg = self.cfg
        b, l, hid = x.shape
        held, inter = cfg.num_held_experts, cfg.moe_intermediate_size
        # the router stays float32 end to end, as published
        router = _value(self.param(
            "router", nn.with_partitioning(nn.initializers.normal(0.02),
                                           ("embed", None)),
            (hid, cfg.num_router_experts), jnp.float32))
        bias = _value(self.param(
            "e_score_correction_bias", nn.initializers.zeros_init(),
            (cfg.num_router_experts,), jnp.float32))
        # gate and up side by side: [.., :inter] is the gate's
        w_up = _value(self.param(
            "w_up", nn.with_partitioning(
                nn.initializers.normal(0.02),
                ("expert", "embed", "expert_mlp")),
            (held, hid, 2 * inter), cfg.param_dtype))
        w_down = _value(self.param(
            "w_down", nn.with_partitioning(
                nn.initializers.normal(0.02),
                ("expert", "expert_mlp", "embed")),
            (held, inter, hid), cfg.param_dtype))
        tokens = x.reshape(b * l, hid)
        live = _live_tokens(cache, b, l)
        if live is not None:
            live = live.reshape(b * l)
        chosen, weights = held_experts.sigmoid_topk_router(
            tokens, router, bias, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
        routed, sizes = held_experts.held_experts_ffn(
            tokens, chosen, weights, w_up, w_down, cfg.first_held_expert,
            live, activation=held_experts.swiglu)
        stats = None
        if isinstance(cache, kv_cache.PagedStep):
            stats = held_experts.routing_stats(chosen, sizes, live)
        out = routed.reshape(b, l, hid)
        if cfg.n_shared_experts:
            # ONE MLP of n_shared x inter on every token, unweighted
            out = out + SwiGLUMLP(cfg, cfg.n_shared_experts * inter,
                                  name="shared")(x)
        return out, stats


class DeepseekBlock(nn.Module):
    cfg: DeepseekV3Config
    routed: bool

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        kv_view, routing = _split_entry(cache)
        attn, new_cache = MLAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x),
            positions, kv_view)
        with jax.named_scope("residual"):
            x = x + attn
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="pre_ff_norm")(x)
        if self.routed:
            out, stats = DeepseekMoE(cfg, name="moe")(u, cache)
            if stats is not None:
                new_cache = dict(new_cache, **count_routing(routing, stats))
        else:
            out = SwiGLUMLP(cfg, cfg.intermediate_size, name="mlp")(u)
        with jax.named_scope("residual"):
            return x + out, new_cache


class DeepseekV3(nn.Module):
    """Returns logits [b, l, vocab]; with ``cache`` (logits, cache)."""
    cfg: DeepseekV3Config

    qtensor_params = True   # QDense consumes QTensor kernels
    # the page pool holds ONE vector a token a layer, read as key and
    # value by every query head (the engine: one head, no `model` axis
    # over the pool, no page-chain hand-off yet)
    latent_cache = True

    @nn.compact
    def __call__(self, input_ids, deterministic=True, positions=None,
                 cache=None):
        cfg = self.cfg
        b, l = input_ids.shape
        if positions is None:
            positions = kv_cache.positions(cache, b, l)
        embed = _value(self.param(
            "embed_tokens", nn.with_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype))
        with jax.named_scope("embed"):
            x = embed.astype(cfg.dtype)[input_ids]
        new_layers = []
        for i in range(cfg.num_layers):
            x, new_c = DeepseekBlock(
                cfg, i >= cfg.first_k_dense_replace, name=f"layers_{i}")(
                x, positions, kv_cache.layer_view(cache, i))
            new_layers.append(new_c)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f")(
            kv_cache.head_rows(cache, x))
        logits = _proj(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")(x)
        if cache is None:
            return logits
        return logits, kv_cache.advance(cache, new_layers)


def init_kv_cache(cfg: DeepseekV3Config, batch_size, max_len=None,
                  dtype=jnp.bfloat16):
    """``generate()``'s dense cache: PER-HEAD keys (nope + rope wide)
    and values, the published form — the oracle the latent pool is held
    against."""
    max_len = max_len or cfg.max_seq_len
    shape = (batch_size, max_len, cfg.num_heads)
    return {"layers": [
        {"k": jnp.zeros(shape + (cfg.qk_head_dim,), dtype),
         "v": jnp.zeros(shape + (cfg.v_head_dim,), dtype),
         "index": jnp.int32(0)} for _ in range(cfg.num_layers)]}


def init_paged_kv_cache(cfg: DeepseekV3Config, num_pages, page_size,
                        dtype=jnp.bfloat16):
    """The serving pools: one latent page leaf a layer (a quantized
    ``dtype`` is refused by name, ops/quant/kv.py) and the routing
    counters (moe/held_experts.routing_stats, summed) beside it in a
    routed block."""
    layers = []
    for i in range(cfg.num_layers):
        entry = kvq.latent_pool_layer(num_pages, page_size, cfg.latent_dim,
                                      dtype)
        if i >= cfg.first_k_dense_replace:
            entry.update(routing_leaves())
        layers.append(entry)
    return {"layers": layers}


def kv_page_bytes(cfg: DeepseekV3Config, page_size, dtype=jnp.bfloat16):
    """Exact bytes one page costs over all layers, as stored."""
    return kvq.latent_page_bytes(cfg.num_layers, cfg.latent_dim, page_size,
                                 dtype)


def latent_bytes_per_token(cfg: DeepseekV3Config, dtype=jnp.bfloat16):
    """(published, stored) bytes a token costs over all layers: the
    cached vector's own width, and the pool's (padding included)."""
    item = jnp.dtype(dtype).itemsize
    return cfg.num_layers * cfg.latent_dim * item, \
        kv_page_bytes(cfg, 1, dtype)


def deepseek_v3_tiny(**overrides):
    """Test-fixture scale: one dense and two routed layers, a latent
    wider than a head, 16 router scores of which 4 are held, two shared
    experts."""
    kwargs = dict(vocab_size=256, hidden_size=64, num_layers=3,
                  num_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
                  moe_intermediate_size=32, n_shared_experts=2,
                  num_router_experts=16, num_held_experts=4,
                  first_held_expert=0, num_experts_per_tok=3,
                  max_seq_len=128)
    kwargs.update(overrides)
    return DeepseekV3Config(**kwargs)
