"""AFMoE-family decoder (``model_type: afmoe``, Arcee Trinity): gated,
QK-normed grouped-query attention, a sliding window with rotary in
three layers of four and full attention with NO positional encoding in
the fourth, a norm on each sub-block's output as well as its input, and
sigmoid-routed SwiGLU experts over a held share beside one shared
expert.

Pre-norm residual with sandwich norms, every norm an RMSNorm
(``rms_eps``), no biases, untied head::

    x = embed[ids] * sqrt(hidden_size)                 (mup_enabled)
    per block i:
      h = x + post_attn_norm( Attn_i( input_norm(x) ) )
      x = h + post_ff_norm ( FFN_i ( pre_ff_norm(h) ) )
    logits = lm_head(norm_f(x))

* Attn: ``q`` and the gate ``g`` as ``num_heads x head_dim``, ``k`` and
  ``v`` as ``num_kv_heads x head_dim``.  q and k are RMS-normed per
  head (one weight [head_dim] each).  ``layer_types[i]`` decides the
  rest: ``sliding_attention`` rotates all ``head_dim`` features of q
  and k (rotate-half, ``rope_theta``) and a query sees the last
  ``sliding_window`` positions, its own included; ``full_attention``
  has no positional encoding and is causal.  Scores over
  ``sqrt(head_dim)``; the output is ``wo(o * sigmoid(g))``, the gate
  applied elementwise in float32 and rounded once.
* FFN: the first ``num_dense_layers`` blocks ``down(silu(gate(u)) *
  up(u))`` at ``intermediate_size``; the others routed
  (moe/held_experts.py): a float32 sigmoid router over
  ``num_router_experts`` with a choice-only ``expert_bias``, top
  ``num_experts_per_tok`` normalised (``route_norm``) and scaled by
  ``route_scale``, each expert a SwiGLU at ``moe_intermediate_size``
  (gate and up packed in ``w_up``), PLUS one shared SwiGLU MLP at
  ``num_shared_experts x moe_intermediate_size`` on every token.  This
  chip HOLDS experts ``first_held_expert .. + num_held_experts`` and
  computes their part of the routed sum; the shared MLP, the router and
  attention are computed whole.

The caches follow the engine's family contract, one entry a block:
``init_kv_cache`` (``generate()``'s dense cache) and
``init_paged_kv_cache`` -- K/V PAGES for a full layer, a RING a slot of
``sliding_window + 2 x window_page_size`` rows for a window layer, which
the paged kernels read as a page pool of the layer's own
(ops/attention/window.py, ``init_paged_ring``), and the routing
counters beside either in a routed block.  The scope names are the
benchmark's: full attention is ``attn``, window attention ``swa``; the
per-head norms, the gate's projection and its multiply stand under
``attn_proj``; the shared expert is ``shared``, the experts ``experts``
and the router ``router`` (both from held_experts.py).
"""

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

# the SwiGLU MLP, the bias-free projection and the split of a block's
# cache entry are the sibling families' own
from deepspeed_tpu.models.deepseek_v3 import SwiGLUMLP
from deepspeed_tpu.models.llama import RMSNorm, _proj
from deepspeed_tpu.models.mimo_v2 import _split_entry
# ``routing_counters`` is imported for the engine, which looks it up in
# the model's module
from deepspeed_tpu.models.nemotron_h import (  # noqa: F401
    _live_tokens, _value, count_routing, routing_counters, routing_leaves)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.attention import kv_cache, window as window_ops
from deepspeed_tpu.ops.attention.reference import apply_partial_rotary
from deepspeed_tpu.ops.quant import kv as kvq

FULL, WINDOW = "full_attention", "sliding_attention"


@dataclasses.dataclass(unsafe_hash=True)
class AFMoEConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    num_layers: int = 60
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL) * 15
    num_dense_layers: int = 6          # the leading blocks' FFN is dense
    # attention
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 1e4
    # rows a page of a window layer's own cache (its ring is the window
    # and two such pages): 128 is what the paged kernels read on a TPU
    window_page_size: int = 128
    # feed-forward
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_router_experts: int = 256      # the router's width
    num_held_experts: int = 256        # experts this chip holds ...
    first_held_expert: int = 0         # ... starting here
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_scale: float = 2.448
    route_norm: bool = True
    mup_enabled: bool = True           # the embedding times sqrt(hidden)
    rms_eps: float = 1e-5
    # the depth the post-norms' gains are scaled by at an init (see
    # ``post_norm_gain``); 0 is num_layers, a cut of a deeper model
    # names the depth it was cut from
    depth_scale_layers: int = 0
    max_seq_len: int = 262144
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"

    def __post_init__(self):
        # a configuration file hands a list over (the dataclass hashes)
        self.layer_types = tuple(self.layer_types)
        self.rope_theta = float(self.rope_theta)
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - {FULL, WINDOW}:
            raise ValueError(
                f"layer_types {self.layer_types!r} must hold num_layers="
                f"{self.num_layers} entries of {FULL!r} or {WINDOW!r}")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} "
                             f"of {self.num_layers} layers")
        if not 0 <= self.first_held_expert <= \
                self.num_router_experts - self.num_held_experts:
            raise ValueError(
                f"held experts {self.first_held_expert}..+"
                f"{self.num_held_experts} are not among the router's "
                f"{self.num_router_experts}")

    def routed(self, i):
        return i >= self.num_dense_layers

    @property
    def post_norm_gain(self):
        """What a post-norm's gain starts at: ``(2 x depth) ** -0.5``,
        one over the root of the sub-blocks that add into the stream
        (the "depth-scaled" sandwich norm; the formula is an assumption).
        With random weights an attention layer's output is close to the
        mean of its values, the same for every token, and a post-norm of
        gain 1 would add it at the embedding's own size block after
        block: the stream, and so the router's choice, would be one
        shared direction, a few experts taking most tokens (the
        busiest of 256 7-35 x the mean where it is 2-7 x at this gain,
        measured at a reduced width).  A trained model's bias balances
        the experts; a seeded one has only this."""
        return (2 * (self.depth_scale_layers or self.num_layers)) ** -0.5

    @property
    def num_kv_layers(self):
        """Layers that hold K/V pages (what a page costs counts these)."""
        return self.layer_types.count(FULL)

    @property
    def window_layers(self):
        return self.layer_types.count(WINDOW)

    @property
    def ring_rows(self):
        return window_ops.paged_ring_rows(self.sliding_window,
                                          self.window_page_size)


class AFMoEAttention(nn.Module):
    """One attention layer of either kind; the flax scope it runs in is
    the block's choice (``attn`` full, ``swa`` window)."""
    cfg: AFMoEConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg, window = self.cfg, self.kind == WINDOW
        b, l, _ = x.shape
        h, kv_h, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _proj(cfg, h * d, ("embed", "heads"), "wq")(x)
        k = _proj(cfg, kv_h * d, ("embed", "kv"), "wk")(x)
        v = _proj(cfg, kv_h * d, ("embed", "kv"), "wv")(x)
        with jax.named_scope("attn_proj"):
            gate = _proj(cfg, h * d, ("embed", "heads"), "wg")(x)
            # one weight [head_dim] for all of q's heads, one for k's
            q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(
                q.reshape(b, l, h, d))
            k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(
                k.reshape(b, l, kv_h, d))
        if window:
            # the full layers have no positional encoding at all
            with jax.named_scope("rope"):
                q = apply_partial_rotary(q, positions, d,
                                         base=cfg.rope_theta)
                k = apply_partial_rotary(k, positions, d,
                                         base=cfg.rope_theta)
        out, new_cache = kv_cache.attend(
            q, k, v.reshape(b, l, kv_h, d), positions, cache,
            impl=cfg.attn_impl,
            window=cfg.sliding_window if window else 0)
        with jax.named_scope("attn_proj"):
            # the gate in float32, rounded once
            out = (out.reshape(b, l, h * d).astype(jnp.float32) *
                   jax.nn.sigmoid(gate.astype(jnp.float32))) \
                .astype(cfg.dtype)
        out = _proj(cfg, cfg.hidden_size, ("heads", "embed"), "wo")(out)
        return out, new_cache


class AFMoEMoE(nn.Module):
    cfg: AFMoEConfig

    @nn.compact
    def __call__(self, x, cache=None):
        """Returns (out, this call's routing counters on a serving
        dispatch, else None)."""
        cfg = self.cfg
        b, l, hid = x.shape
        held, inter = cfg.num_held_experts, cfg.moe_intermediate_size
        # the router stays float32 end to end
        router = _value(self.param(
            "router", nn.with_partitioning(nn.initializers.normal(0.02),
                                           ("embed", None)),
            (hid, cfg.num_router_experts), jnp.float32))
        bias = _value(self.param(
            "expert_bias", nn.initializers.zeros_init(),
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
            cfg.route_scale, cfg.route_norm)
        routed, sizes = held_experts.held_experts_ffn(
            tokens, chosen, weights, w_up, w_down, cfg.first_held_expert,
            live, activation=held_experts.swiglu)
        out = routed.reshape(b, l, hid)
        if cfg.num_shared_experts:
            # ONE MLP of num_shared x inter on every token, unweighted
            out = out + SwiGLUMLP(cfg, cfg.num_shared_experts * inter,
                                  name="shared")(x)
        stats = None
        if isinstance(cache, kv_cache.PagedStep):
            stats = held_experts.routing_stats(chosen, sizes, live)
        return out, stats


class PostNorm(RMSNorm):
    """The RMSNorm on a sub-block's OUTPUT: its gain starts at ``gain``
    (``AFMoEConfig.post_norm_gain``), not at 1; the forward pass is
    RMSNorm's, which finds the parameter made."""
    gain: float = 1.0

    @nn.compact
    def __call__(self, x):
        if not self.has_variable("params", "scale"):
            self.param("scale", nn.with_partitioning(
                nn.initializers.constant(self.gain), ("embed",)),
                (x.shape[-1],), jnp.float32)
        return super().__call__(x)


class AFMoEBlock(nn.Module):
    cfg: AFMoEConfig
    kind: str           # FULL / WINDOW
    routed: bool

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg

        def norm(name):
            if name.startswith("post_"):
                return PostNorm(cfg.rms_eps, cfg.dtype, cfg.post_norm_gain,
                                name=name)
            return RMSNorm(cfg.rms_eps, cfg.dtype, name=name)
        kv_view, routing = _split_entry(cache)
        attn, new_cache = AFMoEAttention(
            cfg, self.kind, name="swa" if self.kind == WINDOW else "attn")(
            norm("input_norm")(x), positions, kv_view)
        attn = norm("post_attn_norm")(attn)
        with jax.named_scope("residual"):
            x = x + attn
        u = norm("pre_ff_norm")(x)
        if self.routed:
            out, stats = AFMoEMoE(cfg, name="moe")(u, cache)
            if stats is not None:
                new_cache = dict(new_cache, **count_routing(routing, stats))
        else:
            out = SwiGLUMLP(cfg, cfg.intermediate_size, name="mlp")(u)
        with jax.named_scope("norm"):
            out = norm("post_ff_norm")(out)
        with jax.named_scope("residual"):
            return x + out, new_cache


class AFMoE(nn.Module):
    """Returns logits [b, l, vocab]; with ``cache`` (logits, cache)."""
    cfg: AFMoEConfig

    qtensor_params = True   # QDense consumes QTensor kernels
    # a ring a slot in the window layers: no prefix-cache match, no
    # speculative verify, no sequence-parallel prefill, no page-chain
    # hand-off (ops/ssm/state.SLOT_STATE_REFUSALS)
    slot_state = "a window ring"

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
            x = embed[input_ids]
            if cfg.mup_enabled:
                # in float32, rounded once
                x = x.astype(jnp.float32) * math.sqrt(cfg.hidden_size)
            x = x.astype(cfg.dtype)
        new_layers = []
        for i, kind in enumerate(cfg.layer_types):
            x, new_c = AFMoEBlock(cfg, kind, cfg.routed(i),
                                  name=f"layers_{i}")(
                x, positions, kv_cache.layer_view(cache, i))
            new_layers.append(new_c)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f")(
            kv_cache.head_rows(cache, x))
        logits = _proj(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")(x)
        if cache is None:
            return logits
        return logits, kv_cache.advance(cache, new_layers)


def init_kv_cache(cfg: AFMoEConfig, batch_size, max_len=None,
                  dtype=jnp.bfloat16):
    """``generate()``'s dense cache (a window layer's holds every
    position too: the mask makes the window)."""
    return kv_cache.init_dense(cfg.num_layers, batch_size,
                               max_len or cfg.max_seq_len,
                               cfg.num_kv_heads, cfg.head_dim, dtype)


def _ring_dtype(dtype):
    # a ring stays in bfloat16 under a quantized page pool: the paged
    # view of it carries no scale leaves
    return jnp.bfloat16 if kvq.is_quantized_kv(dtype) else dtype


def init_paged_kv_cache(cfg: AFMoEConfig, num_pages, page_size,
                        dtype=jnp.bfloat16, num_slots=None):
    """The serving pools: K/V pages for a full layer (``dtype`` may be a
    quantized kv-dtype name), a ring a slot for a window layer -- the
    window and two pages of ``window_page_size`` rows, whatever the
    pool's ``page_size`` -- and the routing counters
    (moe/held_experts.routing_stats, summed) in a routed block."""
    if num_slots is None:
        raise ValueError(
            "a model with window rings sizes its pools by the slot "
            "count: init_paged_kv_cache(..., num_slots=)")
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        if kind == WINDOW:
            entry = window_ops.init_paged_ring(
                num_slots, cfg.sliding_window, cfg.window_page_size,
                cfg.num_kv_heads, cfg.head_dim, cfg.head_dim,
                _ring_dtype(dtype))
        else:
            entry = kvq.paged_pool_layer(
                num_pages, page_size, cfg.num_kv_heads, cfg.head_dim, dtype)
        if cfg.routed(i):
            entry.update(routing_leaves())
        layers.append(entry)
    return {"layers": layers}


def kv_page_bytes(cfg: AFMoEConfig, page_size, dtype=jnp.bfloat16):
    """Exact bytes one page costs over the full layers."""
    return kvq.kv_page_bytes(cfg.num_kv_layers, cfg.num_kv_heads,
                             cfg.head_dim, page_size, dtype)


def state_bytes_per_slot(cfg: AFMoEConfig, dtype=jnp.bfloat16):
    """Exact bytes of ring one slot costs over all window layers."""
    return cfg.window_layers * window_ops.bytes_per_slot(
        cfg.ring_rows, cfg.num_kv_heads, cfg.head_dim, cfg.head_dim,
        _ring_dtype(dtype))


def window_ring(cfg: AFMoEConfig, dtype=jnp.bfloat16):
    """(the window, the bytes of ring one slot costs): every per-slot
    byte of this family is a ring's."""
    return cfg.sliding_window, state_bytes_per_slot(cfg, dtype)


def afmoe_tiny(**overrides):
    """Test-fixture scale: one leading dense block (window attention)
    and four routed ones (window, full, window, window: the published 3
    to 1), a window of two pages of 16, shorter than the test prompts,
    16 router scores of which 4 are held, one shared expert."""
    kwargs = dict(vocab_size=256, hidden_size=64, num_layers=5,
                  layer_types=(WINDOW, WINDOW, FULL, WINDOW, WINDOW),
                  num_dense_layers=1, num_heads=8, num_kv_heads=2,
                  head_dim=16, sliding_window=32, window_page_size=16,
                  intermediate_size=96, moe_intermediate_size=32,
                  num_router_experts=16, num_held_experts=4,
                  first_held_expert=0, num_experts_per_tok=3,
                  num_shared_experts=1, route_scale=2.448, max_seq_len=256)
    kwargs.update(overrides)
    return AFMoEConfig(**kwargs)
