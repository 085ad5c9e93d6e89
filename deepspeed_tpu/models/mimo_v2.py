"""MiMo-V2-family decoder (``model_type: mimo_v2_flash``): sliding-window
and full attention layers mixed by a pattern, each kind with its own
KV-head count, keys wider than values, a sink logit a head in the window
layers, and sigmoid-routed SwiGLU experts over a held share.

Pre-norm residual, every norm an RMSNorm (``rms_eps``), untied head::

    x = embed[ids]
    per block i:
      x = x + Attn_i(RMSNorm(x))        kind: layer_pattern[i] (0 full, 1 window)
      x = x + FFN_i(RMSNorm(x))         kind: moe_pattern[i]   (0 dense, 1 routed)
    logits = lm_head(RMSNorm_f(x))

* Attn: ``q`` as ``num_heads x head_dim``, ``k`` as ``kv x head_dim``,
  ``v`` as ``kv x v_head_dim`` with ``kv = num_kv_heads`` (full) or
  ``swa_num_kv_heads`` (window); no biases.  Rotary (rotate-half) on the
  first ``rotary_dim`` features of q and k — ``partial_rotary_factor x
  head_dim`` rounded down to an even number — at ``rope_theta`` (full)
  or ``swa_rope_theta`` (window); the rest pass through.  ``v`` times
  ``attention_value_scale`` before the weighted sum.  Scores over
  ``sqrt(head_dim)``, causal; a window layer sees the last
  ``sliding_window`` positions (its own included) and, where
  ``add_swa_attention_sink_bias``, one learned logit a query head joins
  its softmax and its column is dropped (``add_full_attention_sink_bias``
  likewise for the full layers).  ``wo`` maps ``num_heads x v_head_dim``
  back.
* FFN: dense ``down(silu(gate(x)) * up(x))`` at ``intermediate_size``;
  routed (moe/held_experts.py) a float32 sigmoid router over
  ``num_router_experts`` with a choice-only correction bias, top
  ``num_experts_per_tok`` normalised and scaled, each expert the same
  SwiGLU at ``moe_intermediate_size`` (gate and up packed in ``w_up``),
  no shared expert.  This chip HOLDS experts ``first_held_expert .. +
  num_held_experts`` and computes their part of the sum.

The caches follow the engine's family contract, one entry a block:
``init_kv_cache`` (``generate()``'s dense cache, at each layer's own
head count) and ``init_paged_kv_cache`` — K/V PAGES for a full layer
(ops/quant/kv.py at ``head_dim`` / ``v_head_dim``), a RING a slot for a
window layer (ops/attention/window.py: ``sliding_window`` positions
whatever the context, no pages), and the routing counters beside either
in a routed block.  The scope names are the benchmark's: full attention
is ``attn``, window attention ``swa``, the experts ``experts`` and the
router ``router`` (both from held_experts.py).
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm
# ``routing_counters`` is imported for the engine, which looks it up in
# the model's module
from deepspeed_tpu.models.nemotron_h import (  # noqa: F401
    ROUTING_LEAVES, _live_tokens, _value, count_routing, routing_counters,
    routing_leaves)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.attention import kv_cache, window as window_ops
from deepspeed_tpu.ops.attention.reference import apply_partial_rotary
from deepspeed_tpu.ops.quant import kv as kvq

FULL, WINDOW = 0, 1
# the minor dim of a TPU tile: what a key's width in the page pool is
# rounded up to (``MiMoV2Config.k_pool_dim``)
LANES = 128


@dataclasses.dataclass(unsafe_hash=True)
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    num_layers: int = 48
    # 0 full / 1 window a layer; 0 dense / 1 routed a layer
    layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 0) + \
        (1, 1, 1, 1, 1, 0) * 7
    moe_pattern: Tuple[int, ...] = (0,) + (1,) * 47
    # attention
    num_heads: int = 64
    num_kv_heads: int = 4             # the full layers'
    swa_num_kv_heads: int = 8         # the window layers'
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5e6
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    # feed-forward
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_router_experts: int = 256      # the router's width
    num_held_experts: int = 256        # experts this chip holds ...
    first_held_expert: int = 0         # ... starting here
    num_experts_per_tok: int = 8
    routed_scaling_factor: Optional[float] = None    # published null: 1
    norm_topk_prob: bool = True
    rms_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"

    def __post_init__(self):
        # a configuration file hands lists over (the dataclass hashes)
        self.layer_pattern = tuple(int(k) for k in self.layer_pattern)
        self.moe_pattern = tuple(int(k) for k in self.moe_pattern)
        self.rope_theta = float(self.rope_theta)
        self.swa_rope_theta = float(self.swa_rope_theta)
        if self.routed_scaling_factor is None:
            self.routed_scaling_factor = 1.0
        for name in ("layer_pattern", "moe_pattern"):
            pat = getattr(self, name)
            if len(pat) != self.num_layers or set(pat) - {0, 1}:
                raise ValueError(
                    f"{name} {pat!r} must hold num_layers="
                    f"{self.num_layers} entries of 0 or 1")
        if not 0 <= self.first_held_expert <= \
                self.num_router_experts - self.num_held_experts:
            raise ValueError(
                f"held experts {self.first_held_expert}..+"
                f"{self.num_held_experts} are not among the router's "
                f"{self.num_router_experts}")

    @property
    def rotary_dim(self):
        return int(self.partial_rotary_factor * self.head_dim) // 2 * 2

    @property
    def k_pool_dim(self):
        """The width a key takes in the PAGE pool: ``head_dim`` rounded
        up to whole lane tiles where it is over one (192 -> 256).  A
        pool [pages, page, kv_heads, 192] is not what the chip keeps:
        where the minor dim is no multiple of 128 lanes the TPU's layout
        of that shape makes the PAGE dim minor, and each paged kernel
        call (which takes row-major pages) then copies the layer's whole
        pool in and out (PERF.md section 6, PR 42 c).  q arrives padded
        alike and the scale stays 1 / sqrt(head_dim)."""
        if self.head_dim <= LANES:
            return self.head_dim
        return -(-self.head_dim // LANES) * LANES

    def kv_heads(self, kind):
        return self.swa_num_kv_heads if kind == WINDOW else self.num_kv_heads

    @property
    def num_kv_layers(self):
        """Layers that hold K/V pages (what a page costs counts these)."""
        return self.layer_pattern.count(FULL)

    @property
    def window_layers(self):
        return self.layer_pattern.count(WINDOW)


def _proj(cfg, features, axes, name):
    # plain normal(0.02) everywhere: the centred draw Nemotron's down
    # projections need (a positive-mean activation's column sums put one
    # vector every token shares into the stream) is not needed after
    # silu(gate) * up, whose mean is zero at a zero-mean draw of ``up``
    from deepspeed_tpu.ops.quant.qdense import QDense
    return QDense(features, use_bias=False, dtype=cfg.dtype,
                  param_dtype=cfg.param_dtype,
                  kernel_init=nn.with_partitioning(
                      nn.initializers.normal(0.02), axes), name=name)


class MiMoAttention(nn.Module):
    """One attention layer of either kind; the flax scope it runs in is
    the block's choice (``attn`` full, ``swa`` window)."""
    cfg: MiMoV2Config
    kind: int

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg, window = self.cfg, self.kind == WINDOW
        b, l, _ = x.shape
        h, kv_h = cfg.num_heads, cfg.kv_heads(self.kind)
        d, d_v = cfg.head_dim, cfg.v_head_dim
        q = _proj(cfg, h * d, ("embed", "heads"), "wq")(x)
        k = _proj(cfg, kv_h * d, ("embed", "kv"), "wk")(x)
        v = _proj(cfg, kv_h * d_v, ("embed", "kv"), "wv")(x)
        base = cfg.swa_rope_theta if window else cfg.rope_theta
        with jax.named_scope("rope"):
            q = apply_partial_rotary(q.reshape(b, l, h, d), positions,
                                     cfg.rotary_dim, base=base)
            k = apply_partial_rotary(k.reshape(b, l, kv_h, d), positions,
                                     cfg.rotary_dim, base=base)
        # the value scale in float32, rounded once (a scale rounded to
        # bfloat16 first would put one common error on every value)
        with jax.named_scope("attn_proj"):
            v = (v.astype(jnp.float32) * cfg.attention_value_scale) \
                .astype(v.dtype).reshape(b, l, kv_h, d_v)
        sink = None
        if cfg.add_swa_attention_sink_bias if window \
                else cfg.add_full_attention_sink_bias:
            # drawn normal(0, 1) so that it matters at a seeded init
            sink = _value(self.param(
                "sink", nn.with_partitioning(nn.initializers.normal(1.0),
                                             ("heads",)),
                (h,), jnp.float32))
        scale = None
        pad = cfg.k_pool_dim - d
        if pad and not window and isinstance(cache, kv_cache.PagedStep):
            # the page pool's key width (see the config): zeros add
            # nothing to a score
            q, k = (jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, pad)))
                    for a in (q, k))
            scale = d ** -0.5
        out, new_cache = kv_cache.attend(
            q, k, v, positions, cache, impl=cfg.attn_impl,
            window=cfg.sliding_window if window else 0, sink=sink,
            scale=scale)
        out = _proj(cfg, cfg.hidden_size, ("heads", "embed"), "wo")(
            out.reshape(b, l, h * d_v))
        return out, new_cache


class MiMoMLP(nn.Module):
    cfg: MiMoV2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _proj(cfg, cfg.intermediate_size, ("embed", "mlp"),
                     "w_gate")(x)
        up = _proj(cfg, cfg.intermediate_size, ("embed", "mlp"), "w_up")(x)
        return _proj(cfg, cfg.hidden_size, ("mlp", "embed"), "w_down")(
            nn.silu(gate) * up)


class MiMoMoE(nn.Module):
    cfg: MiMoV2Config

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
        return routed.reshape(b, l, hid), stats


def _split_entry(cache):
    """(the attention side's view of a block's cache, its routing
    counters' leaves or None): the attention contract is handed its own
    leaves of the entry and nothing else."""
    if not isinstance(cache, kv_cache.PagedStep):
        return cache, None
    entry = cache.layers
    kv = {n: a for n, a in entry.items() if n not in ROUTING_LEAVES}
    routing = {n: entry[n] for n in ROUTING_LEAVES if n in entry}
    return dataclasses.replace(cache, layers=kv), routing or None


class MiMoBlock(nn.Module):
    cfg: MiMoV2Config
    kind: int           # 0 full / 1 window
    routed: int         # 0 dense / 1 routed

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        kv_view, routing = _split_entry(cache)
        attn, new_cache = MiMoAttention(
            cfg, self.kind, name="swa" if self.kind == WINDOW else "attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x),
            positions, kv_view)
        with jax.named_scope("residual"):
            x = x + attn
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="pre_ff_norm")(x)
        if self.routed:
            out, stats = MiMoMoE(cfg, name="moe")(u, cache)
            if stats is not None:
                new_cache = dict(new_cache, **count_routing(routing, stats))
        else:
            out = MiMoMLP(cfg, name="mlp")(u)
        with jax.named_scope("residual"):
            return x + out, new_cache


class MiMoV2(nn.Module):
    """Returns logits [b, l, vocab]; with ``cache`` (logits, cache)."""
    cfg: MiMoV2Config

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
            x = embed.astype(cfg.dtype)[input_ids]
        new_layers = []
        for i, (kind, routed) in enumerate(zip(cfg.layer_pattern,
                                               cfg.moe_pattern)):
            x, new_c = MiMoBlock(cfg, kind, routed, name=f"layers_{i}")(
                x, positions, kv_cache.layer_view(cache, i))
            new_layers.append(new_c)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f")(
            kv_cache.head_rows(cache, x))
        logits = _proj(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")(x)
        if cache is None:
            return logits
        return logits, kv_cache.advance(cache, new_layers)


def init_kv_cache(cfg: MiMoV2Config, batch_size, max_len=None,
                  dtype=jnp.bfloat16):
    """``generate()``'s dense cache: K/V buffers at each layer's own
    head count and the two widths (a window layer's hold every position
    too: the mask makes the window)."""
    max_len = max_len or cfg.max_seq_len
    layers = []
    for kind in cfg.layer_pattern:
        kv_h = cfg.kv_heads(kind)
        layers.append({
            "k": jnp.zeros((batch_size, max_len, kv_h, cfg.head_dim), dtype),
            "v": jnp.zeros((batch_size, max_len, kv_h, cfg.v_head_dim),
                           dtype),
            "index": jnp.int32(0)})
    return {"layers": layers}


def _ring_dtype(dtype):
    # a ring is 128 positions a slot: it stays in bfloat16 under a
    # quantized page pool, as a recurrent layer's conv tail does
    return jnp.bfloat16 if kvq.is_quantized_kv(dtype) else dtype


def init_paged_kv_cache(cfg: MiMoV2Config, num_pages, page_size,
                        dtype=jnp.bfloat16, num_slots=None):
    """The serving pools: K/V pages for a full layer (``dtype`` may be a
    quantized kv-dtype name), a ring a slot for a window layer, and the
    routing counters (moe/held_experts.routing_stats, summed) beside
    either in a routed block."""
    if num_slots is None:
        raise ValueError(
            "a model with window rings sizes its pools by the slot "
            "count: init_paged_kv_cache(..., num_slots=)")
    layers = []
    for kind, routed in zip(cfg.layer_pattern, cfg.moe_pattern):
        if kind == WINDOW:
            entry = window_ops.init_ring(
                num_slots, cfg.sliding_window, cfg.swa_num_kv_heads,
                cfg.head_dim, cfg.v_head_dim, _ring_dtype(dtype))
        else:
            entry = kvq.paged_pool_layer(
                num_pages, page_size, cfg.num_kv_heads, cfg.k_pool_dim,
                dtype, v_dim=cfg.v_head_dim)
        if routed:
            entry.update(routing_leaves())
        layers.append(entry)
    return {"layers": layers}


def kv_page_bytes(cfg: MiMoV2Config, page_size, dtype=jnp.bfloat16):
    """Exact bytes one page costs over the full layers (keys at the
    pool's width, values at theirs)."""
    return kvq.kv_page_bytes(cfg.num_kv_layers, cfg.num_kv_heads,
                             cfg.k_pool_dim, page_size, dtype,
                             v_dim=cfg.v_head_dim)


def state_bytes_per_slot(cfg: MiMoV2Config, dtype=jnp.bfloat16):
    """Exact bytes of ring one slot costs over all window layers."""
    return cfg.window_layers * window_ops.bytes_per_slot(
        cfg.sliding_window, cfg.swa_num_kv_heads, cfg.head_dim,
        cfg.v_head_dim, _ring_dtype(dtype))


def window_ring(cfg: MiMoV2Config, dtype=jnp.bfloat16):
    """(the window, the bytes of ring one slot costs): every per-slot
    byte of this family is a ring's."""
    return cfg.sliding_window, state_bytes_per_slot(cfg, dtype)


def mimo_v2_tiny(**overrides):
    """Test-fixture scale: the leading dense full layer and one whole
    period after it (both layer kinds, a window shorter than the test
    prompts, keys wider than values, two KV-head counts, 16 router
    scores of which 4 are held)."""
    kwargs = dict(vocab_size=256, hidden_size=64, num_layers=7,
                  layer_pattern=(0, 1, 1, 1, 1, 0, 1),
                  moe_pattern=(0, 1, 1, 1, 1, 1, 1), num_heads=8,
                  num_kv_heads=2, swa_num_kv_heads=4, head_dim=24,
                  v_head_dim=16, sliding_window=16, intermediate_size=96,
                  moe_intermediate_size=32, num_router_experts=16,
                  num_held_experts=4, first_held_expert=0,
                  num_experts_per_tok=3, max_seq_len=128)
    kwargs.update(overrides)
    return MiMoV2Config(**kwargs)
