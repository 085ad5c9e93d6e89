"""Nemotron-H-family hybrid (``model_type: nemotron_h``): Mamba-2, routed
feed-forward and GQA attention blocks in one stack.

Block i is chosen by the i-th character of ``pattern`` (the published
``hybrid_override_pattern``) and is ``x + mixer_i(RMSNorm(x))`` — one
mixer a block, no second sub-layer; after the last block ``norm_f`` and
an untied ``lm_head``:

* ``M`` Mamba-2 (ops/ssm/mamba2.py holds the mathematics, ops/ssm/
  state.py what the layer keeps between serving dispatches);
* ``E`` routed feed-forward (moe/held_experts.py): a sigmoid router over
  ``num_router_experts`` with a choice-only correction bias, top
  ``num_experts_per_tok`` normalised and scaled, ungated relu^2 experts,
  plus one shared expert.  This chip HOLDS experts ``first_held_expert
  .. + num_held_experts``: it routes over all of them and computes its
  own experts' part (expert parallelism's share of the layer);
* ``*`` attention: GQA with an explicit ``head_dim``, causal, no bias
  and NO positional embedding (the Mamba layers carry order; the
  published ``rope_theta`` is unused by the family).

The caches follow the engine's family contract: ``init_kv_cache`` (the
dense cache of ``generate()``) and ``init_paged_kv_cache`` (the serving
pools), one entry per block — K/V pages for ``*``, ``{"conv", "ssm"}``
per SLOT for ``M``, the routing counters for ``E``.
"""

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.ops.quant.kv import is_quantized_kv
from deepspeed_tpu.ops.ssm import mamba2, state as ssm_state

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
ROUTING_STATS = 5       # moe/held_experts.routing_stats' length


@dataclasses.dataclass(unsafe_hash=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_layers: int = 52
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # routed feed-forward
    num_router_experts: int = 128      # the router's width
    num_held_experts: int = 128        # experts this chip holds ...
    first_held_expert: int = 0         # ... starting here
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"

    def __post_init__(self):
        if len(self.pattern) != self.num_layers or \
                set(self.pattern) - set(KINDS):
            raise ValueError(
                f"pattern {self.pattern!r} must hold num_layers="
                f"{self.num_layers} characters of {sorted(KINDS)}")
        if not 0 <= self.first_held_expert <= \
                self.num_router_experts - self.num_held_experts:
            raise ValueError(
                f"held experts {self.first_held_expert}..+"
                f"{self.num_held_experts} are not among the router's "
                f"{self.num_router_experts}")

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def recurrent_layers(self):
        return self.pattern.count("M")

    @property
    def num_kv_layers(self):
        """Layers that hold K/V pages (what a page costs counts these)."""
        return self.pattern.count("*")

    def ssm_dims(self):
        return dict(heads=self.mamba_num_heads, head_dim=self.mamba_head_dim,
                    groups=self.n_groups, state=self.ssm_state_size,
                    inner=self.mamba_inner, conv_dim=self.conv_dim,
                    chunk=self.chunk_size, eps=self.rms_eps)


def _centred_normal(stddev, axis):
    """normal(stddev) with its mean over ``axis`` (the input dim) taken
    out, so a constant input maps to zero.  The projections that follow
    a positive-mean activation (relu^2, the silu-gated state-space
    output) are drawn so: at a plain normal(0.02) init their column sums
    carry that mean into the residual stream as ONE vector every token
    shares, it grows to 88% of the router's input by the 24th block at
    the published widths, and every token then takes nearly the same
    experts (76 of 128 never chosen; with this draw the busiest takes
    2.1 times the mean; PERF.md section 6, PR 35).  Trained weights
    route evenly; a seeded stand-in for them has to as well."""
    def init(key, shape, dtype):
        w = jax.random.normal(key, shape, jnp.float32) * stddev
        return (w - w.mean(axis, keepdims=True)).astype(dtype)
    return init


def _proj(cfg, features, axes, name, centred=False):
    from deepspeed_tpu.ops.quant.qdense import QDense
    init = _centred_normal(0.02, 0) if centred \
        else nn.initializers.normal(0.02)
    return QDense(features, use_bias=False, dtype=cfg.dtype,
                  param_dtype=cfg.param_dtype,
                  kernel_init=nn.with_partitioning(init, axes), name=name)


def _value(p):
    return p.value if hasattr(p, "value") else p


def _live_tokens(cache, b, l):
    """[b, l] bool: the tokens of this call that exist (a serving
    dispatch carries padding columns and idle slots), or None."""
    if not isinstance(cache, kv_cache.PagedStep):
        return None
    if cache.mode == "decode":
        return jnp.broadcast_to(cache.count.astype(bool)[:, None], (b, l))
    return jnp.arange(l)[None, :] < cache.count[:, None]


class NemotronAttention(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        b, l, _ = x.shape
        h, kv_h, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _proj(cfg, h * d, ("embed", "heads"), "wq")(x)
        k = _proj(cfg, kv_h * d, ("embed", "kv"), "wk")(x)
        v = _proj(cfg, kv_h * d, ("embed", "kv"), "wv")(x)
        # no rotary: the family's attention layers are position-free
        out, new_cache = kv_cache.attend(
            q.reshape(b, l, h, d), k.reshape(b, l, kv_h, d),
            v.reshape(b, l, kv_h, d), positions, cache, impl=cfg.attn_impl)
        out = _proj(cfg, cfg.hidden_size, ("heads", "embed"), "wo")(
            out.reshape(b, l, h * d))
        return out, new_cache


def _dt_bias_init(cfg):
    """Inverse softplus of dt drawn log-uniform in [time_step_min,
    time_step_max], as the published initialisation."""
    def init(key, shape, dtype):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) *
                     (hi - lo) + lo)
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _uniform(bound):
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    return init


class NemotronMamba(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        b, l, _ = x.shape
        heads, k, dims = cfg.mamba_num_heads, cfg.conv_kernel, cfg.ssm_dims()
        zxbcdt = _proj(cfg, cfg.mamba_inner + cfg.conv_dim + heads,
                       ("embed", "mlp"), "in_proj")(x)
        bound = 1.0 / math.sqrt(k)     # torch's depthwise Conv1d default
        w = {
            "conv_w": self.param("conv_w", _uniform(bound),
                                 (k, cfg.conv_dim), cfg.param_dtype),
            "conv_b": self.param("conv_b", _uniform(bound),
                                 (cfg.conv_dim,), cfg.param_dtype),
            "dt_bias": self.param("dt_bias", _dt_bias_init(cfg), (heads,),
                                  cfg.param_dtype),
            "A_log": self.param(
                "A_log", lambda key, shape, dtype: jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
                ).astype(dtype), (heads,), cfg.param_dtype),
            "D": self.param("D", nn.initializers.ones_init(), (heads,),
                            cfg.param_dtype),
            "norm": self.param("norm", nn.initializers.ones_init(),
                               (cfg.mamba_inner,), cfg.param_dtype),
        }
        w = {n: _value(p) for n, p in w.items()}
        paged = isinstance(cache, kv_cache.PagedStep)
        if cache is None:
            tail = jnp.zeros((b, k - 1, cfg.conv_dim), zxbcdt.dtype)
            h0 = jnp.zeros((b, heads, cfg.mamba_head_dim,
                            cfg.ssm_state_size), jnp.float32)
        elif paged:
            # the rows' states are read where this layer runs: a gather
            # that depends on nothing but the program's inputs is
            # hoisted to its start, and twelve layers' gathered states
            # are then live at once (3.2 GB at 128 prefill rows)
            entry, zxbcdt = lax.optimization_barrier((cache.layers, zxbcdt))
            cache = dataclasses.replace(cache, layers=entry)
            tail, h0 = ssm_state.read(entry, cache)
        else:
            tail, h0 = cache["conv"], cache["ssm"]
        if cache.mode == "decode" if paged else \
                (cache is not None and l == 1):
            y, tail, h = mamba2.mixer_token(zxbcdt, w, dims, tail, h0)
        else:
            y, tail, h = mamba2.mixer_sequence(
                zxbcdt, w, dims, tail, h0,
                cache.count if paged else None)
        out = _proj(cfg, cfg.hidden_size, ("mlp", "embed"), "out_proj",
                    centred=True)(y.astype(cfg.dtype))
        if cache is None:
            return out, None
        if paged:
            return out, ssm_state.write(cache.layers, cache, tail, h)
        return out, {"conv": tail.astype(cache["conv"].dtype), "ssm": h,
                     "index": cache["index"] + l}


class NemotronMoE(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x, positions, cache=None):
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
        w_up = _value(self.param(
            "w_up", nn.with_partitioning(
                nn.initializers.normal(0.02),
                ("expert", "embed", "expert_mlp")),
            (held, hid, inter), cfg.param_dtype))
        w_down = _value(self.param(
            "w_down", nn.with_partitioning(
                _centred_normal(0.02, 1),
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
            live)
        shared = _proj(cfg, cfg.moe_shared_expert_intermediate_size,
                       ("embed", "mlp"), "shared_up")(x)
        shared = _proj(cfg, hid, ("mlp", "embed"), "shared_down",
                       centred=True)(held_experts.relu2(shared))
        out = routed.reshape(b, l, hid) + shared
        if cache is None:
            return out, None
        if isinstance(cache, kv_cache.PagedStep):
            return out, {"routing": cache.layers["routing"] +
                         held_experts.routing_stats(chosen, sizes, live)}
        return out, {"index": cache["index"] + l}


MIXERS = {"mamba": NemotronMamba, "moe": NemotronMoE,
          "attn": NemotronAttention}


class NemotronBlock(nn.Module):
    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, cache=None):
        out, new_cache = MIXERS[self.kind](self.cfg, name=self.kind)(
            RMSNorm(self.cfg.rms_eps, self.cfg.dtype, name="norm")(x),
            positions, cache)
        return x + out, new_cache


class NemotronH(nn.Module):
    """Returns logits [b, l, vocab]; with ``cache`` (logits, cache)."""
    cfg: NemotronHConfig

    qtensor_params = True   # QDense consumes QTensor kernels
    # recurrent per-slot state: no prefix-cache match, no speculative
    # verify, no sequence-parallel prefill, no page-chain hand-off
    slot_state = "recurrent state"

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
        x = embed.astype(cfg.dtype)[input_ids]
        new_layers = []
        for i, ch in enumerate(cfg.pattern):
            x, new_c = NemotronBlock(cfg, KINDS[ch], name=f"layers_{i}")(
                x, positions, kv_cache.layer_view(cache, i))
            new_layers.append(new_c)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f")(
            kv_cache.head_rows(cache, x))
        logits = _proj(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")(x)
        if cache is None:
            return logits
        return logits, kv_cache.advance(cache, new_layers)


def _state_args(cfg):
    return (cfg.conv_kernel, cfg.conv_dim, cfg.mamba_num_heads,
            cfg.mamba_head_dim, cfg.ssm_state_size)


def init_kv_cache(cfg: NemotronHConfig, batch_size, max_len=None,
                  dtype=jnp.bfloat16):
    """``generate()``'s dense cache: K/V buffers for ``*``, one state a
    batch row for ``M``, nothing but the position for ``E``."""
    max_len = max_len or cfg.max_seq_len
    kv = (batch_size, max_len, cfg.num_kv_heads, cfg.head_dim)
    layers = []
    for ch in cfg.pattern:
        entry = {"index": jnp.int32(0)}
        if ch == "*":
            entry.update(k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype))
        elif ch == "M":
            entry.update(ssm_state.init_state(batch_size, *_state_args(cfg),
                                              dtype))
        layers.append(entry)
    return {"layers": layers}


def init_paged_kv_cache(cfg: NemotronHConfig, num_pages, page_size,
                        dtype=jnp.bfloat16, num_slots=None):
    """The serving pools: K/V pages for ``*`` (``dtype`` may be a
    quantized kv-dtype name), ``{"conv", "ssm"}`` per slot for ``M``
    (the conv tail in bfloat16 under a quantized KV cache), the routing
    counters (moe/held_experts.routing_stats, summed) for ``E``."""
    if num_slots is None:
        raise ValueError(
            "a model with recurrent state sizes its pools by the slot "
            "count: init_paged_kv_cache(..., num_slots=)")
    from deepspeed_tpu.ops.quant.kv import paged_pool_layer
    tail_dtype = jnp.bfloat16 if is_quantized_kv(dtype) else dtype
    layers = []
    for ch in cfg.pattern:
        if ch == "*":
            layers.append(paged_pool_layer(num_pages, page_size,
                                           cfg.num_kv_heads, cfg.head_dim,
                                           dtype))
        elif ch == "M":
            layers.append(ssm_state.init_state(num_slots, *_state_args(cfg),
                                               tail_dtype))
        else:
            layers.append({"routing": jnp.zeros(ROUTING_STATS, jnp.uint32)})
    return {"layers": layers}


def state_bytes_per_slot(cfg: NemotronHConfig, dtype=jnp.bfloat16):
    """Exact bytes of recurrent state one slot costs over all layers."""
    tail_dtype = jnp.bfloat16 if is_quantized_kv(dtype) else dtype
    return cfg.recurrent_layers * ssm_state.bytes_per_slot(
        *_state_args(cfg), tail_dtype)


def routing_counters(pools):
    """uint32 [5] host array: the ``E`` layers' counters summed (mod
    2**32; a reader takes differences)."""
    stats = [np.asarray(entry["routing"]) for entry in pools["layers"]
             if "routing" in entry]
    return np.sum(stats, axis=0, dtype=np.uint32)


def nemotron_h_tiny(**overrides):
    """Test-fixture scale: one whole period, every kind of block."""
    kwargs = dict(vocab_size=256, hidden_size=64, num_layers=7,
                  pattern="MEMEM*E", num_heads=4, num_kv_heads=2,
                  head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
                  ssm_state_size=16, n_groups=2, conv_kernel=4,
                  chunk_size=8, num_router_experts=16, num_held_experts=4,
                  first_held_expert=0, num_experts_per_tok=3,
                  moe_intermediate_size=32,
                  moe_shared_expert_intermediate_size=48, max_seq_len=128)
    kwargs.update(overrides)
    return NemotronHConfig(**kwargs)
