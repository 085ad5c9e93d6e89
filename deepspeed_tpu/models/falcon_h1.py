"""Falcon-H1-family parallel hybrid (``model_type: falcon_h1``): GQA
attention and a Mamba-2 mixer side by side in EVERY block, then a dense
gated MLP, every projection under a muP multiplier.

With ``x`` the residual stream and every norm an RMSNorm::

    x = embed[ids] * embedding_multiplier
    per block:
      u = RMSNorm_in(x)
      a = Attn(u * attention_in_multiplier) * attention_out_multiplier
      m = Mamba2(u * ssm_in_multiplier)     * ssm_out_multiplier
      x = x + a + m
      v = RMSNorm_ff(x)
      x = x + down(silu(gate(v) * mlp_multipliers[0]) * up(v))
              * mlp_multipliers[1]
    logits = lm_head(RMSNorm_f(x)) * lm_head_multiplier      (head untied)

* Attn: no bias; ``k * key_multiplier`` BEFORE the rotary; rotary over
  the whole head dim, rotate-half, ``rope_base`` (1e11 as published: the
  inverse frequencies reach 1e-11, built in float32 from the positions
  by ``ops/attention/reference.apply_rotary_emb``).
* Mamba2: ``in_proj(u') * mup_vector`` where ``mup_vector`` lays
  ``ssm_multipliers[0..4]`` over the segments ``[z | x | B | C | dt]``,
  BEFORE the conv; everything between that and ``out_proj`` is
  ops/ssm/mamba2.py (``mamba_d_ssm`` is stated, = heads x head_dim).

The caches follow the engine's family contract with BOTH kinds in one
layer: a block's entry in the serving pools holds the K/V page leaves
of ops/attention/kv_cache.py and the ``{"conv", "ssm"}`` per-slot
leaves of ops/ssm/state.py together.  Neither contract knows of the
other: the block hands each its own leaves (:func:`_split_entry`) and
merges what they return; ``PagedStep.lengths`` advances once, at the
model's top level.  ``generate()``'s dense cache likewise holds
``{k, v, conv, ssm, index}`` a layer.
"""

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.models.nemotron_h import _dt_bias_init, _uniform, _value
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.ops.attention.reference import apply_rotary_emb
from deepspeed_tpu.ops.quant.kv import is_quantized_kv
from deepspeed_tpu.ops.ssm import mamba2, state as ssm_state

STATE_LEAVES = ("conv", "ssm")      # ops/ssm/state.py's leaves of an entry


@dataclasses.dataclass(unsafe_hash=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_layers: int = 72
    intermediate_size: int = 21504
    # attention
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_base: float = 1e11
    # Mamba-2
    mamba_d_ssm: int = 4096
    mamba_num_heads: int = 32
    mamba_head_dim: int = 128
    ssm_state_size: int = 256
    n_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # muP multipliers (the 34B's published values)
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # over the in_proj segments [z | x | B | C | dt]
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)
    lm_head_multiplier: float = 0.0078125
    rms_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"

    def __post_init__(self):
        # a configuration file hands lists over (the dataclass hashes)
        # and 100000000000 as an integer no int32 holds
        self.rope_base = float(self.rope_base)
        self.ssm_multipliers = tuple(self.ssm_multipliers)
        self.mlp_multipliers = tuple(self.mlp_multipliers)
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError(
                "ssm_multipliers holds five numbers ([z | x | B | C | dt]) "
                "and mlp_multipliers two (gate, down); got "
                f"{self.ssm_multipliers} and {self.mlp_multipliers}")
        if self.mamba_d_ssm != self.mamba_num_heads * self.mamba_head_dim:
            raise ValueError(
                f"mamba_d_ssm={self.mamba_d_ssm} is not mamba_num_heads x "
                f"mamba_head_dim = {self.mamba_num_heads} x "
                f"{self.mamba_head_dim}")

    @property
    def conv_dim(self):
        return self.mamba_d_ssm + 2 * self.n_groups * self.ssm_state_size

    def ssm_dims(self):
        return dict(heads=self.mamba_num_heads, head_dim=self.mamba_head_dim,
                    groups=self.n_groups, state=self.ssm_state_size,
                    inner=self.mamba_d_ssm, conv_dim=self.conv_dim,
                    chunk=self.chunk_size, eps=self.rms_eps)

    def mup_vector(self):
        """[z | x | B | C | dt] -> one multiplier a column of in_proj."""
        gn = self.n_groups * self.ssm_state_size
        widths = (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                  self.mamba_num_heads)
        return jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in
                                zip(widths, self.ssm_multipliers)])


def _proj(cfg, features, axes, name):
    # plain normal(0.02) everywhere: the centred draw Nemotron's out_proj
    # needs (one vector every token shares, carried by a positive-mean
    # activation's column sums) is not needed here — under the muP
    # multipliers that vector is 1.4% of the stream's energy by the
    # sixth block at the published widths (PERF.md section 6, PR 37)
    from deepspeed_tpu.ops.quant.qdense import QDense
    return QDense(features, use_bias=False, dtype=cfg.dtype,
                  param_dtype=cfg.param_dtype,
                  kernel_init=nn.with_partitioning(
                      nn.initializers.normal(0.02), axes), name=name)


def _scaled(x, multiplier):
    """``x * multiplier`` with the product taken in float32 and rounded
    once: a multiplier rounded to bfloat16 first would put one common
    relative error (up to 2**-9) on a whole projection."""
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


def _split_entry(cache):
    """(the attention side's view, the state side's view) of a block's
    cache: each contract is handed its own leaves of the entry and
    nothing of the other's (the dense cache's ``index`` is the
    attention side's)."""
    if cache is None:
        return None, None
    paged = isinstance(cache, kv_cache.PagedStep)
    entry = cache.layers if paged else cache
    state = {n: entry[n] for n in STATE_LEAVES}
    kv = {n: a for n, a in entry.items() if n not in STATE_LEAVES}
    if paged:
        return (dataclasses.replace(cache, layers=kv),
                dataclasses.replace(cache, layers=state))
    return kv, state


class FalconAttention(nn.Module):
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        b, l, _ = x.shape
        h, kv_h, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _proj(cfg, h * d, ("embed", "heads"), "wq")(x)
        k = _proj(cfg, kv_h * d, ("embed", "kv"), "wk")(x)
        with jax.named_scope("attn_proj"):
            k = _scaled(k, cfg.key_multiplier)
        v = _proj(cfg, kv_h * d, ("embed", "kv"), "wv")(x)
        with jax.named_scope("rope"):
            q = apply_rotary_emb(q.reshape(b, l, h, d), positions,
                                 base=cfg.rope_base)
            k = apply_rotary_emb(k.reshape(b, l, kv_h, d), positions,
                                 base=cfg.rope_base)
        out, new_cache = kv_cache.attend(
            q, k, v.reshape(b, l, kv_h, d), positions, cache,
            impl=cfg.attn_impl)
        out = _proj(cfg, cfg.hidden_size, ("heads", "embed"), "wo")(
            out.reshape(b, l, h * d))
        return out, new_cache


class FalconMamba(nn.Module):
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        b, l, _ = x.shape
        heads, k, dims = cfg.mamba_num_heads, cfg.conv_kernel, cfg.ssm_dims()
        zxbcdt = _proj(cfg, cfg.mamba_d_ssm + cfg.conv_dim + heads,
                       ("embed", "mlp"), "in_proj")(x)
        zxbcdt = _scaled(zxbcdt, cfg.mup_vector())
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
                               (cfg.mamba_d_ssm,), cfg.param_dtype),
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
            # hoisted to its start, and every layer's gathered states
            # are then live at once (537 MB a layer at 128 prefill rows)
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
        out = _proj(cfg, cfg.hidden_size, ("mlp", "embed"), "out_proj")(
            y.astype(cfg.dtype))
        if cache is None:
            return out, None
        if paged:
            return out, ssm_state.write(cache.layers, cache, tail, h)
        return out, {"conv": tail.astype(cache["conv"].dtype), "ssm": h}


class FalconMLP(nn.Module):
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate_mult, down_mult = cfg.mlp_multipliers
        gate = _proj(cfg, cfg.intermediate_size, ("embed", "mlp"),
                     "w_gate")(x)
        up = _proj(cfg, cfg.intermediate_size, ("embed", "mlp"), "w_up")(x)
        down = _proj(cfg, cfg.hidden_size, ("mlp", "embed"), "w_down")(
            nn.silu(_scaled(gate, gate_mult)) * up)
        return _scaled(down, down_mult)


class FalconH1Block(nn.Module):
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        kv_view, state_view = _split_entry(cache)
        with jax.named_scope("norm"):
            u_attn = _scaled(u, cfg.attention_in_multiplier)
        attn, new_kv = FalconAttention(cfg, name="attn")(
            u_attn, positions, kv_view)
        with jax.named_scope("norm"):
            u_ssm = _scaled(u, cfg.ssm_in_multiplier)
        ssm, new_state = FalconMamba(cfg, name="mamba")(
            u_ssm, positions, state_view)
        with jax.named_scope("residual"):
            x = x + _scaled(attn, cfg.attention_out_multiplier) + \
                _scaled(ssm, cfg.ssm_out_multiplier)
        mlp_out = FalconMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="pre_ff_norm")(x))
        with jax.named_scope("residual"):
            x = x + mlp_out
        if cache is None:
            return x, None
        # each side returned its own leaves; the entry is both
        return x, {**new_kv, **new_state}


class FalconH1(nn.Module):
    """Returns logits [b, l, vocab]; with ``cache`` (logits, cache)."""
    cfg: FalconH1Config

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
        with jax.named_scope("embed"):
            x = _scaled(embed.astype(cfg.dtype)[input_ids],
                        cfg.embedding_multiplier)
        new_layers = []
        for i in range(cfg.num_layers):
            x, new_c = FalconH1Block(cfg, name=f"layers_{i}")(
                x, positions, kv_cache.layer_view(cache, i))
            new_layers.append(new_c)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f")(
            kv_cache.head_rows(cache, x))
        with jax.named_scope("head"):
            logits = _scaled(_proj(cfg, cfg.vocab_size, ("embed", "vocab"),
                                   "lm_head")(x), cfg.lm_head_multiplier)
        if cache is None:
            return logits
        return logits, kv_cache.advance(cache, new_layers)


def _state_args(cfg):
    return (cfg.conv_kernel, cfg.conv_dim, cfg.mamba_num_heads,
            cfg.mamba_head_dim, cfg.ssm_state_size)


def init_kv_cache(cfg: FalconH1Config, batch_size, max_len=None,
                  dtype=jnp.bfloat16):
    """``generate()``'s dense cache: K/V buffers AND one state a batch
    row, in every layer."""
    cache = kv_cache.init_dense(cfg.num_layers, batch_size,
                                max_len or cfg.max_seq_len,
                                cfg.num_kv_heads, cfg.head_dim, dtype)
    for entry in cache["layers"]:
        entry.update(ssm_state.init_state(batch_size, *_state_args(cfg),
                                          dtype))
    return cache


def init_paged_kv_cache(cfg: FalconH1Config, num_pages, page_size,
                        dtype=jnp.bfloat16, num_slots=None):
    """The serving pools: every layer's entry holds K/V pages (``dtype``
    may be a quantized kv-dtype name) and ``{"conv", "ssm"}`` per slot
    (the conv tail in bfloat16 under a quantized KV cache)."""
    if num_slots is None:
        raise ValueError(
            "a model with recurrent state sizes its pools by the slot "
            "count: init_paged_kv_cache(..., num_slots=)")
    tail_dtype = jnp.bfloat16 if is_quantized_kv(dtype) else dtype
    pools = kv_cache.init_paged(cfg.num_layers, num_pages, page_size,
                                cfg.num_kv_heads, cfg.head_dim, dtype)
    for entry in pools["layers"]:
        entry.update(ssm_state.init_state(num_slots, *_state_args(cfg),
                                          tail_dtype))
    return pools


def state_bytes_per_slot(cfg: FalconH1Config, dtype=jnp.bfloat16):
    """Exact bytes of recurrent state one slot costs over all layers."""
    tail_dtype = jnp.bfloat16 if is_quantized_kv(dtype) else dtype
    return cfg.num_layers * ssm_state.bytes_per_slot(
        *_state_args(cfg), tail_dtype)


def falcon_h1_tiny(**overrides):
    """Test-fixture scale: a GQA group of 5, two state groups, and every
    multiplier at a value of its own other than 1 (the published
    ``attention_in_multiplier`` is 1: a test of placement needs more)."""
    kwargs = dict(vocab_size=256, hidden_size=64, num_layers=2,
                  intermediate_size=96, num_heads=10, num_kv_heads=2,
                  head_dim=16, mamba_d_ssm=64, mamba_num_heads=8,
                  mamba_head_dim=8, ssm_state_size=16, n_groups=2,
                  conv_kernel=4, chunk_size=8, max_seq_len=128,
                  embedding_multiplier=2.5, attention_in_multiplier=0.8,
                  attention_out_multiplier=0.6, key_multiplier=0.35,
                  ssm_in_multiplier=0.7, ssm_out_multiplier=0.45,
                  ssm_multipliers=(0.9, 0.55, 0.42, 1.7, 2.2),
                  mlp_multipliers=(0.5, 0.3), lm_head_multiplier=0.4)
    kwargs.update(overrides)
    return FalconH1Config(**kwargs)
