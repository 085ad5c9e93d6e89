"""GPT-2-family causal transformer, TPU-first.

This is the flagship training model (BASELINE.json config #1: "HF GPT-2-small,
ZeRO stage-1"). Design notes:

* flax.linen with **logical axis names** on every param
  (``nn.with_partitioning``) — `vocab/embed/heads/kv/mlp` — so tensor
  parallelism is a sharding-rule choice (parallel/sharding.py), not a code
  change. The reference reaches TP via Megatron mpu objects
  (`deepspeed/__init__.py:59`); here it's `pjit` + rules.
* attention may route through the Pallas flash kernel (ops/attention) or the
  jnp reference oracle (CPU tests), selected by `attn_impl`.
* remat ("activation checkpointing", reference
  `runtime/activation_checkpointing/checkpointing.py`) is `nn.remat` on the
  block, policy from config.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.lora import layer_adapters, lora_delta
from deepspeed_tpu.ops.attention import kv_cache
from deepspeed_tpu.runtime.zero import gather as zero_gather


@dataclasses.dataclass(unsafe_hash=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    mlp_ratio: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.float32          # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = False
    scan_layers: bool = False          # lax.scan over layers: stacked
    # params with a leading [num_layers] dim. One compiled block instead
    # of num_layers inlined copies (fast compiles at depth), and under
    # ZeRO-3 param offload XLA streams each layer's slice from host
    # memory per scan step. Training-path only: the KV-cache decode path
    # keeps per-layer modules, and MoE interleaving is unsupported.
    attn_impl: str = "auto"            # "auto" | "reference" | "flash"
    use_bias: bool = True
    tie_embeddings: bool = True
    layer_norm_eps: float = 1e-5       # HF GPT-2/OPT/BLOOM value
    activation: str = "gelu"           # "gelu" (GPT-2/BLOOM) | "relu" (OPT)
    pos_embed: str = "learned"         # "learned" | "none" (rotary/ALiBi)
    pos_offset: int = 0                # OPT stores positions at index+2
    embed_layernorm: bool = False      # BLOOM word_embeddings_layernorm
    use_alibi: bool = False            # BLOOM attention bias
    rotary_dim: int = 0                # >0: rotary on first dims (GPT-J/NeoX)
    rotary_interleaved: bool = False   # GPT-J rotate-every-two convention
    rope_base: float = 10000.0
    parallel_residual: bool = False    # x + attn(ln1 x) + mlp(...) (J/NeoX)
    single_ln: bool = False            # GPT-J: mlp reads ln_1's output
    attn_bias: Optional[bool] = None   # GPT-J: no attn biases; default use_bias
    qkv_bias: Optional[bool] = None    # GPT-Neo: qkv unbiased, proj biased
    # per-layer local-attention windows (GPT-Neo "global"/"local"
    # alternation): entry i is layer i's window size, 0 = full causal.
    # Empty = all global.
    attn_windows: tuple = ()
    lm_head_bias: bool = False         # GPT-J lm_head carries a bias
    # MoE (reference deepspeed/moe): every `moe_every`-th block swaps its MLP
    # for a sharded MoE layer
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_use_residual: bool = False
    moe_use_rts: bool = False          # Random Token Selection (top-1 drops)
    moe_loss_coef: float = 0.01

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def _dense(features, cfg, kernel_axes, name=None, use_bias=None):
    from deepspeed_tpu.ops.quant.qdense import QDense
    return QDense(
        features,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        use_bias=cfg.use_bias if use_bias is None else use_bias,
        kernel_init=nn.with_partitioning(
            nn.initializers.normal(stddev=0.02), kernel_axes),
        name=name)


def alibi_slopes(num_heads):
    """ALiBi per-head slopes (BLOOM attention; Press et al. closed form)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        return jnp.asarray(pow2_slopes(num_heads), jnp.float32)
    closest = 2 ** math.floor(math.log2(num_heads))
    extra = pow2_slopes(2 * closest)[0::2][:num_heads - closest]
    return jnp.asarray(pow2_slopes(closest) + extra, jnp.float32)


class SelfAttention(nn.Module):
    cfg: GPTConfig
    window: int = 0   # >0: local sliding-window causal attention

    @nn.compact
    def __call__(self, x, deterministic=True, cache=None, positions=None):
        cfg = self.cfg
        b, l, _ = x.shape
        attn_bias = cfg.use_bias if cfg.attn_bias is None else cfg.attn_bias
        qkv_bias = attn_bias if cfg.qkv_bias is None else cfg.qkv_bias
        # multi-tenant serving: per-slot LoRA deltas ride the paged
        # cache as a stacked side input (models/lora.py); absent for
        # base-only traffic, so that path's trace is unchanged
        ad, ad_rows = layer_adapters(cache)
        qkv = _dense(3 * cfg.hidden_size, cfg, ("embed", "kv"), name="qkv",
                     use_bias=qkv_bias)(x)
        if ad is not None and "qkv" in ad:
            qkv = qkv + lora_delta(x, ad["qkv"], ad_rows, ad["scale"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, l, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, l, cfg.num_heads, cfg.head_dim)
        v = v.reshape(b, l, cfg.num_heads, cfg.head_dim)
        if cfg.rotary_dim:
            from deepspeed_tpu.ops.attention.reference import (
                apply_partial_rotary)
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
            q = apply_partial_rotary(q, positions, cfg.rotary_dim,
                                     base=cfg.rope_base,
                                     interleaved=cfg.rotary_interleaved)
            k = apply_partial_rotary(k, positions, cfg.rotary_dim,
                                     base=cfg.rope_base,
                                     interleaved=cfg.rotary_interleaved)
        alibi = None
        if cfg.use_alibi:
            alibi = lambda k_pos: (
                alibi_slopes(cfg.num_heads)[None, :, None, None]
                * k_pos[None, None, None, :])
        out, new_cache = kv_cache.attend(
            q, k, v, positions, cache, impl=cfg.attn_impl,
            window=self.window, key_bias=alibi)
        out = out.reshape(b, l, cfg.hidden_size)
        proj_in = out
        out = _dense(cfg.hidden_size, cfg, ("heads", "embed"), name="proj",
                     use_bias=attn_bias)(proj_in)
        if ad is not None and "proj" in ad:
            out = out + lora_delta(proj_in, ad["proj"], ad_rows,
                                   ad["scale"])
        if cfg.dropout > 0:
            out = nn.Dropout(cfg.dropout)(out, deterministic=deterministic)
        return out, new_cache


class MLP(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic=True, adapters=None, ad_rows=None):
        cfg = self.cfg
        h = _dense(cfg.mlp_ratio * cfg.hidden_size, cfg, ("embed", "mlp"),
                   name="fc_in")(x)
        if adapters is not None and "fc_in" in adapters:
            h = h + lora_delta(x, adapters["fc_in"], ad_rows,
                               adapters["scale"])
        h = nn.relu(h) if cfg.activation == "relu" else \
            nn.gelu(h, approximate=cfg.activation != "gelu_exact")
        mid = h
        h = _dense(cfg.hidden_size, cfg, ("mlp", "embed"), name="fc_out")(mid)
        if adapters is not None and "fc_out" in adapters:
            h = h + lora_delta(mid, adapters["fc_out"], ad_rows,
                               adapters["scale"])
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    cfg: GPTConfig
    use_moe: bool = False
    window: int = 0

    @nn.compact
    def __call__(self, x, deterministic=True, cache=None, positions=None,
                 pld_keep=None):
        cfg = self.cfg
        # ZeRO-3 gather-at-use (runtime/zero/gather.py): the residual
        # stream enters and leaves a block with its batch on `data`
        plan = zero_gather.active() if cache is None else None
        if plan is not None:
            x = plan.pin_batch(x)
        x_in = x
        ad, ad_rows = layer_adapters(cache)
        ln1 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                           name="ln_1")(x)
        attn_out, new_cache = SelfAttention(cfg, self.window, name="attn")(
            ln1, deterministic, cache, positions)
        if cfg.parallel_residual:
            # GPT-J / GPT-NeoX: attn and mlp branch from the same input;
            # GPT-J (single_ln) feeds the mlp ln_1's output directly
            h = ln1 if cfg.single_ln else nn.LayerNorm(
                epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="ln_2")(x)
            assert not self.use_moe, "parallel residual + MoE unsupported"
            mlp_out = MLP(cfg, name="mlp")(h, deterministic, ad, ad_rows)
            with jax.named_scope("residual"):
                out = x + attn_out + mlp_out
        else:
            with jax.named_scope("residual"):
                x = x + attn_out
            h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             name="ln_2")(x)
            if self.use_moe:
                from deepspeed_tpu.moe import MoE
                h, _, _ = MoE(hidden_size=cfg.hidden_size,
                              num_experts=cfg.moe_num_experts,
                              ffn_hidden_size=cfg.mlp_ratio * cfg.hidden_size,
                              k=cfg.moe_top_k,
                              capacity_factor=cfg.moe_capacity_factor,
                              min_capacity=cfg.moe_min_capacity,
                              use_residual=cfg.moe_use_residual,
                              use_rts=cfg.moe_use_rts,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              name="moe")(h, deterministic)
            else:
                h = MLP(cfg, name="mlp")(h, deterministic, ad, ad_rows)
            with jax.named_scope("residual"):
                out = x + h
        if pld_keep is not None:
            # progressive layer drop (reference
            # runtime/progressive_layer_drop.py + the PLD paper's
            # stochastic depth): with prob 1 - pld_keep the whole block
            # is skipped this step — the residual stream passes through.
            # Kept branches scale by 1/keep (inverted-dropout
            # convention) so the eval-time full-depth forward matches
            # the training-time expectation without a rescale pass.
            keep = jax.random.bernoulli(self.make_rng("pld"), pld_keep)
            scaled = x_in + (out - x_in) / pld_keep.astype(out.dtype)
            out = jnp.where(keep, scaled, x_in)
        if plan is not None:
            out = plan.pin_batch(out)
        return out, new_cache


def _make_embed_tables(mdl, cfg):
    """Create wte/wpe on `mdl` (shared by GPT2 and GPT2Embed so the init
    scales and logical axis names live in exactly one place)."""
    wte = mdl.param(
        "wte",
        nn.with_partitioning(nn.initializers.normal(0.02), ("vocab", "embed")),
        (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
    wte_v = wte.value if hasattr(wte, "value") else wte
    if cfg.pos_embed == "none":
        return wte_v, None
    wpe = mdl.param(
        "wpe",
        nn.with_partitioning(nn.initializers.normal(0.01), ("seq", "embed")),
        (cfg.max_seq_len + cfg.pos_offset, cfg.hidden_size), cfg.param_dtype)
    wpe_v = wpe.value if hasattr(wpe, "value") else wpe
    return wte_v, wpe_v


def _embed_tokens(wte_v, wpe_v, input_ids, cfg, positions=None,
                  gather_at=None):
    """``gather_at``: (ZeRO-3 gather plan, the path of the module that
    owns the tables), where the tables come sharded over `data` and are
    gathered at these lookups (runtime/zero/gather.py)."""
    b, l = input_ids.shape
    plan, path = gather_at or (None, ())

    def take(w, i, name):
        if plan is None:
            return w[i]
        return plan.pin_batch(plan.take(w, i, path + (name,)))

    with jax.named_scope("embed"):
        x = take(wte_v.astype(cfg.dtype), input_ids, "wte")
        if wpe_v is not None:
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
            x = x + take(wpe_v.astype(cfg.dtype),
                         positions + cfg.pos_offset, "wpe")
        return x


def _head_logits(x, cfg, *, wte_v=None, dense_ctor=None, gather_at=None):
    """ln_f + LM projection; tied path multiplies by wte, untied builds a
    lm_head Dense (caller supplies the constructors so params land on the
    calling module; ``gather_at`` as in :func:`_embed_tokens`)."""
    x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                     name="ln_f")(x)
    if cfg.tie_embeddings:
        assert wte_v is not None, "tied head needs the embedding table"
        with jax.named_scope("head"):
            if gather_at is not None:
                plan, path = gather_at
                return plan.einsum("ble,ve->blv", x,
                                   wte_v.astype(cfg.dtype), path + ("wte",))
            return jnp.einsum("ble,ve->blv", x, wte_v.astype(cfg.dtype))
    return dense_ctor(cfg.vocab_size, cfg, ("embed", "vocab"),
                      name="lm_head", use_bias=cfg.lm_head_bias)(x)


class GPT2(nn.Module):
    """Returns logits [batch, len, vocab]; with ``cache`` returns
    (logits, new_cache) — same decode contract as models/llama.py."""
    cfg: GPTConfig

    # QDense layers consume QTensor kernel leaves directly (int8 serving
    # without whole-tree dequantization; inference/engine._materialize)
    qtensor_params = True

    @nn.compact
    def __call__(self, input_ids, deterministic=True, positions=None,
                 cache=None, pld_theta=None, rltd_keep=None):
        cfg = self.cfg
        b, l = input_ids.shape
        if rltd_keep is not None and (cache is not None or
                                      rltd_keep >= l):
            rltd_keep = None     # decode / schedule-complete: full layers
        if rltd_keep is not None:
            assert not any(cfg.attn_windows) and not cfg.use_alibi, \
                "random_ltd middle layers attend over the gathered " \
                "SUBsequence, where index distance != token distance — " \
                "local attn_windows / ALiBi biases would silently " \
                "change meaning; disable one of the two"
        if positions is None:
            positions = kv_cache.positions(cache, b, l)

        wte_v, wpe_v = _make_embed_tables(self, cfg)
        # ZeRO-3 gather-at-use: under a plan the engine installed, the
        # tables are gathered at the lookups and at the tied head, every
        # QDense kernel at its matmul, and the batch stays on `data`
        plan = zero_gather.active() if cache is None else None
        gather_at = None if plan is None else (plan, self.path)
        x = _embed_tokens(wte_v, wpe_v, input_ids, cfg, positions,
                          gather_at=gather_at)
        if cfg.embed_layernorm:
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             name="ln_embed")(x)

        # progressive layer drop: keep prob shrinks with depth,
        # keep_l = 1 - (l/L) * (1 - theta) (PLD paper's progressive
        # schedule; theta from runtime/progressive_layer_drop.py via the
        # engine). Needs an apply-time "pld" rng.
        pld_keeps = None
        if pld_theta is not None and cache is None:
            fracs = (jnp.arange(cfg.num_layers) + 1.0) / cfg.num_layers
            pld_keeps = (1.0 - fracs * (1.0 - pld_theta)).astype(
                jnp.float32)

        block = Block
        if cfg.remat and cache is None:
            block = nn.remat(Block, prevent_cse=False)
        new_layer_caches = []
        if cfg.scan_layers and cache is None:
            assert cfg.moe_num_experts <= 1, \
                "scan_layers cannot interleave MoE blocks (heterogeneous)"
            assert not any(cfg.attn_windows), \
                "scan_layers needs homogeneous layers (no local windows)"
            assert rltd_keep is None, \
                "random_ltd keeps the first/last layers full-sequence " \
                "(heterogeneous shapes); use scan_layers=False"
            # one scanned block: params stack to [num_layers, ...] leaves
            # ('layers' logical axis). With the stacked leaves in host
            # memory (ZeRO-3 param offload) XLA's scan streams one layer
            # slice to HBM per step — the partitioned_param_coordinator's
            # prefetch loop (reference :218) as a compiler schedule.
            sc = dict(variable_axes={"params": 0},
                      split_rngs={"params": True, "dropout": True,
                                  "pld": True},
                      length=cfg.num_layers,
                      metadata_params={nn.PARTITION_NAME: "layers"})
            if pld_keeps is None:
                scanned = nn.scan(block, in_axes=(
                    nn.broadcast, nn.broadcast, nn.broadcast), **sc)
                x, _ = scanned(cfg, False, name="h_scan")(
                    x, deterministic, None, positions)
            else:   # per-layer keep prob rides the scan axis
                scanned = nn.scan(block, in_axes=(
                    nn.broadcast, nn.broadcast, nn.broadcast, 0), **sc)
                x, _ = scanned(cfg, False, name="h_scan")(
                    x, deterministic, None, positions, pld_keeps)
        else:
            if cfg.scan_layers:
                raise ValueError(
                    "scan_layers is a training-path option: the KV-cache "
                    "decode path needs per-layer modules. Serve with "
                    "scan_layers=False (unstack the h_scan leaves along "
                    "axis 0 into h_{i} subtrees).")
            for i in range(cfg.num_layers):
                use_moe = (cfg.moe_num_experts > 1 and
                           i % cfg.moe_every == cfg.moe_every - 1)
                win = cfg.attn_windows[i] if i < len(cfg.attn_windows) else 0
                layer_cache = kv_cache.layer_view(cache, i)
                pk = None if pld_keeps is None else pld_keeps[i]
                # random layerwise token dropping (reference
                # data_routing/basic_layer.py:14 RandomLayerTokenDrop):
                # middle layers see a random ordered subset of rltd_keep
                # tokens; dropped tokens carry their residual value past
                # the layer. First/last layers stay full-sequence (the
                # reference's default layer selection).
                if rltd_keep is not None and 0 < i < cfg.num_layers - 1:
                    from deepspeed_tpu.runtime.data_pipeline.random_ltd \
                        import (random_ltd_gather, random_ltd_indices,
                                random_ltd_scatter)
                    idx = random_ltd_indices(self.make_rng("rltd"), l,
                                             rltd_keep, b)
                    sub = random_ltd_gather(x, idx)
                    sub_pos = jnp.take_along_axis(positions, idx, axis=1)
                    sub_out, _ = block(cfg, use_moe, win, name=f"h_{i}")(
                        sub, deterministic, None, sub_pos, pk)
                    x = random_ltd_scatter(sub_out, idx, x)
                    new_layer_caches.append(None)
                    continue
                x, new_c = block(cfg, use_moe, win, name=f"h_{i}")(
                    x, deterministic, layer_cache, positions, pk)
                new_layer_caches.append(new_c)

        logits = _head_logits(kv_cache.head_rows(cache, x), cfg, wte_v=wte_v,
                              dense_ctor=_dense, gather_at=gather_at)
        if plan is not None:
            logits = plan.pin_batch(logits)
        if cache is None:
            return logits
        return logits, kv_cache.advance(cache, new_layer_caches)


def gpt2_loss_fn(logits, batch):
    """Mean next-token cross-entropy; expects batch['labels'] (already
    shifted) or computes shift from input_ids.

    HBM note: the label gather reads the RAW (bf16) logits and only the
    gathered [b, l] column upcasts — converting the whole tensor first
    would force XLA to materialize a full fp32 copy as the gather
    operand (1.6 GB at gpt2-small bench shapes). The logsumexp's upcast
    fuses into its reduction, so no fp32 tensor ever lands in HBM."""
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.pad(batch["input_ids"][:, 1:], ((0, 0), (0, 1)),
                         constant_values=-100)
    valid = labels >= 0
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(
        logits, safe_labels[..., None], axis=-1)[..., 0].astype(jnp.float32)
    nll = (logz - ll) * valid
    plan = zero_gather.active()
    if plan is not None:    # the per-token loss stays on `data` too
        nll = plan.pin_batch(nll)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


class GPT2Embed(nn.Module):
    """Embedding front (outside the pipelined region in PP)."""
    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids):
        wte_v, wpe_v = _make_embed_tables(self, self.cfg)
        return _embed_tokens(wte_v, wpe_v, input_ids, self.cfg)


class GPT2Head(nn.Module):
    """Final norm + LM projection (outside the pipelined region in PP).
    With cfg.tie_embeddings the decoder reuses the embedding table, passed
    in as `embed_params` by PipelineModule (tied_head=True)."""
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, embed_params=None):
        cfg = self.cfg
        wte_v = None
        if cfg.tie_embeddings:
            assert embed_params is not None, \
                "tie_embeddings needs PipelineModule(tied_head=True)"
            wte_v = embed_params["wte"]
            wte_v = wte_v.value if hasattr(wte_v, "value") else wte_v
        return _head_logits(x, cfg, wte_v=wte_v, dense_ctor=_dense)


def gpt2_pipeline(cfg, num_stages, num_microbatches=None, layer_weights=None,
                  schedule="1f1b"):
    """GPT-2 as a pipeline-parallel model (reference PipelineModule usage,
    e.g. Megatron GPT on DeepSpeed PP). Honors cfg.tie_embeddings via the
    PipelineModule tied-head path (reference TiedLayerSpec);
    `layer_weights` gives non-uniform stage partitioning
    (reference partition_balanced)."""
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    return PipelineModule(block=Block(cfg), num_blocks=cfg.num_layers,
                          num_stages=num_stages,
                          embed=GPT2Embed(cfg), head=GPT2Head(cfg),
                          num_microbatches=num_microbatches,
                          tied_head=cfg.tie_embeddings,
                          layer_weights=layer_weights, schedule=schedule)


def init_kv_cache(cfg: GPTConfig, batch_size, max_len=None,
                  dtype=jnp.bfloat16):
    """Empty dense KV cache of ``generate()`` at this model's head
    geometry (ops/attention/kv_cache.py holds the contract)."""
    return kv_cache.init_dense(cfg.num_layers, batch_size,
                               max_len or cfg.max_seq_len, cfg.num_heads,
                               cfg.head_dim, dtype)


def init_paged_kv_cache(cfg: GPTConfig, num_pages, page_size,
                        dtype=jnp.bfloat16):
    """Per-layer paged KV pools of the serving path at this model's
    head geometry; ``dtype`` may be a quantized kv-dtype name."""
    return kv_cache.init_paged(cfg.num_layers, num_pages, page_size,
                               cfg.num_heads, cfg.head_dim, dtype)


# canonical "HF GPT-2 small" hyperparameters
def gpt2_small(**overrides):
    return GPTConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                     num_heads=12, max_seq_len=1024, **overrides)


def gpt2_tiny(**overrides):
    """Test fixture scale (reference tests/unit/simple_model.py spirit)."""
    kwargs = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                  max_seq_len=128)
    kwargs.update(overrides)
    return GPTConfig(**kwargs)
