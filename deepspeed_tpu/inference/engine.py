"""InferenceEngine: TPU-native serving wrapper.

Reference: ``deepspeed/inference/engine.py:37`` — dtype conversion :422, TP
group creation :198, kernel injection :321, CUDA-graph capture :437,
``forward`` :497, generate wrapper :525 with token-latency hooks :162-196.

TPU redesign:
  * "kernel injection" (`replace_transformer_layer`) becomes a no-op
    decision: models are already native flax; `replace_with_kernel_inject`
    toggles the Pallas flash path via the model's `attn_impl`.
  * auto-TP (`module_inject/auto_tp.py`) becomes sharding: the same logical
    axis rules shard qkv/mlp weights over the `model` mesh axis; the
    row-parallel all-reduce the reference inserts as ``LinearAllreduce``
    (module_inject/layers.py:15) is emitted by XLA at the matmul.
  * CUDA-graph capture/replay is XLA compilation — always on.
  * generation = jitted prefill (batch seq -> logits+cache) + jitted
    single-token decode step, KV cache as a device-resident pytree.
"""

import functools
import os
import sys
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu.ops.ssm.state import SLOT_STATE_REFUSALS
from deepspeed_tpu.parallel import sharding as shd
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.serving.sampling import pipeline as policy_pipeline
from deepspeed_tpu.serving.sharding import (ServingShardingConfig,
                                            config_scope, is_page_leaf,
                                            pool_bytes_per_device,
                                            resolve_sequence_plan,
                                            split_pools)
from deepspeed_tpu.tracing import annotation, jit_cache_size
from deepspeed_tpu.utils.logging import log_dist

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
          "float16": jnp.float16}


def _sampling_label(do_sample, temperature, top_k, top_p):
    """Comm-ledger signature suffix for the sampling statics: greedy
    is the bare label, a sampled combo is its OWN compiled executable
    (the statics are jit static args) and must ledger separately."""
    if not do_sample or not temperature:
        return ""
    return f"[sampled T={temperature:g},k={int(top_k)},p={top_p:g}]"


def _sample_tokens(logits, rng, do_sample, temperature, top_k, top_p):
    """Next-token selection on [batch, vocab] logits, fully traced.

    THE greedy contract (speculative-decode verification depends on it):
    ``do_sample=False`` OR ``temperature == 0`` is a deterministic
    argmax over the fp32 logits — no rng is consumed — and ties break
    to the LOWEST token id (``jnp.argmax`` returns the first maximal
    index).  Verification compares drafted tokens against exactly this
    argmax, so any change here silently breaks token-exactness between
    spec-decode serving and ``generate()``.
    """
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
        if not do_sample or not temperature:
            return jnp.argmax(logits, axis=-1)
        if temperature and temperature != 1.0:
            logits = logits / temperature
        if top_k and top_k > 0:
            kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p and top_p < 1.0:
            sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # smallest set with cumulative prob >= top_p
            cutoff_idx = jnp.sum(cum < top_p, axis=-1)
            cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                         axis=-1)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(rng, logits, axis=-1)


class InferenceEngine:
    """Wraps a flax module (+ params) for generation/serving."""

    def __init__(self, model, config, params=None, mesh=None, seed=0):
        self._config = config
        self.module = model
        self.mp_world_size = config.tensor_parallel.tp_size

        # multi-slice ICI x DCN topologies are pure config: `mesh`
        # carries the within-slice (ICI) sizes, `mesh_dcn` the
        # across-slice factors; the serving axis rules are untouched
        # (model stays ICI-innermost, slots span DCN over data)
        self.mesh_dcn = {k: int(v) for k, v in (config.mesh_dcn or {})
                         .items() if int(v) > 1} or None
        if mesh is None:
            from deepspeed_tpu.parallel.topology import make_hybrid_mesh
            from deepspeed_tpu.runtime.config import MeshConfig
            mcfg = config.mesh or {"data": -1,
                                   "model": config.tensor_parallel.tp_size}
            if self.mesh_dcn:
                mesh = make_hybrid_mesh(MeshConfig(**mcfg), self.mesh_dcn,
                                        allow_subset=True)
            else:
                mesh = make_mesh(MeshConfig(**mcfg), allow_subset=True)
        self.mesh = mesh
        # paged-attention dispatch policy ("auto"|"force"|"reference");
        # trace-time static — see DeepSpeedInferenceConfig.paged_kernel
        from deepspeed_tpu.ops.attention import decode as _decode_ops
        mode = {"off": "reference"}.get(config.paged_kernel,
                                        config.paged_kernel)
        if mode not in _decode_ops.PAGED_KERNEL_MODES:
            raise ValueError(
                f"unsupported paged_kernel {config.paged_kernel!r}; "
                f"pick one of {_decode_ops.PAGED_KERNEL_MODES}")
        self.paged_kernel_mode = mode
        # don't clobber a live training engine's global mesh; module
        # internals see self.mesh via dist.mesh_scope around every trace
        if dist.get_mesh() is None:
            dist.set_mesh(mesh)
        # logical serving axes -> mesh axes (kv_heads/slots/pages/vocab;
        # serving/sharding.py); resolved lazily at first paged-serving
        # use so forward/generate-only engines never pay or constrain it
        self.serving_sharding = ServingShardingConfig()
        self._serving_shd = None
        self._validate_mesh_for_model()

        from deepspeed_tpu.ops.quant.kv import (KV_QUANT_DTYPES,
                                                kv_storage_dtype)
        if config.dtype not in DTYPES:
            raise ValueError(
                f"unsupported inference dtype {config.dtype!r}; pick one "
                f"of {sorted(DTYPES)}; dtype='int8' (weight-only "
                "quantization) is accepted via init_inference/"
                "DeepSpeedInferenceConfig")
        if config.kv_cache_dtype not in DTYPES and \
                config.kv_cache_dtype not in KV_QUANT_DTYPES:
            raise ValueError(
                f"unsupported inference kv_cache_dtype "
                f"{config.kv_cache_dtype!r}; pick one of "
                f"{sorted(DTYPES) + sorted(KV_QUANT_DTYPES)}")
        self.dtype = DTYPES[config.dtype]
        # kv_dtype is either a jnp dtype (float pools) or the quantized
        # kv-dtype NAME ("int8"/"fp8" — the paged pools then carry
        # int8/fp8 payload + parallel f32 scale pools, ops/quant/kv.py);
        # fp8 runtime support is validated HERE, at construction, not on
        # the first serving dispatch
        if config.kv_cache_dtype in KV_QUANT_DTYPES:
            kv_storage_dtype(config.kv_cache_dtype)   # runtime gate
            self.kv_dtype = config.kv_cache_dtype
        else:
            self.kv_dtype = DTYPES[config.kv_cache_dtype]
        self.kv_dtype_name = config.kv_cache_dtype
        self._rng = jax.random.PRNGKey(seed)
        self._model_times = []
        self.params = None
        self._decode_fn = None
        self._prefill_fn = None
        self._fwd = None
        # comm/compile observability (PR 12): both default OFF — the
        # zero-cost path is one attribute load + a None check per
        # dispatch, and neither can ever change tokens or compile
        # counts (pinned by tests/unit/test_comm_telemetry.py)
        self._compile_watchdog = None     # tracing.CompileWatchdog
        self._comm_capture = None         # (name,label) -> arg specs
        self._comm_ledger_cache = {}

        # "kernel injection": route attention to the Pallas path via a fresh
        # config (never mutate the caller's model — it may be live in a
        # training engine). "auto" keeps the block-alignment guard.
        cfg = getattr(model, "cfg", None)
        if config.replace_with_kernel_inject and cfg is not None and \
                getattr(cfg, "attn_impl", None) not in (None, "auto"):
            import dataclasses
            self.module = type(model)(dataclasses.replace(cfg,
                                                          attn_impl="auto"))

        ckpt = config.checkpoint
        if isinstance(ckpt, dict):
            ckpt = ckpt.get("checkpoint_dir") or ckpt.get("base_dir")
        elif hasattr(ckpt, "checkpoint_dir"):
            ckpt = ckpt.checkpoint_dir or getattr(ckpt, "base_dir", None)
        if ckpt is not None and not isinstance(ckpt, (str, os.PathLike)):
            raise ValueError(
                f"unusable checkpoint config: {config.checkpoint!r} "
                "(expected a path or {'checkpoint_dir': path})")
        if config.checkpoint is not None and ckpt is None:
            raise ValueError(
                f"unusable checkpoint config: {config.checkpoint!r} "
                "(expected a path or {'checkpoint_dir': path})")

        # a pending checkpoint load replaces provided params — skip the
        # full cast/quantize/offload of a tree about to be thrown away
        if params is not None and ckpt is None:
            self.set_params(params)
        if ckpt is not None:
            self.load_checkpoint(str(ckpt))

    # ------------------------------------------------------------------- mesh
    def _model_head_counts(self):
        """(num_heads, num_kv_heads) from the module config, or (None,
        None) when the module has no head-count contract (generic flax
        modules still forward/generate; only validation and KV-pool
        sharding need the counts)."""
        cfg = getattr(self.module, "cfg", None)
        heads = getattr(cfg, "num_heads", None)
        kv = getattr(cfg, "num_kv_heads", heads)
        return heads, kv

    @property
    def latent_cache(self):
        """True for a model whose page pool holds ONE vector a token a
        layer that every query head reads as key and value (multi-head
        latent attention; ops/quant/kv.py ``latent_pool_layer``): the
        module says so as ``latent_cache = True``.  Such a pool has one
        head: ``_model_head_counts`` reads (heads, 1) off its config."""
        return bool(getattr(self.module, "latent_cache", False))

    def _validate_mesh_for_model(self):
        """Construction-time mesh-shape validation: a ``model``-axis
        size that does not divide ``num_heads`` would shard attention
        mid-head — the exact configuration the legacy (jax<0.5) SPMD
        partitioner silently miscompiles into ~1e-2 output drift (the
        seed-era tp=8-over-4-heads failure).  Fail loudly at
        construction instead (the check lives in
        ``ServingShardingConfig.validate_heads`` so a custom rule table
        validates its own configured axis); the serving path
        additionally validates ``num_kv_heads`` when the paged KV pools
        are built (GQA pools shard their kv-head dim over ``model`` —
        kv divisibility is deliberately NOT a construction error:
        generate()-only GQA engines with tp > num_kv_heads are legal
        and tested)."""
        heads, _ = self._model_head_counts()
        if heads:
            self.serving_sharding.validate_heads(self.mesh, heads)

    def _serving_shardings(self, num_slots=None):
        """Resolved serving shardings (serving/sharding.py) for this
        mesh + model: KV pools shard kv_heads over ``model``, per-slot
        carries / token blocks / the page table shard slots over
        ``data``, page ids stay global (replicated page dim).  Raises a
        clear ValueError when ``model`` does not divide num_kv_heads.
        Resolved at first paged-serving use; the serving wrappers pass
        the live ``num_slots`` so a slot count the data axis cannot
        divide evenly degrades that one family to replicated (jax
        requires dim % shards == 0) instead of crashing — when that
        decision flips vs the cached resolution, the jitted serving
        fns are rebuilt (their pinned out_shardings carry it)."""
        def _resolve(n):
            _, kv_heads = self._model_head_counts()
            cfg = getattr(self.module, "cfg", None)
            return self.serving_sharding.resolve(
                self.mesh, num_kv_heads=kv_heads or 1,
                vocab_size=getattr(cfg, "vocab_size", None), num_slots=n)
        if self._serving_shd is None:
            self._serving_shd = _resolve(num_slots)
            self._serving_shd_slots = num_slots
        elif num_slots is not None and \
                num_slots != getattr(self, "_serving_shd_slots", None):
            fresh = _resolve(num_slots)
            if fresh.slot_axis != self._serving_shd.slot_axis:
                log_dist(
                    f"serving slot sharding -> {fresh.slot_axis or 'replicated'}"
                    f" for num_slots={num_slots}; rebuilding serving fns")
                self._drop_serving_fns()
            self._serving_shd = fresh
            self._serving_shd_slots = num_slots
        return self._serving_shd

    def _drop_serving_fns(self):
        """The jitted serving programs pin their out_shardings: when
        those change, the next dispatch builds them again."""
        self._paged_prefill_fn = None
        self._paged_prefill_sp_fn = None
        self._paged_decode_fn = None
        self._paged_decode_multi_fn = None
        self._paged_verify_fn = None
        self._paged_decode_policy_fn = None
        self._paged_verify_policy_fn = None

    def _serving_scope(self):
        """Trace scope for the model-tracing serving primitives: the
        mesh via ``dist.mesh_scope`` (module internals), the engine's
        serving rule table via ``sharding.config_scope`` (the in-graph
        KV-pool constraint must agree with the pinned out_shardings
        even under a custom table), and the paged-kernel dispatch mode
        via ``decode.kernel_mode_scope`` (so
        ``paged_decode_attention`` resolves kernel-vs-reference with
        the engine's configured policy)."""
        import contextlib
        from deepspeed_tpu.ops.attention.decode import kernel_mode_scope
        stack = contextlib.ExitStack()
        stack.enter_context(dist.mesh_scope(self.mesh))
        stack.enter_context(config_scope(self.serving_sharding))
        stack.enter_context(kernel_mode_scope(self.paged_kernel_mode))
        return stack

    def paged_kernel_decision(self, pools=None, page_size=None):
        """The paged-attention kernel-eligibility decision
        (``ops/attention/decode.paged_kernel_decision``) for THIS
        engine's model + mesh + configured mode: ``{"path", "dispatch",
        "reason"}`` of single-token decode, under ``"multi_token"`` the
        same three for the prefill / verify path, and under ``"heads"``
        the ``[num_heads, num_kv_heads]`` decided for.  ``page_size``
        comes from the live pools when given (the leaves' page dim),
        else from the argument; the serving dispatch makes the
        IDENTICAL decisions at trace time, so what health() reports is
        what runs."""
        from deepspeed_tpu.ops.attention import decode as _decode_ops
        from deepspeed_tpu.ops.quant.kv import page_leaf
        heads, kv_heads = self._model_head_counts()
        if page_size is None and pools is not None:
            layers = pools.get("layers") if isinstance(pools, dict) \
                else None
            # a hybrid's first blocks may hold state, not pages
            kv = [page_leaf(L) for L in layers or ()
                  if page_leaf(L) is not None]
            if kv:
                page_size = int(kv[0].shape[1])
        cfg = getattr(self.module, "cfg", None)
        decide = functools.partial(
            _decode_ops.paged_kernel_decision,
            num_heads=heads or 1, num_kv_heads=kv_heads or heads or 1,
            page_size=page_size, mesh=self.mesh,
            mode=self.paged_kernel_mode,
            has_bias=bool(getattr(cfg, "use_alibi", False)))
        # the head geometry decided for (20 / 4 is a query group of 5),
        # so a fall-back is read beside what it fell back FOR
        return dict(decide(), multi_token=decide(multi_token=True),
                    heads=[heads, kv_heads])

    def prefill_key_block_counter(self, pools, chunk):
        """How the ``paged_prefill`` kernel walks a ``[rows, chunk]``
        prefill dispatch over ``pools``, as a function ``(starts,
        counts) -> dict`` of ``record_prefill_dispatch``'s page
        counters (``ops/attention/paged_prefill.count_key_blocks`` at
        this engine's geometry: per-shard head counts where the kernel
        runs under ``shard_map``); None for a model without a page
        pool.  It is the kernel's own plan whichever path the decision
        takes, as ``prefill_live_page_share`` always was."""
        from deepspeed_tpu.ops.attention import decode as _decode_ops
        from deepspeed_tpu.ops.attention import paged_prefill as _pp
        from deepspeed_tpu.ops.quant.kv import page_leaf
        layers = pools.get("layers") if isinstance(pools, dict) else ()
        # a hybrid's first blocks may hold state, not pages
        leaf = next((page_leaf(L) for L in layers or ()
                     if page_leaf(L) is not None), None)
        heads, kv_heads = self._model_head_counts()
        if leaf is None or not heads:
            return None
        # leaf: [pages, page_size, (kv_heads,) d]
        kv_heads = 1 if leaf.ndim == 3 else kv_heads or heads
        with self._serving_scope():
            head_ax, _ = _decode_ops._shard_map_axes(
                self.mesh, 1, heads, kv_heads)
        shards = int(self.mesh.shape[head_ax]) if head_ax else 1
        cols, tiles, block = _pp.key_block_plan(
            chunk, heads // shards, kv_heads // shards, int(leaf.shape[1]),
            int(leaf.shape[-1]), jnp.dtype(self.dtype).itemsize,
            leaf.dtype.itemsize)
        return functools.partial(
            _pp.count_key_blocks, page_size=int(leaf.shape[1]), cols=cols,
            tiles=tiles, block=block)

    def serving_mesh_info(self, pools=None, num_slots=None):
        """Mesh topology + serving-sharding snapshot for operators
        (``bin/ds_serve`` startup log and ``health()``): per-axis mesh
        sizes, the resolved logical->mesh axis map, and — given the live
        pools — per-device KV-pool bytes (each device holds its kv-head
        shard of every page).  Pass the scheduler's ``num_slots`` so the
        snapshot reflects the slot-family resolution serving will
        actually use (an uneven slot count degrades to replicated — the
        report must say so, not echo the rule table)."""
        info = {
            "mesh_shape": {a: int(s) for a, s in self.mesh.shape.items()
                           if int(s) > 1} or {"data": 1},
            "mesh_devices": int(np.prod(list(self.mesh.shape.values()))),
            "serving_axes":
                self._serving_shardings(num_slots=num_slots).describe(),
            # the kernel-vs-reference dispatch decision, as data — an
            # accidental reference-path fallback must be visible to
            # operators, never silent (health() snapshots this)
            "paged_attention": self.paged_kernel_decision(pools=pools),
        }
        if self.mesh_dcn:
            info["mesh_hybrid"] = {
                "ici": {a: int(s) // self.mesh_dcn.get(a, 1)
                        for a, s in self.mesh.shape.items()
                        if int(s) // self.mesh_dcn.get(a, 1) > 1} or
                       {"data": 1},
                "dcn": dict(self.mesh_dcn),
            }
        if pools is not None:
            # K/V pages and per-slot recurrent state are two pools with
            # two units (a page, a slot): the page ledgers divide the
            # first by num_pages, so the second is counted beside it
            kv, state = split_pools(pools)
            info["kv_pool_bytes_per_device"] = pool_bytes_per_device(kv)
            info["kv_pool_bytes_total"] = sum(
                int(leaf.nbytes) for leaf in jax.tree.leaves(kv))
            if self.slot_state:
                info["state_pool_bytes_per_device"] = \
                    pool_bytes_per_device(state)
                info["state_pool_bytes_total"] = sum(
                    int(leaf.nbytes) for leaf in jax.tree.leaves(state))
        return info

    # ------------------------------------------------------------------ params
    def _param_shardings(self, params):
        logical = shd.get_logical_specs(params)   # from Partitioned metadata
        unboxed = shd.unbox(params)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), self.dtype), unboxed)
        pspecs = shd.tree_pspecs(self.mesh, shapes, logical, zero_stage=0,
                                 kind="param")
        return shd.tree_shardings(self.mesh, pspecs)

    def set_params(self, params, quantize=None, offload=None):
        """Cast to inference dtype and shard over the mesh (the reference's
        _convert_to_dtype + ReplaceWithTensorSlicing combined); with
        quant.enabled, Dense kernels then quantize to int8 groups
        (reference GroupQuantizer sweep, replace_module.py:138).
        `quantize=False` keeps floats (checkpoint-restore target trees)."""
        offload = (self._config.zero or {}).get("stage") == 3 \
            if offload is None else offload
        sh = self._param_shardings(params)     # needs Partitioned metadata
        params = shd.unbox(params)
        if offload:
            # larger-than-HBM loading: cast/quantize/offload LEAF BY LEAF
            # so peak device memory is one leaf, never the whole model
            return self._set_params_offloaded(params, sh, quantize)
        cast = jax.jit(
            lambda p: jax.tree.map(
                lambda x: x.astype(self.dtype)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
                p),
            out_shardings=sh)
        self.params = cast(params)
        return self._postprocess_params(quantize=quantize, offload=False)

    def _set_params_offloaded(self, params, sh_tree, quantize):
        from deepspeed_tpu.ops.quant import QTensor
        from deepspeed_tpu.ops.quant.quantizer import _eligible, quantize as q
        quantize = self._config.quant.enabled if quantize is None else quantize
        qcfg = self._config.quant

        def host(x):
            return jax.device_put(
                x, x.sharding.with_memory_kind("pinned_host"))

        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        sh_flat = jax.tree.leaves(sh_tree)
        out = []
        for (path, leaf), sh in zip(flat, sh_flat):
            dev = jax.device_put(leaf, sh)
            if jnp.issubdtype(dev.dtype, jnp.floating):
                dev = dev.astype(self.dtype)
            key = jax.tree_util.keystr(path)
            if quantize and self._quant_leaf_predicate(key) and \
                    _eligible(dev):
                qv, scale = q(dev, bits=qcfg.num_bits,
                              group_size=qcfg.group_size)
                out.append(QTensor(host(qv), host(scale), dev.dtype,
                                   qcfg.num_bits, qcfg.group_size))
            else:
                out.append(host(dev))
            del dev
        self.params = jax.tree_util.tree_unflatten(treedef, out)
        self._offload_params = True
        self._params_postprocessed = True
        self._mat_sh = jax.tree.map(
            lambda l: l.sharding.with_memory_kind("device"), self.params)
        n = sum(int(np.prod(np.shape(l)))
                for l in jax.tree.leaves(self.params))
        log_dist(f"inference params ready: {n/1e6:.1f}M, "
                 f"dtype={self._config.dtype}"
                 f"{' +int8' if quantize else ''} +host-offload "
                 f"(leaf-streamed), tp={self.mp_world_size}", ranks=[0])
        return self

    def _postprocess_params(self, quantize=None, offload=None):
        """Quantize then host-offload self.params per config (split out so
        checkpoint restore can load raw floats first)."""
        quantize = self._config.quant.enabled if quantize is None else quantize
        if quantize:
            self.params = self._quantize(self.params)
        if offload is None:
            offload = (self._config.zero or {}).get("stage") == 3
        self._offload_params = bool(offload)
        self._params_postprocessed = bool(quantize or offload)
        if offload:
            # ZeRO-Inference (reference zero.stage=3 + init_inference,
            # docs/2022-09-10-zero-inference.md): weights live in PINNED
            # HOST memory and stream to HBM per use inside the jitted
            # forward — models larger than HBM serve from host RAM, and
            # with int8 the PCIe/DMA stream is the quantized bytes.
            self._mat_sh = jax.tree.map(
                lambda l: l.sharding.with_memory_kind("device"), self.params)
            self.params = jax.tree.map(
                lambda l: jax.device_put(
                    l, l.sharding.with_memory_kind("pinned_host")),
                self.params)
        n = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(self.params))
        log_dist(f"inference params ready: {n/1e6:.1f}M, dtype={self._config.dtype}"
                 f"{' +int8' if quantize else ''}"
                 f"{' +host-offload' if offload else ''}, "
                 f"tp={self.mp_world_size}", ranks=[0])
        return self

    @property
    def weight_dtype_name(self):
        """Canonical weight-storage dtype for operator surfaces
        (health(), ds_serve startup log): "int8" under weight-only
        quantization, else the compute dtype name."""
        return "int8" if self._config.quant.enabled else self._config.dtype

    @staticmethod
    def _quant_leaf_predicate(path):
        """THE quant leaf predicate — shared by the on-device tree sweep
        and the leaf-streamed offload path."""
        return "kernel" in path

    def _quantize(self, params):
        from deepspeed_tpu.ops.quant import quantize_tree
        qcfg = self._config.quant
        return quantize_tree(
            params, bits=qcfg.num_bits, group_size=qcfg.group_size,
            predicate=lambda path, leaf: self._quant_leaf_predicate(path))

    def _materialize(self, params):
        """Inside a jitted computation: stream host-offloaded leaves to
        device memory (XLA schedules each transfer next to its consumer).
        QTensor leaves pass through untouched when the module is
        quant-aware (our models' QDense consumes them directly — on a
        single TPU chip via the Pallas dequant-matmul, so the weight
        never materializes in bf16); only legacy float-kernel modules get
        the whole-tree dequantize. Offloaded int8 weights cross the
        host-device link quantized either way."""
        if getattr(self, "_offload_params", False):
            params = jax.tree.map(jax.device_put, params, self._mat_sh)
        if not self._config.quant.enabled or \
                getattr(self.module, "qtensor_params", False):
            return params
        from deepspeed_tpu.ops.quant import dequantize_tree
        return dequantize_tree(params)

    def init_params(self, example_ids=None, seed=0, quantize=None,
                    offload=None):
        """Random init (benchmarks / smoke tests)."""
        ids = example_ids if example_ids is not None \
            else jnp.zeros((1, 8), jnp.int32)
        variables = self.module.init(jax.random.PRNGKey(seed),
                                     jnp.asarray(ids))
        return self.set_params(variables.get("params", variables),
                               quantize=quantize, offload=offload)

    def _host_float_template(self):
        """A zero-valued float param tree already placed in PINNED HOST
        memory, built leaf-by-leaf from eval_shape — nothing ever
        materializes on device (the restore target for larger-than-HBM
        ZeRO-Inference loads)."""
        ids = jnp.zeros((1, 8), jnp.int32)
        boxed = jax.eval_shape(
            lambda: self.module.init(jax.random.PRNGKey(0), ids))["params"]
        sh_tree = self._param_shardings(boxed)
        shapes = shd.unbox(boxed)
        flat, treedef = jax.tree_util.tree_flatten(shapes)
        sh_flat = jax.tree.leaves(sh_tree)
        out = []
        for leaf, sh in zip(flat, sh_flat):
            dtype = self.dtype if jnp.issubdtype(leaf.dtype, jnp.floating) \
                else leaf.dtype
            out.append(jax.device_put(
                np.zeros(leaf.shape, dtype),
                sh.with_memory_kind("pinned_host")))
        return jax.tree_util.tree_unflatten(treedef, out)

    def load_checkpoint(self, path, tag=None):
        """Load params saved by the training engine's save_checkpoint.
        For ZeRO-Inference engines the restore streams straight into host
        memory (and quantizes leaf-by-leaf) — peak device memory during
        the load is at most one parameter. Reads go through the
        pluggable checkpoint backend (checkpoint/backend.py) so custom
        training-side engines serve too."""
        from deepspeed_tpu.checkpoint.backend import get_checkpoint_engine
        backend = get_checkpoint_engine(self._config.checkpoint_engine)

        def load_subtree(path, target, prefix):
            return backend.load_subtree(path, target, prefix=prefix)
        if tag is None:
            latest = os.path.join(path, "latest")
            if os.path.exists(latest):
                with open(latest) as f:
                    tag = f.read().strip()
        full = os.path.join(path, tag) if tag else path
        quant = self._config.quant.enabled
        offload = (self._config.zero or {}).get("stage") == 3

        if offload:
            target = self._host_float_template()
            loaded = load_subtree(full, target, prefix=".params")
            # leaf-streamed postprocess: host float -> (device) quantize
            # -> host, one leaf at a time
            from deepspeed_tpu.ops.quant import QTensor
            from deepspeed_tpu.ops.quant.quantizer import (_eligible,
                                                           quantize as q)
            qcfg = self._config.quant
            flat, treedef = jax.tree_util.tree_flatten_with_path(loaded)
            out = []
            for pth, leaf in flat:
                key = jax.tree_util.keystr(pth)
                if quant and self._quant_leaf_predicate(key) and \
                        _eligible(leaf):
                    dev = jax.device_put(
                        leaf, leaf.sharding.with_memory_kind("device"))
                    qv, scale = q(dev, bits=qcfg.num_bits,
                                  group_size=qcfg.group_size)
                    host = lambda x: jax.device_put(
                        x, x.sharding.with_memory_kind("pinned_host"))
                    out.append(QTensor(host(qv), host(scale), dev.dtype,
                                       qcfg.num_bits, qcfg.group_size))
                    del dev
                else:
                    out.append(leaf)
            self.params = jax.tree_util.tree_unflatten(treedef, out)
            self._offload_params = True
            self._params_postprocessed = True
            self._mat_sh = jax.tree.map(
                lambda l: l.sharding.with_memory_kind("device"), self.params)
            log_dist(f"inference checkpoint loaded from {full} "
                     "(host-offloaded, leaf-streamed)", ranks=[0])
            return self

        if self.params is None or quant or \
                getattr(self, "_params_postprocessed", False):
            # restore needs a float on-DEVICE target tree (shapes +
            # shardings); quantization re-applies after the load. Also
            # rebuilds when the LIVE params were postprocessed (e.g. an
            # explicit set_params override) so the restore target is
            # never a quantized/host tree
            self.init_params(quantize=False, offload=False)
        # restore only the params subtree of the saved TrainState
        self.params = load_subtree(full, self.params, prefix=".params")
        self._postprocess_params(quantize=quant, offload=False)
        log_dist(f"inference checkpoint loaded from {full}", ranks=[0])
        return self

    # ----------------------------------------------------------------- forward
    def forward(self, input_ids, **kwargs):
        """Full forward -> logits (reference engine.forward :497). Extra
        kwargs reach the module: arrays are traced (attention_mask,
        token_type_ids), python scalars/bools are static (deterministic)."""
        assert self.params is not None, "set_params/init_params first"
        static = {k: v for k, v in kwargs.items()
                  if isinstance(v, (bool, str)) or v is None}
        arrays = {k: jnp.asarray(v) for k, v in kwargs.items()
                  if k not in static}
        key = tuple(sorted(static.items()))
        if not hasattr(self, "_fwd_cache"):
            self._fwd_cache = {}
        if key not in self._fwd_cache:
            module = self.module
            materialize = self._materialize

            def fwd(params, ids, **kw):
                return module.apply({"params": materialize(params)}, ids,
                                    **static, **kw)

            self._fwd_cache[key] = jax.jit(fwd)
        t0 = time.time()
        with dist.mesh_scope(self.mesh):
            out = self._fwd_cache[key](self.params, jnp.asarray(input_ids),
                                       **arrays)
        out.block_until_ready()
        self._model_times.append(time.time() - t0)
        return out

    __call__ = forward

    def model_times(self):
        """Per-call latencies (reference token-latency hooks :162-196)."""
        t, self._model_times = self._model_times, []
        return t

    # ---------------------------------------------------------------- generate
    def _cache_module(self):
        """The model family's file: one that follows the KV-cache
        contract (ops/attention/kv_cache.py) exports ``init_kv_cache``
        and ``init_paged_kv_cache`` at its own head geometry."""
        mod = sys.modules.get(type(self.module).__module__)
        return mod if hasattr(mod, "init_paged_kv_cache") else None

    def _init_cache(self, batch_size, max_len):
        from deepspeed_tpu.ops.quant.kv import is_quantized_kv
        mod = self._cache_module()
        # quantized kv_dtype applies to the PAGED serving pools only;
        # generate()'s dense cache stays fp32 — generate() is the
        # divergence oracle the quantized serving path is measured
        # against, so it must not quantize out from under that contract
        dt = jnp.float32 if is_quantized_kv(self.kv_dtype) \
            else self.kv_dtype
        return mod.init_kv_cache(self.module.cfg, batch_size,
                                 max_len=max_len, dtype=dt)

    def _build_gen_fns(self):
        module = self.module
        materialize = self._materialize

        def prefill(params, ids, cache):
            logits, cache = module.apply({"params": materialize(params)},
                                         ids, cache=cache)
            return logits[:, -1], cache

        def decode(params, tok, cache, rng, do_sample, temperature, top_k,
                   top_p):
            logits, cache = module.apply({"params": materialize(params)},
                                         tok[:, None], cache=cache)
            nxt = _sample_tokens(logits[:, 0], rng, do_sample, temperature,
                                 top_k, top_p)
            return nxt, cache

        def decode_loop(params, tok, cache, finished, rng, n_steps,
                        do_sample, temperature, top_k, top_p, eos, fill):
            """The whole decode loop as ONE dispatch (lax.scan over steps).
            The per-token Python loop pays a host round-trip per token;
            this is the CUDA-graph-replay equivalent of the reference
            (inference/engine.py:437-456), expressed as a traced loop."""
            def body(carry, i):
                tok, cache, finished = carry
                logits, cache = module.apply(
                    {"params": materialize(params)}, tok[:, None],
                    cache=cache)
                nxt = _sample_tokens(logits[:, 0], jax.random.fold_in(rng, i),
                                     do_sample, temperature, top_k, top_p)
                if eos is not None:
                    nxt = jnp.where(finished, fill, nxt.astype(jnp.int32))
                    finished = finished | (nxt == eos)
                return (nxt.astype(tok.dtype), cache, finished), nxt
            (tok, cache, finished), toks = jax.lax.scan(
                body, (tok, cache, finished), jnp.arange(n_steps))
            return toks.T, cache, finished  # [b, n_steps]

        self._prefill_fn = jax.jit(prefill, donate_argnums=(2,))
        # sampling params static: new compile per (do_sample, temp, k, p) combo
        self._decode_fn = jax.jit(decode, donate_argnums=(2,),
                                  static_argnums=(4, 5, 6, 7))
        self._decode_loop_fn = jax.jit(decode_loop, donate_argnums=(2,),
                                       static_argnums=(5, 6, 7, 8, 9, 10, 11))

    # ------------------------------------------------------- paged serving
    # Slot-level primitives for the continuous-batching serving layer
    # (deepspeed_tpu/serving/): a fixed pool of KV pages shared by all
    # live sequences through a page table. Both primitives have a SINGLE
    # jit signature — shapes are fixed by (num_slots, chunk, num_pages,
    # page_size, max_pages) config constants, never by request churn —
    # so the serving loop never recompiles.

    def _paged_module(self):
        mod = self._cache_module()
        if mod is None:
            raise ValueError(
                "paged serving needs the KV-cache model contract: "
                "init_kv_cache and init_paged_kv_cache in the model's "
                f"module (ops/attention/kv_cache.py); "
                f"{type(self.module).__module__} has neither")
        return mod

    @property
    def slot_state(self):
        """What the model keeps per SLOT beside the page pool, in its
        module's words — "recurrent state" (conv/SSM, ops/ssm/state.py),
        "a window ring" (ops/attention/window.py) — or None."""
        return getattr(self.module, "slot_state", None)

    def slot_state_refusal(self, feature):
        """THE rule for a model that keeps per-slot state: None where
        ``feature`` (a key of ``SLOT_STATE_REFUSALS``) can serve this
        engine's model, else the reason it cannot — one sentence naming
        the model and what it keeps, for ``health()`` or an error."""
        if not self.slot_state:
            return None
        return (f"{type(self.module).__name__} keeps {self.slot_state} per "
                f"slot, which {SLOT_STATE_REFUSALS[feature]}")

    def refuse_slot_state(self, feature):
        """Raise where :meth:`slot_state_refusal` has a reason -- and
        for a hand-off of LATENT pages.  A latent page IS a page (prefix
        cache, verify and preemption run over it as over K/V pages, and
        ``slot_state_refusal`` refuses nothing), but the hand-off
        transport frames ``[n, page_size, kv_heads, d]`` K/V leaves
        under the one pool sharding, and a latent layer's leaf has no
        head dim and rides beside routing counters: shipping it is not
        built."""
        why = self.slot_state_refusal(feature)
        if why is not None:
            raise ValueError(f"{feature} cannot serve this model: {why}")
        if feature == "handoff" and self.latent_cache:
            raise ValueError(
                "handoff cannot serve this model: "
                f"{type(self.module).__name__} keeps latent pages (one "
                "vector a token a layer, no head dim), which the page-"
                "chain transport does not frame yet")

    def init_paged_cache(self, num_pages, page_size, kv_dtype=None,
                         num_slots=None):
        """Device-resident per-layer K/V page pools, committed to the
        serving pool sharding (kv_heads over ``model``, page ids
        global). The page table, lengths and active mask are host-owned
        (the scheduler passes them per call as small traced inputs).
        Built INSIDE a jit so the pools carry the same committed
        sharding as the pools the serving primitives return — otherwise
        the first prefill/decode call compiles a second signature just
        for the uncommitted zeros.

        ``kv_dtype`` overrides the engine's configured kv_cache_dtype
        for THIS pool (the serving autotuner varies the knob per trial
        scheduler without rebuilding engines): a float name from
        ``DTYPES`` or a quantized name ("int8"/"fp8") — quantized pools
        add parallel f32 scale leaves, all four under the one pool-axis
        sharding (the scale leaf keeps rank 4, trailing dim 1, exactly
        so the single NamedSharding broadcasts)."""
        from deepspeed_tpu.ops.quant.kv import (KV_QUANT_DTYPES,
                                                kv_storage_dtype)
        mod = self._paged_module()
        cfg = self.module.cfg
        dt = self.kv_dtype if kv_dtype is None else kv_dtype
        if isinstance(dt, str):
            if dt in DTYPES:
                dt = DTYPES[dt]
            elif dt in KV_QUANT_DTYPES:
                kv_storage_dtype(dt)   # fp8 runtime gate
            else:
                # a raw CLI path (worker --kv-dtype) can reach here
                # without the config-level alias normalization: fail
                # with the crisp message, not a jnp.zeros TypeError
                # from inside the pool-init jit
                raise ValueError(
                    f"unsupported kv_dtype {dt!r}; pick one of "
                    f"{sorted(DTYPES) + sorted(KV_QUANT_DTYPES)}")
        # one-shot kernel-eligibility report at pool construction (the
        # serving "constructor" moment): which paged-attention path
        # will run, how it dispatches, and why — an accidental
        # reference fallback is a logged fact plus a health() field,
        # never a silent slowdown.  A page size that is the ONLY
        # blocker warns loudly by name (the old silent `page_size %
        # 128` gate).
        dec = self.paged_kernel_decision(page_size=page_size)
        if not getattr(self, "_paged_kernel_logged", False):
            self._paged_kernel_logged = True
            for what, d in (("decode", dec),
                            ("prefill/verify", dec["multi_token"])):
                via = f" via {d['dispatch']}" if d.get("dispatch") else ""
                log_dist(f"paged attention path, {what}: {d['path']}{via}"
                         f" — {d['reason']}", ranks=[0])
        if dec.get("blocker") == "page_size":
            import warnings
            warnings.warn(
                f"page_size={page_size} keeps the paged Pallas kernel "
                "OFF (pages must tile the 128-lane TPU layout): decode "
                "runs the gather reference path — use page_size 128 or "
                "256 for kernel-speed paged attention", stacklevel=2)
        if not self.slot_state and not self.latent_cache:
            build = functools.partial(mod.init_paged_kv_cache, cfg,
                                      num_pages, page_size, dtype=dt)
        else:
            # the family sizes its per-slot state by the slot count, or
            # its pools hold more than one kind of leaf (a latent leaf
            # has no head dim; routing counters ride beside it): one
            # sharding a leaf, by the leaf's name (serving/sharding.py)
            slots = {"num_slots": num_slots} if self.slot_state else {}
            build = functools.partial(mod.init_paged_kv_cache, cfg,
                                      num_pages, page_size, dtype=dt,
                                      **slots)
            struct = jax.eval_shape(build)
            if jax.tree.structure(struct) != jax.tree.structure(
                    getattr(self, "_pool_struct", None)):
                self._pool_struct = struct
                self._drop_serving_fns()
        pool_sh = self._pool_shardings(num_slots)
        with dist.mesh_scope(self.mesh):
            return jax.jit(build, out_shardings=pool_sh)()

    def _pool_shardings(self, num_slots=None):
        """What pins the pools pytree: ONE sharding for a family whose
        pools are K/V pages alone, one a leaf where the family keeps
        recurrent state too."""
        shd = self._serving_shardings(num_slots=num_slots)
        struct = getattr(self, "_pool_struct", None)
        return shd.pool if struct is None else shd.pool_tree(struct)

    def _kv_dtype_of(self, kv_dtype=None):
        """``kv_dtype`` (default: the engine's) as a jnp dtype where it
        names a float one; a quantized kv-dtype name stays a name."""
        dt = self.kv_dtype if kv_dtype is None else kv_dtype
        return DTYPES[dt] if isinstance(dt, str) and dt in DTYPES else dt

    def state_bytes_per_slot(self, kv_dtype=None):
        """Exact bytes of per-slot state (recurrent state, window
        rings) ONE slot costs across all layers (0 for a model without
        any) — beside ``kv_page_bytes``, the second unit the capacity
        arithmetic bills in."""
        if not self.slot_state:
            return 0
        dt = self._kv_dtype_of(kv_dtype)
        return self._paged_module().state_bytes_per_slot(self.module.cfg,
                                                         dt)

    def window_ring(self, kv_dtype=None):
        """(the window a sliding-window layer's ring holds, the bytes of
        ring ONE slot costs across all such layers); (0, 0) for a model
        without one.  Its module exports ``window_ring(cfg, dtype)``."""
        mod = self._cache_module()
        if mod is None or not hasattr(mod, "window_ring"):
            return 0, 0
        dt = self._kv_dtype_of(kv_dtype)
        return mod.window_ring(self.module.cfg, dt)

    def latent_bytes_per_token(self, kv_dtype=None):
        """(published, stored) bytes ONE token costs over all layers of
        a latent page pool: the latent vector's own width, and the width
        the pool stores it at (``latent_stored_dim``'s padding
        included).  Its module exports ``latent_bytes_per_token(cfg,
        dtype)``; (0, 0) for a model without a latent cache."""
        if not self.latent_cache:
            return 0, 0
        return self._paged_module().latent_bytes_per_token(
            self.module.cfg, self._kv_dtype_of(kv_dtype))

    def routing_counters(self, pools):
        """The routed layers' counters riding the pools (uint32, mod
        2**32; ``moe/held_experts.routing_stats`` summed over layers and
        dispatches), or None for a model that routes nothing."""
        mod = self._cache_module()
        if mod is None or not hasattr(mod, "routing_counters"):
            return None
        return mod.routing_counters(pools)

    def kv_page_bytes(self, page_size, kv_dtype=None):
        """Exact bytes ONE paged-KV page costs across all layers (K+V
        payload + the f32 scale rows of a quantized pool) — the unit
        the capacity ledgers and the autotuner's feasibility arithmetic
        bill in.  Agrees with the allocated leaves' nbytes to the byte
        (pinned by tests/unit/test_kv_quant.py).  A family whose pages
        are not ``head_dim`` wide for K and V alike exports its own
        ``kv_page_bytes(cfg, page_size, dtype)``."""
        from deepspeed_tpu.ops.quant import kv as kvq
        cfg = self.module.cfg
        dt = self._kv_dtype_of(kv_dtype)
        mod = self._cache_module()
        if hasattr(mod, "kv_page_bytes"):
            return mod.kv_page_bytes(cfg, page_size, dt)
        heads, kv_heads = self._model_head_counts()
        return kvq.kv_page_bytes(getattr(cfg, "num_kv_layers",
                                         cfg.num_layers), kv_heads or heads,
                                 cfg.head_dim, page_size, dt)

    def _build_serving_fns(self):
        from deepspeed_tpu.ops.attention import kv_cache
        module = self.module
        materialize = self._materialize

        # every serving program names its step through a kv_cache
        # constructor, built INSIDE the traced closure (mode and the
        # sequence-parallel plan are static).  The multi-tenant LoRA
        # side input ``adapters=None`` is a LEAFLESS pytree, so
        # base-only traffic keeps the exact pre-tenancy signature and
        # trace; a stacked adapter pack adds one signature per rank
        # bucket (shapes), never per adapter (ids/weights are traced)
        def prefill(params, ids, slot, n_valid, page_table, lengths, pools,
                    adapters, seq_parallel=None):
            logits, step = module.apply(
                {"params": materialize(params)}, ids,
                cache=kv_cache.prefill_step(
                    pools["layers"], page_table, lengths, slot, n_valid,
                    adapters=adapters, seq_parallel=seq_parallel))
            # the model already reduced each row to its chunk's boundary
            # position (the only one a scheduler ever samples from)
            with jax.named_scope("head"):
                rows = logits[:, 0]
            return rows, step.pools

        seq_plan = self.seq_parallel_plan()

        def prefill_sp(params, ids, slot, n_valid, page_table, lengths,
                       pools):
            # sequence-parallel twin of prefill: identical signature and
            # paged landing, but the chunk's attention runs distributed
            # over the sequence axis.  ids arrive sequence-sharded on
            # dim 1 (the staging in prefill_sequence_parallel), which is
            # what makes GSPMD shard the whole per-token pipeline and
            # gather the KV scatter over the axis
            return prefill(params, ids, slot, n_valid, page_table, lengths,
                           pools, None, (seq_plan.axis, seq_plan.impl))

        def decode(params, toks, active, page_table, lengths, pools, rng,
                   do_sample, temperature, top_k, top_p):
            logits, step = module.apply(
                {"params": materialize(params)}, toks[:, None],
                cache=kv_cache.decode_step(pools["layers"], page_table,
                                           lengths, active))
            nxt = _sample_tokens(logits[:, 0], rng, do_sample, temperature,
                                 top_k, top_p)
            return nxt.astype(jnp.int32), step.pools

        def decode_multi(params, tok, active, page_table, lengths, pools,
                         emitted, budgets, eos_ids, rng, adapters, horizon,
                         do_sample, temperature, top_k, top_p):
            """``horizon`` fused decode steps as ONE dispatch (lax.scan):
            token feedback, the active mask, per-slot lengths and EOS /
            budget freezing all stay on device — the host sees one token
            block per horizon instead of one round-trip per token (the
            continuous-batching counterpart of generate()'s
            _decode_loop_fn).

            Per-slot freeze rules, matching the scheduler's host logic
            exactly so fused output is token-identical to the single-step
            path: a slot freezes after sampling ``eos_ids[slot]`` (-1 =
            no eos) or once its cumulative ``emitted`` count reaches
            ``budgets[slot]`` (= remaining_new at the chain's start;
            ``emitted`` is a carry so chained dispatches continue the
            count). Frozen slots write no K/V, advance no length, and
            emit ``valid=False`` rows."""
            def body(carry, i):
                tok, active, lengths, emitted, layers = carry
                # adapter factors are scan CONSTANTS (closure capture of
                # the traced outer arg), never carries — each step
                # re-gathers by the same per-slot ids
                logits, cache = module.apply(
                    {"params": materialize(params)}, tok[:, None],
                    cache=kv_cache.decode_step(layers, page_table, lengths,
                                               active, adapters=adapters))
                nxt = _sample_tokens(logits[:, 0],
                                     jax.random.fold_in(rng, i), do_sample,
                                     temperature, top_k, top_p)
                nxt = jnp.where(active, nxt.astype(jnp.int32), tok)
                emitted = emitted + active.astype(jnp.int32)
                new_active = active & (nxt != eos_ids) & (emitted < budgets)
                return (nxt, new_active, cache.lengths, emitted,
                        cache.layers), (nxt, active)
            with jax.named_scope("horizon"):
                (tok, active, lengths, emitted, layers), (toks, valid) = \
                    jax.lax.scan(body,
                                 (tok, active, lengths, emitted,
                                  pools["layers"]),
                                 jnp.arange(horizon))
            with jax.named_scope("horizon"):
                toks, valid = toks.T, valid.T
            return (toks, valid, tok, active, lengths, emitted,
                    {"layers": layers})

        def verify_multi(params, tok, drafts, widths, active, page_table,
                         lengths, pools, emitted, budgets, eos_ids,
                         adapters):
            """Teacher-forced speculative verification: score K drafted
            tokens per slot in ONE forward over the paged cache (the
            draft/verify counterpart of ``decode_multi``'s scan).

            The input row is ``[tok, d_1 .. d_K]`` (K+1 columns): column
            j's logits are the target model's prediction for the
            (j+1)-th new token, so the longest prefix of drafts matching
            the greedy argmax is accepted and the first non-matching
            argmax is emitted as the bonus/correction token — by
            construction exactly the token sequential greedy decode
            would have produced, so acceptance only changes SPEED, never
            output.  K/V is written for all ``widths[s]+1`` columns;
            ``lengths_end`` rewinds to count only emitted tokens (the
            host mirrors with ``PagedKVManager.truncate_slot``) and the
            stale tail is overwritten before any later gather can read
            it.  EOS / budget freezing replays ``decode_multi``'s rules
            over the emitted stream so the carries stay
            loop-compatible."""
            slots, K = drafts.shape
            x = jnp.concatenate([tok[:, None], drafts], axis=1)
            cols = jnp.where(active, widths + 1, 0)
            logits, step = module.apply(
                {"params": materialize(params)}, x,
                cache=kv_cache.verify_step(pools["layers"], page_table,
                                           lengths, cols, adapters=adapters))
            with jax.named_scope("sample"):
                # the greedy contract: fp32 argmax, ties to the lowest id
                g = jnp.argmax(logits.astype(jnp.float32),
                               axis=-1).astype(jnp.int32)       # [slots, K+1]
                jK = jnp.arange(K)
                ok = (drafts == g[:, :K]) & (jK[None, :] < widths[:, None])
                a = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
                bonus = jnp.take_along_axis(g, a[:, None], axis=1)
                jW = jnp.arange(K + 1)
                drafts_pad = jnp.concatenate(
                    [drafts, jnp.zeros((slots, 1), jnp.int32)], axis=1)
                # emitted stream: accepted drafts then the bonus token
                # (positions past it are frozen padding, masked by `valid`)
                out_toks = jnp.where(jW[None, :] < a[:, None], drafts_pad,
                                     bonus)
                nominal = a + 1
                is_eos = (out_toks == eos_ids[:, None]) & \
                    (eos_ids[:, None] >= 0)
                has_eos = jnp.any(is_eos, axis=1)
                n_eos = jnp.where(has_eos, jnp.argmax(is_eos, axis=1) + 1,
                                  K + 2)
                n = jnp.minimum(jnp.minimum(nominal, n_eos),
                                jnp.maximum(budgets - emitted, 0))
                n = jnp.where(active, n, 0)
                valid = jW[None, :] < n[:, None]
                emitted_end = emitted + n
                last = jnp.take_along_axis(
                    out_toks, jnp.maximum(n - 1, 0)[:, None], axis=1)[:, 0]
                tok_end = jnp.where(n > 0, last, tok)
                emitted_eos = has_eos & (n_eos <= n)
                active_end = active & ~emitted_eos & (emitted_end < budgets)
                lengths_end = lengths + n
                accepted = jnp.minimum(a, n)
            return (out_toks, valid, tok_end, active_end, lengths_end,
                    emitted_end, accepted, step.pools)

        def decode_multi_policy(params, tok, active, page_table, lengths,
                                pools, emitted, budgets, eos_ids, keys,
                                tok_base, temps, top_ks, top_ps, rep_pens,
                                pres_pens, freq_pens, counts, mask,
                                horizon):
            """``decode_multi`` with the per-slot decoding-policy
            pipeline (serving/sampling/pipeline.py) in place of the
            static-args sampler.  EVERY policy knob is a traced
            per-slot array — temperature, top-k/p, the three history
            penalties over the ``counts`` token table, the grammar
            ``mask``, and a per-request PRNG key + absolute token base
            — so a mixed greedy/sampled/penalized/constrained batch is
            ONE compiled signature per horizon bucket and param churn
            never recompiles.  Token ``tok_base[s] + emitted[s]`` keys
            the slot's fold_in stream: batching-independent and
            replayable across preemption/failover.  Freeze rules are
            decode_multi's exactly; ``counts`` rides the carry so
            penalties see tokens sampled earlier in the same chain."""
            slots = tok.shape[0]

            def body(carry, i):
                tok, active, lengths, emitted, counts, layers = carry
                logits, cache = module.apply(
                    {"params": materialize(params)}, tok[:, None],
                    cache=kv_cache.decode_step(layers, page_table, lengths,
                                               active))
                x = policy_pipeline.process_logits(
                    logits[:, 0], counts, mask, temps, top_ks, top_ps,
                    rep_pens, pres_pens, freq_pens)
                nxt = policy_pipeline.sample_processed(
                    x, keys, tok_base + emitted, temps).astype(jnp.int32)
                nxt = jnp.where(active, nxt, tok)
                counts = counts.at[jnp.arange(slots), nxt].add(
                    active.astype(jnp.int32))
                emitted = emitted + active.astype(jnp.int32)
                new_active = active & (nxt != eos_ids) & (emitted < budgets)
                return (nxt, new_active, cache.lengths, emitted,
                        counts, cache.layers), (nxt, active)
            with jax.named_scope("horizon"):
                (tok, active, lengths, emitted, counts, layers), \
                    (toks, valid) = jax.lax.scan(
                        body, (tok, active, lengths, emitted, counts,
                               pools["layers"]), jnp.arange(horizon))
            with jax.named_scope("horizon"):
                toks, valid = toks.T, valid.T
            return (toks, valid, tok, active, lengths, emitted,
                    counts, {"layers": layers})

        def verify_multi_policy(params, tok, drafts, widths, active,
                                page_table, lengths, pools, emitted,
                                budgets, eos_ids, keys, tok_base, temps,
                                top_ks, top_ps, rep_pens, pres_pens,
                                freq_pens, counts, mask):
            """Lossless speculative verification under the decoding
            policy: one teacher-forced forward (identical to
            ``verify_multi``), then a scan over the K+1 logit columns
            applying leftover-probability rejection sampling per slot.
            Our drafters propose point-mass tokens (no draft probs), so
            the accept rule collapses to ``u < p_target(draft)`` and a
            rejection resamples the residual (p_target with the draft
            zeroed, renormalized) — by construction the emitted stream
            is distributed EXACTLY as sequential ``decode_multi_policy``
            (frequency oracle pins this).  Greedy rows (temp == 0) keep
            the legacy token-exact rule: accept iff fp32 argmax ==
            draft, the correction token IS the argmax.  Column ``j``
            draws from ``fold_in(key, tok_base + j)`` sub-streams;
            counts carry accepted drafts so penalties stay causal
            within the round.  Assembly (eos/budget clamping, rewound
            lengths, carries) matches ``verify_multi`` line for line."""
            slots, K = drafts.shape
            x_in = jnp.concatenate([tok[:, None], drafts], axis=1)
            cols = jnp.where(active, widths + 1, 0)
            logits, step = module.apply(
                {"params": materialize(params)}, x_in,
                cache=kv_cache.verify_step(pools["layers"], page_table,
                                           lengths, cols))
            with jax.named_scope("sample"):
                drafts_pad = jnp.concatenate(
                    [drafts, jnp.zeros((slots, 1), jnp.int32)], axis=1)

                def col(carry, j):
                    counts_c, accepting, acc, bonus = carry
                    lg = policy_pipeline.process_logits(
                        logits[:, j], counts_c, mask, temps, top_ks, top_ps,
                        rep_pens, pres_pens, freq_pens)
                    d = drafts_pad[:, j]
                    is_draft = (j < widths) & accepting
                    is_bonus = (j == widths) & accepting
                    accept_col, fallback = policy_pipeline.accept_or_resample(
                        lg, d, keys, tok_base + j, temps)
                    bonus_col = policy_pipeline.bonus_sample(
                        lg, keys, tok_base + j, temps)
                    draft_accept = is_draft & accept_col
                    reject_now = is_draft & ~accept_col
                    bonus = jnp.where(reject_now, fallback,
                                      jnp.where(is_bonus, bonus_col, bonus))
                    counts_c = counts_c.at[jnp.arange(slots), d].add(
                        draft_accept.astype(jnp.int32))
                    acc = acc + draft_accept.astype(jnp.int32)
                    return (counts_c, draft_accept, acc, bonus), None
                (counts, _, a, bonus), _ = jax.lax.scan(
                    col, (counts, active, jnp.zeros(slots, jnp.int32),
                          jnp.zeros(slots, jnp.int32)), jnp.arange(K + 1))
                jW = jnp.arange(K + 1)
                out_toks = jnp.where(jW[None, :] < a[:, None], drafts_pad,
                                     bonus[:, None])
                nominal = a + 1
                is_eos = (out_toks == eos_ids[:, None]) & \
                    (eos_ids[:, None] >= 0)
                has_eos = jnp.any(is_eos, axis=1)
                n_eos = jnp.where(has_eos, jnp.argmax(is_eos, axis=1) + 1,
                                  K + 2)
                n = jnp.minimum(jnp.minimum(nominal, n_eos),
                                jnp.maximum(budgets - emitted, 0))
                n = jnp.where(active, n, 0)
                valid = jW[None, :] < n[:, None]
                emitted_end = emitted + n
                last = jnp.take_along_axis(
                    out_toks, jnp.maximum(n - 1, 0)[:, None], axis=1)[:, 0]
                tok_end = jnp.where(n > 0, last, tok)
                emitted_eos = has_eos & (n_eos <= n)
                active_end = active & ~emitted_eos & (emitted_end < budgets)
                lengths_end = lengths + n
                accepted = jnp.minimum(a, n)
            return (out_toks, valid, tok_end, active_end, lengths_end,
                    emitted_end, accepted, counts, step.pools)

        # every in/out array family gets its serving sharding
        # (serving/sharding.py): pools shard kv_heads over `model`,
        # slot carries / token blocks / the page table shard slots over
        # `data`. out_shardings stay PINNED so the donated round-trip
        # keeps ONE jit signature per bucket: an inferred sharding that
        # differed from init_paged_cache's (or from the staged host
        # inputs') would compile a second copy on the first feedback
        # call — same invariant as the replicated PR-1 design, now per
        # axis family
        shd = self._serving_shardings()
        slot, block, pool = shd.slot, shd.block, self._pool_shardings()
        self._paged_prefill_fn = jax.jit(prefill, donate_argnums=(6,),
                                         out_shardings=(shd.logits, pool))
        # the sequence-parallel twin only exists when the mesh has a
        # usable sequence axis (resolve_sequence_plan); its pools /
        # logits round-trip is pinned identically, so landed pages and
        # boundary logits are drop-in for everything downstream
        self._paged_prefill_sp_fn = jax.jit(
            prefill_sp, donate_argnums=(6,),
            out_shardings=(shd.logits, pool)) if seq_plan.usable else None
        self._paged_decode_fn = jax.jit(decode, donate_argnums=(5,),
                                        static_argnums=(7, 8, 9, 10),
                                        out_shardings=(slot, pool))
        # one compiled signature per (horizon, sampling) combo — the
        # scheduler quantizes horizons to a small bucket set so the
        # compile count stays bounded across slot churn
        self._paged_decode_multi_fn = jax.jit(
            decode_multi, donate_argnums=(5,),
            static_argnums=(11, 12, 13, 14, 15),
            out_shardings=(block, block, slot, slot, slot, slot, pool))
        # K is baked into the drafts shape, so the compile count is
        # bounded by the scheduler's spec-K bucket set (greedy-only: no
        # sampling statics)
        self._paged_verify_fn = jax.jit(
            verify_multi, donate_argnums=(7,),
            out_shardings=(block, block, slot, slot, slot, slot, slot,
                           pool))
        # policy twins: horizon is the ONLY static — every sampling /
        # penalty / grammar knob is a traced per-slot array, so the
        # compile count stays bounded by the horizon/K bucket sets
        # across arbitrary per-request param churn.  counts donates and
        # round-trips (the in-chain penalty carry); mask is read-only.
        self._paged_decode_policy_fn = jax.jit(
            decode_multi_policy, donate_argnums=(5, 17),
            static_argnums=(19,),
            out_shardings=(block, block, slot, slot, slot, slot, block,
                           pool))
        self._paged_verify_policy_fn = jax.jit(
            verify_multi_policy, donate_argnums=(7, 19),
            out_shardings=(block, block, slot, slot, slot, slot, slot,
                           block, pool))

    def copy_page(self, pools, src_page, dst_page):
        """Copy ONE KV page across every layer's pool (the prefix
        cache's copy-on-write primitive: a partially matched cached page
        is duplicated into a fresh private page before the owning slot
        may append to it).  Page ids are traced scalars, so churn in
        which pages get copied never adds a jit signature — ONE compile
        per serving config, like the other paged primitives."""
        self.refuse_slot_state("prefix_cache")
        if getattr(self, "_copy_page_fn", None) is None:
            # a page copy moves one index of the GLOBAL page dim; the
            # kv-head shards copy in place on their own devices (no
            # cross-device traffic), so the pool sharding is pinned
            # through like every other primitive.  Copying EVERY leaf of
            # the layer dict (not just k/v payload) is what keeps a
            # quantized pool's per-row scales welded to their page: a
            # COW copy that moved payload without scales would dequantize
            # the private copy with the ORIGINAL page's scales forever
            # (a leaf that is no page array -- the routing counters
            # beside a latent leaf -- passes through)
            def copy(pools, src, dst):
                return {"layers": [
                    {name: arr.at[dst].set(arr[src])
                     if is_page_leaf(name) else arr
                     for name, arr in L.items()}
                    for L in pools["layers"]]}
            pool_sh = self._pool_shardings()

            self._copy_page_fn = jax.jit(copy, donate_argnums=(0,),
                                         out_shardings=pool_sh)
        args = (pools, jnp.int32(src_page), jnp.int32(dst_page))
        if self._comm_capture is not None:
            self._capture_comm_sig("copy_page", "copy_page",
                                   "_copy_page_fn", args)
        with dist.mesh_scope(self.mesh):
            return self._dispatch("copy_page", self._copy_page_fn, *args)

    def serving_page_copy_compile_count(self):
        """Compiled signatures behind copy_page (stays <= 1 per serving
        config: cache hits/misses must never grow the compile set).
        Reads ``tracing.jit_cache_size`` — the ONE compile-count
        definition shared with the train engine, the goodput ledger and
        the recompile watchdog."""
        return jit_cache_size(getattr(self, "_copy_page_fn", None))

    def export_page_chain(self, pools, page_ids):
        """Gather a page chain out of the paged pool as a transferable
        payload: one ``[n, page_size, kv_heads, d]`` leaf per pool leaf
        per layer, where ``n == len(page_ids)``.  The disaggregated
        handoff transport's READ half — the payload either rides
        ``jax.device_put`` to a sibling pool in-process or gets staged
        to host and framed onto a cross-process KV sidecar fd.

        Gathering EVERY leaf of each layer dict (not just k/v payload)
        is what keeps a quantized pool's per-row scales welded to their
        page across a transfer: a chain that moved int8/fp8 payload
        without its scale rows would dequantize on the destination with
        whatever stale scales its fresh pages held.  Same rule as
        ``copy_page``, for the same reason.

        ``page_ids`` must be padded to a power-of-two chunk bucket
        (``transport.chunk_bucket``) — pad with any in-range id (0 is
        conventional; the extra gathered page is trimmed on host).  Ids
        are a traced operand, so churn in WHICH pages transfer never
        adds a signature: exactly one compile per bucket length."""
        self.refuse_slot_state("handoff")
        if getattr(self, "_chain_export_fn", None) is None:
            def export(pools, ids):
                return [{name: arr[ids] for name, arr in L.items()}
                        for L in pools["layers"]]
            pool_sh = self._serving_shardings().pool
            # payload leaves keep the pool's layout ([page-dim, ps,
            # kvh, d] with kv-heads model-sharded), so the pool
            # sharding pins through — device_put to the destination's
            # identical NamedSharding is then resharding-free
            self._chain_export_fn = jax.jit(export, out_shardings=pool_sh)
        args = (pools, jnp.asarray(page_ids, jnp.int32))
        with dist.mesh_scope(self.mesh):
            return self._dispatch("chain_export", self._chain_export_fn,
                                  *args)

    def import_page_chain(self, pools, payload, page_ids):
        """Scatter an exported chain payload into this pool at
        ``page_ids`` (the destination's freshly allocated pages) and
        return the updated pools — the transport's WRITE half, the
        functional-update twin of ``export_page_chain``.

        ``page_ids`` must be padded to the payload's chunk bucket with
        ``num_pages`` (one past the last page): ``mode="drop"`` masks
        the padded writes, the same out-of-range discipline every paged
        write primitive rides.  Donates the pools like every other
        pool-mutating primitive; one compile per bucket length."""
        self.refuse_slot_state("handoff")
        if getattr(self, "_chain_import_fn", None) is None:
            def imp(pools, payload, ids):
                return {"layers": [
                    {name: arr.at[ids].set(pl[name], mode="drop")
                     for name, arr in L.items()}
                    for L, pl in zip(pools["layers"], payload)]}
            pool_sh = self._serving_shardings().pool
            self._chain_import_fn = jax.jit(imp, donate_argnums=(0,),
                                            out_shardings=pool_sh)
        args = (pools, payload, jnp.asarray(page_ids, jnp.int32))
        with dist.mesh_scope(self.mesh):
            return self._dispatch("chain_import", self._chain_import_fn,
                                  *args)

    def serving_chain_export_compile_count(self):
        """Compiled signatures behind export_page_chain — one per
        power-of-two chunk bucket a transfer ever used, NOT per chain
        length (the bucket pins assert this stays flat across handoff
        churn)."""
        return jit_cache_size(getattr(self, "_chain_export_fn", None))

    def serving_chain_import_compile_count(self):
        """Compiled signatures behind import_page_chain — one per
        chunk bucket, the mirror of the export pin."""
        return jit_cache_size(getattr(self, "_chain_import_fn", None))

    # -------------------------------------- comm/compile observability
    def set_compile_watchdog(self, watchdog):
        """Install a :class:`tracing.CompileWatchdog` (None removes
        it): every serving dispatch whose jit signature cache grows
        records a ``compile`` span, and steady-state growth fires the
        watchdog's recompile detection.  Pure host bookkeeping around
        the dispatch — it never changes what compiles."""
        self._compile_watchdog = watchdog

    def _dispatch(self, name, fn, *args, detail=None):
        """Run one serving-primitive dispatch, feeding the compile
        watchdog when the callable's signature cache grew across the
        call (jit compiles synchronously at dispatch, so this call's
        wall time IS compile + dispatch)."""
        wd = self._compile_watchdog
        if wd is not None:
            n0 = jit_cache_size(fn)
            t0 = time.monotonic()
        with annotation("ds.engine.launch", program=name):
            out = fn(*args)
        if wd is not None:
            n1 = jit_cache_size(fn)
            if n1 > n0:
                wd.on_compile(name, n1 - n0, t0, time.monotonic(),
                              detail=detail)
        return out

    def enable_comm_telemetry(self, enabled=True):
        """Arm (or disarm) HLO comm-ledger capture: each serving
        primitive records the arg specs (shapes/dtypes/shardings +
        statics) of every distinct signature it dispatches, so
        :meth:`comm_ledger` can later re-lower and statically count the
        collective bytes of exactly the executables serving runs.  The
        capture itself is a dict lookup per dispatch; the analysis
        compile happens only inside :meth:`comm_ledger`."""
        if enabled:
            # re-arming keeps both the capture and the analyzed-ledger
            # cache: signatures are (name, label)-keyed and stable, so
            # a fleet of schedulers sharing one engine (each __init__
            # re-arms) must not force a re-compile sweep per replica
            if self._comm_capture is None:
                self._comm_capture = {}
        else:
            self._comm_capture = None
            self._comm_ledger_cache = {}

    def _capture_comm_sig(self, name, label, fn_attr, args, statics=()):
        cap = self._comm_capture
        if cap is None:
            return
        # geometry rides the ARRAY arg shapes (slots/pages/chunk): two
        # schedulers sharing one engine with different geometry are
        # distinct executables and must ledger separately even under
        # the same display label
        geom = tuple(np.shape(a) for a in args
                     if isinstance(a, (np.ndarray, jax.Array)))
        if (name, label, geom) in cap:
            return
        # ShapeDtypeStructs with committed shardings: enough for
        # .lower() to reproduce the exact partitioned executable
        # without holding (donated!) buffers alive.  An UNCOMMITTED
        # single-device array (the rng key from jax.random.split) is
        # normalized to replicated-on-mesh — that is what jit does
        # with it at real dispatch, and a literal single-device spec
        # would make the analysis lowering reject the mesh-sharded
        # co-arguments
        mesh_devs = frozenset(
            d.id for d in np.asarray(self.mesh.devices).flat)

        def spec(x):
            sh = getattr(x, "sharding", None)
            if sh is not None:
                try:
                    if frozenset(d.id for d in sh.device_set) != \
                            mesh_devs:
                        sh = NamedSharding(self.mesh, P())
                except Exception:
                    sh = None
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                        sharding=sh)

        cap[(name, label, geom)] = (fn_attr, jax.tree.map(spec, args),
                                    statics)

    def comm_ledger(self, refresh=False):
        """Static HLO comm ledger per captured serving signature
        (``profiling/comm_ledger.py``): ``{label: ledger}`` where the
        label carries the primitive and its statics (e.g.
        ``decode_multi[h=8]``).  First call per signature pays one
        analysis re-compile (lower -> compile -> parse); results are
        cached until ``refresh=True`` or :meth:`enable_comm_telemetry`
        is toggled.  Empty dict when capture is off or nothing
        dispatched yet."""
        if self._comm_capture is None:
            return {}
        from deepspeed_tpu.profiling import comm_ledger as _cl
        out = {}
        for key, (fn_attr, specs, statics) in \
                list(self._comm_capture.items()):
            name, label = key[0], key[1]
            # two geometries under one display label (engine shared by
            # differently-sized schedulers) stay distinct entries
            disp = label
            n = 2
            while disp in out:
                disp = f"{label}@{n}"
                n += 1
            cached = self._comm_ledger_cache.get(key)
            if cached is not None and not refresh:
                out[disp] = cached
                continue
            fn = getattr(self, fn_attr, None)
            if fn is None:
                # the serving fns were rebuilt (slot-family resharding)
                self._build_serving_fns()
                fn = getattr(self, fn_attr, None)
                if fn is None:
                    continue
            with self._serving_scope():
                led = _cl.ledger_for(fn, *specs, *statics,
                                     mesh=self.mesh)
            self._comm_ledger_cache[key] = led
            out[disp] = led
        return out

    def prefill_into_slots(self, ids_chunk, slot, n_valid, page_table,
                           lengths, pools, adapter_ids=None, adapters=None):
        """One prefill chunk for each of ``rows`` slots in ONE dispatch:
        row r writes the K/V of ``ids_chunk[r, :n_valid[r]]`` through
        slot ``slot[r]``'s page table and the call returns (boundary
        logits [rows, vocab], new pools).  ``ids_chunk`` is [rows,
        chunk] (padded past ``n_valid[r]``), ``slot`` / ``n_valid`` are
        int32 [rows] (a scalar is one row); the pages covering
        positions lengths[slot[r]] .. +n_valid[r] must be allocated.  A
        padding row has ``n_valid == 0`` and any live slot id: it
        writes nothing and its logits row is garbage.  The slots of the
        non-padding rows must be distinct.

        Row r's positions (and rotary offsets) start at
        ``lengths[slot[r]]``, which need not be 0 OR page-aligned: a
        prefix-cache hit seeds it to the cached boundary and prefill
        resumes there — start offsets, slots and valid counts are data,
        never shape, so there is one jit signature per ROW COUNT (the
        scheduler packs rows into a few row buckets)."""
        assert self.params is not None, "set_params/init_params first"
        slot = np.asarray(slot, np.int32).reshape(-1)
        n_valid = np.asarray(n_valid, np.int32).reshape(-1)
        shd = self._serving_shardings(num_slots=int(np.shape(lengths)[0]))
        if getattr(self, "_paged_prefill_fn", None) is None:
            self._build_serving_fns()
        rep, slot_sh, blk = shd.replicated, shd.slot, shd.block
        ids_chunk, slot, n_valid, page_table, lengths = \
            self._stage_host_inputs([
                (ids_chunk, np.int32, rep), (slot, np.int32, rep),
                (n_valid, np.int32, rep), (page_table, np.int32, blk),
                (lengths, np.int32, slot_sh)])
        # multi-tenant LoRA: the stacked factor pack is already device-
        # committed (AdapterStore caches it); only the per-slot ids are
        # per-dispatch host state. None = leafless side input, so base-
        # only traffic keeps the exact pre-tenancy signature.
        ad = None
        if adapters is not None:
            (ids_arr,) = self._stage_host_inputs(
                [(adapter_ids, np.int32, slot_sh)])
            ad = dict(adapters, ids=ids_arr)
        args = (self.params, ids_chunk, slot, n_valid, page_table,
                lengths, pools, ad)
        if self._comm_capture is not None:   # label cost only when armed
            rows, chunk = np.shape(ids_chunk)
            self._capture_comm_sig(
                "prefill", f"prefill[rows={rows},chunk={chunk}]",
                "_paged_prefill_fn", args)
        with self._serving_scope():
            return self._dispatch("prefill", self._paged_prefill_fn,
                                  *args)

    def seq_parallel_plan(self):
        """The resolved sequence-parallel prefill plan for this engine's
        mesh + model (``serving.sharding.resolve_sequence_plan``),
        cached — the scheduler reads it once at construction to decide
        whether a ``seq_parallel_threshold`` can route anywhere, and
        health() surfaces it."""
        if getattr(self, "_seq_plan", None) is None:
            heads, kv_heads = self._model_head_counts()
            self._seq_plan = resolve_sequence_plan(
                self.mesh, self.serving_sharding,
                num_heads=heads or 1, num_kv_heads=kv_heads or 1)
        return self._seq_plan

    def prefill_sequence_parallel(self, ids_chunk, slot, n_valid,
                                  page_table, lengths, pools):
        """Sequence-parallel twin of :meth:`prefill_into_slots` for ONE
        row: same arguments, same ``(boundary logits [1, vocab], new
        pools)`` return, same paged landing — but ``ids_chunk`` ([1,
        chunk]) stages SHARDED over the
        sequence mesh axis, the per-token pipeline (embedding, rotary,
        MLP) runs 1/P-sized per device under GSPMD, and the chunk's
        attention runs through the Ulysses all-to-all (or ring
        ppermute) transport per the resolved plan.  The chunk length
        must be a multiple of the axis size (the scheduler's power-of-
        two chunk buckets >= the axis size guarantee it).  Pages land
        in the standard pool, so decode / prefix-cache donation / COW /
        spec verify / handoff downstream never notice which path
        prefilled them."""
        self.refuse_slot_state("seq_parallel_prefill")
        assert self.params is not None, "set_params/init_params first"
        plan = self.seq_parallel_plan()
        assert plan.usable, \
            f"no usable sequence axis on this mesh: {plan.reason}"
        chunk = int(np.shape(ids_chunk)[1])
        assert chunk % plan.size == 0, \
            (f"chunk length {chunk} must be a multiple of the "
             f"'{plan.axis}' axis size {plan.size}")
        slot = np.asarray(slot, np.int32).reshape(1)
        n_valid = np.asarray(n_valid, np.int32).reshape(1)
        shd = self._serving_shardings(num_slots=int(np.shape(lengths)[0]))
        if getattr(self, "_paged_prefill_sp_fn", None) is None:
            self._build_serving_fns()
        rep, slot_sh, blk = shd.replicated, shd.slot, shd.block
        seq_sh = NamedSharding(self.mesh, P(None, plan.axis))
        ids_chunk, slot, n_valid, page_table, lengths = \
            self._stage_host_inputs([
                (ids_chunk, np.int32, seq_sh), (slot, np.int32, rep),
                (n_valid, np.int32, rep), (page_table, np.int32, blk),
                (lengths, np.int32, slot_sh)])
        args = (self.params, ids_chunk, slot, n_valid, page_table,
                lengths, pools)
        if self._comm_capture is not None:
            self._capture_comm_sig(
                "seq_prefill", f"seq_prefill[chunk={chunk}]",
                "_paged_prefill_sp_fn", args)
        with self._serving_scope():
            return self._dispatch("seq_prefill",
                                  self._paged_prefill_sp_fn, *args)

    def decode_step(self, toks, active, page_table, lengths, pools,
                    do_sample=False, temperature=1.0, top_k=0, top_p=1.0):
        """One continuous-batching decode step over ALL slots: write each
        active slot's token K/V at position lengths[slot], attend through
        the page table, and return (next tokens [slots] i32, new pools).
        Inactive slots pass through untouched (writes dropped)."""
        assert self.params is not None, "set_params/init_params first"
        shd = self._serving_shardings(num_slots=int(np.shape(lengths)[0]))
        if getattr(self, "_paged_decode_fn", None) is None:
            self._build_serving_fns()
        self._rng, rng = jax.random.split(self._rng)
        toks, active, page_table, lengths = self._stage_host_inputs([
            (toks, np.int32, shd.slot), (active, bool, shd.slot),
            (page_table, np.int32, shd.block),
            (lengths, np.int32, shd.slot)])
        args = (self.params, toks, active, page_table, lengths, pools,
                rng)
        statics = (bool(do_sample), float(temperature), int(top_k),
                   float(top_p))
        if self._comm_capture is not None:
            self._capture_comm_sig(
                "decode", "decode" + _sampling_label(*statics),
                "_paged_decode_fn", args, statics)
        with self._serving_scope():
            return self._dispatch("decode", self._paged_decode_fn,
                                  *args, *statics)

    def _stage_host_inputs(self, triples):
        """Move the per-dispatch host arrays to their committed serving
        shardings in ONE batched ``device_put`` (per-array puts cost
        ~0.2 ms each of pure dispatch machinery on the CPU rig — at 7-9
        small arrays per decode/verify round that overhead was rivaling
        the model compute itself).  Each triple is ``(value, dtype,
        sharding)``; slot-indexed arrays stage to the data-axis
        sharding, the page table to the block sharding, scalars to
        replicated.  Device-resident carries from a previous dispatch
        pass through untouched: they are already committed to their
        exact sharding by ``out_shardings``, so barrier and chained
        dispatches share one compiled signature per bucket.

        Host values are COPIED: ``device_put`` may alias a numpy buffer
        (zero-copy on CPU) or read it asynchronously (TPU), and the
        scheduler mutates its live ``lengths``/page-table state right
        after the dispatch returns."""
        with annotation("ds.engine.stage"):
            staged = [x if isinstance(x, jax.Array) and x.dtype == dt
                      else np.array(x, dt) for x, dt, _ in triples]
            return jax.device_put(tuple(staged),
                                  tuple(sh for _, _, sh in triples))

    def decode_multi(self, toks, active, page_table, lengths, pools, *,
                     horizon, budgets, eos_ids, emitted=None,
                     do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                     adapter_ids=None, adapters=None):
        """``horizon`` continuous-batching decode steps as ONE dispatch.

        Returns ``(toks_block [slots, H] i32, valid [slots, H] bool,
        tok_end, active_end, lengths_end, emitted_end, new pools)``.
        ``valid[s, i]`` marks a genuinely sampled token; rows after a
        slot hits its eos id or exhausts ``budgets[slot]`` are frozen
        padding. The ``*_end`` carries are device arrays that can feed
        the next ``decode_multi`` call directly (the overlapped serving
        loop chains horizons without a host round-trip); ``emitted``
        must then be threaded through so budget accounting spans the
        chain. ``toks``/``active``/``lengths`` accept host numpy or the
        previous call's device carries interchangeably."""
        assert self.params is not None, "set_params/init_params first"
        # host inputs get the SAME committed shardings the *_end carries
        # come back with (slot arrays over `data`, table over `data`),
        # so barrier dispatches and chained dispatches share one
        # compiled signature per horizon bucket
        shd = self._serving_shardings(num_slots=int(np.shape(budgets)[0]))
        if getattr(self, "_paged_decode_multi_fn", None) is None:
            self._build_serving_fns()
        self._rng, rng = jax.random.split(self._rng)
        if emitted is None:
            emitted = np.zeros(np.shape(budgets), np.int32)
        slot, blk = shd.slot, shd.block
        toks, active, page_table, lengths, emitted, budgets, eos_ids = \
            self._stage_host_inputs([
                (toks, np.int32, slot), (active, bool, slot),
                (page_table, np.int32, blk), (lengths, np.int32, slot),
                (emitted, np.int32, slot), (budgets, np.int32, slot),
                (eos_ids, np.int32, slot)])
        ad = None
        if adapters is not None:
            (ids_arr,) = self._stage_host_inputs(
                [(adapter_ids, np.int32, slot)])
            ad = dict(adapters, ids=ids_arr)
        args = (self.params, toks, active, page_table, lengths, pools,
                emitted, budgets, eos_ids, rng, ad)
        statics = (int(horizon), bool(do_sample), float(temperature),
                   int(top_k), float(top_p))
        if self._comm_capture is not None:
            self._capture_comm_sig(
                "decode_multi",
                f"decode_multi[h={int(horizon)}]"
                + _sampling_label(*statics[1:]),
                "_paged_decode_multi_fn", args, statics)
        with self._serving_scope():
            return self._dispatch(
                "decode_multi", self._paged_decode_multi_fn,
                *args, *statics,
                detail=None if self._compile_watchdog is None
                else {"horizon": int(horizon)})

    def verify_multi(self, toks, drafts, active, page_table, lengths,
                     pools, *, widths, budgets, eos_ids, emitted=None,
                     adapter_ids=None, adapters=None):
        """Speculative-decode verification: score ``drafts`` [slots, K]
        proposed tokens per slot in ONE teacher-forced dispatch over the
        paged cache, accept the longest greedy-matching prefix plus the
        target model's one bonus/correction token.

        ``widths[s] <= K`` is the real draft count for slot ``s`` (the
        rest of the row is padding); pages covering positions
        ``lengths[s] .. lengths[s] + widths[s]`` must be allocated.
        Greedy-only by design: acceptance compares against the
        ``temperature=0`` argmax contract of ``sample_from_logits``, so
        spec-decode output is token-exact vs ``generate()``.

        Returns ``(toks_block [slots, K+1] i32, valid [slots, K+1]
        bool, tok_end, active_end, lengths_end, emitted_end,
        accepted [slots] i32, new pools)``.  The carries have exactly
        ``decode_multi``'s shapes/meanings — ``lengths_end`` already
        reflects the KV rollback (count of emitted tokens only), so a
        follow-up dispatch can run straight off them; the host mirrors
        the rollback with ``PagedKVManager.truncate_slot``.  One
        compiled signature per K (the scheduler's spec-K bucket set)."""
        self.refuse_slot_state("spec_decode")
        assert self.params is not None, "set_params/init_params first"
        shd = self._serving_shardings(num_slots=int(np.shape(budgets)[0]))
        if getattr(self, "_paged_verify_fn", None) is None:
            self._build_serving_fns()
        if emitted is None:
            emitted = np.zeros(np.shape(budgets), np.int32)
        slot, blk = shd.slot, shd.block
        (toks, drafts, widths, active, page_table, lengths, emitted,
         budgets, eos_ids) = self._stage_host_inputs([
             (toks, np.int32, slot), (drafts, np.int32, blk),
             (widths, np.int32, slot), (active, bool, slot),
             (page_table, np.int32, blk), (lengths, np.int32, slot),
             (emitted, np.int32, slot), (budgets, np.int32, slot),
             (eos_ids, np.int32, slot)])
        ad = None
        if adapters is not None:
            (ids_arr,) = self._stage_host_inputs(
                [(adapter_ids, np.int32, slot)])
            ad = dict(adapters, ids=ids_arr)
        args = (self.params, toks, drafts, widths, active, page_table,
                lengths, pools, emitted, budgets, eos_ids, ad)
        k = int(np.shape(drafts)[1])
        if self._comm_capture is not None:
            self._capture_comm_sig("verify", f"verify[k={k}]",
                                   "_paged_verify_fn", args)
        with self._serving_scope():
            return self._dispatch("verify", self._paged_verify_fn,
                                  *args,
                                  detail=None if self._compile_watchdog
                                  is None else {"k": k})

    def _stage_policy_inputs(self, shd, keys, tok_base, temps, top_ks,
                             top_ps, rep_pens, pres_pens, freq_pens,
                             counts, mask):
        """Stage the per-slot decoding-policy arrays (one batched
        device_put, same committed shardings every dispatch): the raw
        uint32 request keys and every pipeline knob as slot lanes, the
        counts/mask tables slot-major like the page table."""
        slot, blk = shd.slot, shd.block
        return self._stage_host_inputs([
            (keys, np.uint32, blk), (tok_base, np.int32, slot),
            (temps, np.float32, slot), (top_ks, np.int32, slot),
            (top_ps, np.float32, slot), (rep_pens, np.float32, slot),
            (pres_pens, np.float32, slot), (freq_pens, np.float32, slot),
            (counts, np.int32, blk), (mask, bool, blk)])

    def decode_multi_policy(self, toks, active, page_table, lengths,
                            pools, *, horizon, budgets, eos_ids, keys,
                            tok_base, temps, top_ks, top_ps, rep_pens,
                            pres_pens, freq_pens, counts, mask,
                            emitted=None):
        """``decode_multi`` under the per-slot decoding policy.  Same
        carries and return shape plus a ``counts`` carry before the
        pools: ``(toks_block, valid, tok_end, active_end, lengths_end,
        emitted_end, counts_end, pools)``.  All policy knobs are traced
        per-slot arrays (see ``_build_serving_fns``) — ONE compiled
        signature per horizon bucket regardless of the request mix, so
        ``serving_decode_multi_compile_count()`` (which sums the legacy
        and policy caches) stays within the bucket set across sampling-
        param churn.  ``counts``/``mask`` accept host numpy at a
        barrier or the previous call's device carry in a chain."""
        assert self.params is not None, "set_params/init_params first"
        shd = self._serving_shardings(num_slots=int(np.shape(budgets)[0]))
        if getattr(self, "_paged_decode_policy_fn", None) is None:
            self._build_serving_fns()
        if emitted is None:
            emitted = np.zeros(np.shape(budgets), np.int32)
        slot, blk = shd.slot, shd.block
        toks, active, page_table, lengths, emitted, budgets, eos_ids = \
            self._stage_host_inputs([
                (toks, np.int32, slot), (active, bool, slot),
                (page_table, np.int32, blk), (lengths, np.int32, slot),
                (emitted, np.int32, slot), (budgets, np.int32, slot),
                (eos_ids, np.int32, slot)])
        (keys, tok_base, temps, top_ks, top_ps, rep_pens, pres_pens,
         freq_pens, counts, mask) = self._stage_policy_inputs(
             shd, keys, tok_base, temps, top_ks, top_ps, rep_pens,
             pres_pens, freq_pens, counts, mask)
        args = (self.params, toks, active, page_table, lengths, pools,
                emitted, budgets, eos_ids, keys, tok_base, temps,
                top_ks, top_ps, rep_pens, pres_pens, freq_pens, counts,
                mask)
        if self._comm_capture is not None:
            self._capture_comm_sig(
                "decode_multi_policy",
                f"decode_multi_policy[h={int(horizon)}]",
                "_paged_decode_policy_fn", args, (int(horizon),))
        with self._serving_scope():
            return self._dispatch(
                "decode_multi_policy", self._paged_decode_policy_fn,
                *args, int(horizon),
                detail=None if self._compile_watchdog is None
                else {"horizon": int(horizon), "policy": True})

    def verify_multi_policy(self, toks, drafts, active, page_table,
                            lengths, pools, *, widths, budgets, eos_ids,
                            keys, tok_base, temps, top_ks, top_ps,
                            rep_pens, pres_pens, freq_pens, counts, mask,
                            emitted=None):
        """Lossless speculative verification under the decoding policy
        (leftover-probability rejection sampling; greedy rows keep the
        token-exact argmax rule).  ``verify_multi``'s contract with a
        ``counts`` carry before the pools: ``(toks_block, valid,
        tok_end, active_end, lengths_end, emitted_end, accepted,
        counts_end, pools)``.  One compiled signature per K bucket —
        sampling params are traced, so sampled+spec composes without
        recompiles (the gate ``ds_serve`` used to force off)."""
        self.refuse_slot_state("spec_decode")
        assert self.params is not None, "set_params/init_params first"
        shd = self._serving_shardings(num_slots=int(np.shape(budgets)[0]))
        if getattr(self, "_paged_verify_policy_fn", None) is None:
            self._build_serving_fns()
        if emitted is None:
            emitted = np.zeros(np.shape(budgets), np.int32)
        slot, blk = shd.slot, shd.block
        (toks, drafts, widths, active, page_table, lengths, emitted,
         budgets, eos_ids) = self._stage_host_inputs([
             (toks, np.int32, slot), (drafts, np.int32, blk),
             (widths, np.int32, slot), (active, bool, slot),
             (page_table, np.int32, blk), (lengths, np.int32, slot),
             (emitted, np.int32, slot), (budgets, np.int32, slot),
             (eos_ids, np.int32, slot)])
        (keys, tok_base, temps, top_ks, top_ps, rep_pens, pres_pens,
         freq_pens, counts, mask) = self._stage_policy_inputs(
             shd, keys, tok_base, temps, top_ks, top_ps, rep_pens,
             pres_pens, freq_pens, counts, mask)
        args = (self.params, toks, drafts, widths, active, page_table,
                lengths, pools, emitted, budgets, eos_ids, keys,
                tok_base, temps, top_ks, top_ps, rep_pens, pres_pens,
                freq_pens, counts, mask)
        k = int(np.shape(drafts)[1])
        if self._comm_capture is not None:
            self._capture_comm_sig("verify_policy",
                                   f"verify_policy[k={k}]",
                                   "_paged_verify_policy_fn", args)
        with self._serving_scope():
            return self._dispatch(
                "verify_policy", self._paged_verify_policy_fn, *args,
                detail=None if self._compile_watchdog is None
                else {"k": k, "policy": True})

    def sample_from_logits_policy(self, logits, keys, tok_idx, temps,
                                  top_ks, top_ps, rep_pens, pres_pens,
                                  freq_pens, counts, mask):
        """Boundary sampling under the decoding policy: the prefill-
        finish counterpart of ``sample_from_logits``.  ``logits`` is a
        list of [vocab] rows (or an [n, vocab] batch); every other
        argument is per-row.  Unlike the legacy sampled path (one rng
        split per CALL), each row draws from ``fold_in(keys[r],
        tok_idx[r])`` — the same position-keyed stream the fused decode
        uses, so the boundary token is reproducible across batching,
        preemption-recompute and failover.  One compiled signature per
        row count (bounded by num_slots)."""
        if isinstance(logits, (list, tuple)):
            rows = jnp.stack([jnp.asarray(r) for r in logits])
        else:
            rows = jnp.asarray(logits)
        single = rows.ndim == 1
        if single:
            rows = rows[None]
        if getattr(self, "_policy_rows_fn", None) is None:
            def rows_fn(rows, keys, tok_idx, temps, top_ks, top_ps,
                        rep_pens, pres_pens, freq_pens, counts, mask):
                with jax.named_scope("sample"):
                    x = policy_pipeline.process_logits(
                        rows, counts, mask, temps, top_ks, top_ps,
                        rep_pens, pres_pens, freq_pens)
                    return policy_pipeline.sample_processed(
                        x, keys, tok_idx, temps).astype(jnp.int32)
            self._policy_rows_fn = jax.jit(rows_fn)
        n = rows.shape[0]
        with dist.mesh_scope(self.mesh):
            toks = self._dispatch(
                "sample_policy", self._policy_rows_fn, rows,
                jnp.asarray(np.asarray(keys, np.uint32).reshape(n, 2)),
                jnp.asarray(np.asarray(tok_idx, np.int32)),
                jnp.asarray(np.asarray(temps, np.float32)),
                jnp.asarray(np.asarray(top_ks, np.int32)),
                jnp.asarray(np.asarray(top_ps, np.float32)),
                jnp.asarray(np.asarray(rep_pens, np.float32)),
                jnp.asarray(np.asarray(pres_pens, np.float32)),
                jnp.asarray(np.asarray(freq_pens, np.float32)),
                jnp.asarray(np.asarray(counts, np.int32)),
                jnp.asarray(np.asarray(mask, bool)))
        out = [int(t) for t in np.asarray(jax.device_get(toks))]
        return out[0] if single else out

    def serving_verify_compile_count(self):
        """Compiled signatures behind verify_multi (legacy greedy +
        policy twin summed) — bounded by the scheduler's spec-K bucket
        set per path, never by request churn, acceptance outcomes or
        sampling-param churn."""
        return (jit_cache_size(getattr(self, "_paged_verify_fn", None)) +
                jit_cache_size(getattr(self, "_paged_verify_policy_fn",
                                       None)))

    def sample_from_logits(self, logits, do_sample=False, temperature=1.0,
                           top_k=0, top_p=1.0):
        """Sample from logits (same `_sample_tokens` math as generate()).
        A single [vocab] row returns an int; a list of rows (or an
        [n, vocab] batch) samples every row in ONE device call and
        returns a list — the serving scheduler batches all slots
        finishing prefill in a step this way instead of paying one tiny
        dispatch per slot. Sampled mode draws one rng split per CALL
        (not per row), so batching changes the stream; greedy decoding
        is unaffected.

        Greedy contract: ``do_sample=False`` OR ``temperature=0`` is a
        deterministic fp32 argmax, ties breaking to the LOWEST token id
        — the exact comparison ``verify_multi`` replays on device, so
        speculative verification stays token-exact vs this function."""
        single = not isinstance(logits, (list, tuple)) and \
            np.ndim(logits) == 1
        toks = self.sample_launch(logits, do_sample, temperature, top_k,
                                  top_p)
        out = [int(t) for t in np.asarray(jax.device_get(toks))]
        return out[0] if single else out

    def sample_launch(self, logits, do_sample=False, temperature=1.0,
                      top_k=0, top_p=1.0):
        """The device half of :meth:`sample_from_logits`: the sampled
        tokens of every row as an int32 ``[n]`` device array, their
        copy to the host started and not waited for.  The scheduler
        keeps it with a prefill dispatch it leaves in flight and pulls
        it one dispatch later."""
        if isinstance(logits, (list, tuple)):
            rows = jnp.stack([jnp.asarray(r) for r in logits])
        else:
            rows = jnp.asarray(logits)
        if rows.ndim == 1:
            rows = rows[None]
        self._rng, rng = jax.random.split(self._rng)
        toks = _sample_tokens(rows, rng, do_sample, temperature, top_k,
                              top_p)
        toks.copy_to_host_async()
        return toks

    # ------------------------------------------- tokens kept on device
    def _token_feedback_fns(self):
        """The programs that keep a boundary sample's tokens on the
        device for the dispatch after it, one signature a row bucket
        each: ``keep(tokens [slots], sampled [rows], slot [rows])``
        files row r's token under ``slot[r]`` (``slot[r] == slots``:
        not kept), and ``ids(host_ids [rows, chunk], src [rows], tokens
        [slots])`` is ``host_ids`` with ``tokens[src[r]]`` in column 0
        of every row whose ``src[r] >= 0``; and one a slot count:
        ``merge(host [slots], owed [slots], tokens [slots])``, a
        horizon's ``last_tok`` with the device's token where ``owed``.
        Their outputs are pinned replicated, as ``prefill_into_slots``
        stages its ids (``decode_multi`` stages its tokens itself), so
        the model's programs keep their one signature a bucket."""
        if getattr(self, "_token_keep_fn", None) is None:
            rep = self._serving_shardings().replicated

            def keep(tokens, sampled, slot):
                with jax.named_scope("sample"):
                    return tokens.at[slot].set(
                        sampled.astype(tokens.dtype), mode="drop")

            def ids(host_ids, src, tokens):
                with jax.named_scope("sample"):
                    first = jnp.where(src >= 0,
                                      tokens[jnp.maximum(src, 0)],
                                      host_ids[:, 0])
                    return host_ids.at[:, 0].set(first)

            def merge(host, owed, tokens):
                with jax.named_scope("sample"):
                    return jnp.where(owed, tokens, host)
            self._token_keep_fn = jax.jit(keep, out_shardings=rep)
            self._token_ids_fn = jax.jit(ids, out_shardings=rep)
            self._token_merge_fn = jax.jit(merge, out_shardings=rep)
        return self._token_keep_fn, self._token_ids_fn, \
            self._token_merge_fn

    def slot_tokens(self, num_slots):
        """A zeroed per-slot token vector for :meth:`keep_sampled`."""
        return jax.device_put(np.zeros(num_slots, np.int32),
                              self._serving_shardings().replicated)

    def keep_sampled(self, tokens, sampled, slot):
        """``tokens`` with the sampled token of row r under ``slot[r]``
        (rows whose ``slot[r]`` is ``len(tokens)`` are dropped)."""
        keep, _, _ = self._token_feedback_fns()
        with dist.mesh_scope(self.mesh):
            return self._dispatch("keep_sampled", keep, tokens, sampled,
                                  np.asarray(slot, np.int32))

    def prefill_ids(self, host_ids, src, tokens):
        """The ``ids_chunk`` of a prefill dispatch some of whose rows'
        first input id is a token still on the device: row r takes
        ``tokens[src[r]]`` where ``src[r] >= 0``, else ``host_ids``."""
        _, ids, _ = self._token_feedback_fns()
        with dist.mesh_scope(self.mesh):
            return self._dispatch("prefill_ids", ids,
                                  np.asarray(host_ids, np.int32),
                                  np.asarray(src, np.int32), tokens)

    def decode_tokens(self, host_toks, owed, tokens):
        """The ``toks`` of a ``decode_multi`` launched before a prefill
        boundary's sample is pulled: slot s starts from ``tokens[s]``
        (the device's copy) where ``owed[s]``, else from ``host_toks``."""
        _, _, merge = self._token_feedback_fns()
        with dist.mesh_scope(self.mesh):
            return self._dispatch("decode_tokens", merge,
                                  np.array(host_toks, np.int32),
                                  np.asarray(owed, bool), tokens)

    def warm_token_feedback(self, sampled, chunk, num_slots):
        """Compile :meth:`keep_sampled`, :meth:`prefill_ids` and
        :meth:`decode_tokens` for the row bucket ``sampled`` came from
        (and the slot count), once an engine: a scheduler
        calls this with the first boundary sample of each bucket (the
        real one: a jit signature is keyed by where its inputs live),
        so the two are built where the bucket's prefill program is (its
        first use, a warm-up's) and never in a window that is measured.
        Not a serving dispatch: no ``ds.engine.launch`` event."""
        rows = int(np.shape(sampled)[0])
        key = (rows, int(chunk), int(num_slots))
        if getattr(self, "_token_feedback_warm", None) is None:
            self._token_feedback_warm = set()
        if key in self._token_feedback_warm:
            return
        self._token_feedback_warm.add(key)
        keep, ids, merge = self._token_feedback_fns()
        with dist.mesh_scope(self.mesh):
            tokens = keep(self.slot_tokens(num_slots), sampled,
                          np.full(rows, num_slots, np.int32))
            ids(np.zeros((rows, chunk), np.int32),
                np.full(rows, -1, np.int32), tokens)
            merge(np.zeros(num_slots, np.int32), np.zeros(num_slots, bool),
                  tokens)

    def serving_prefill_compile_count(self):
        """Compiled signatures behind prefill_into_slots — bounded by
        the scheduler's prefill row-bucket set (one per distinct row
        count), never by request churn: slots / n_valid / start
        offsets are traced data, the row count is the only shape that
        varies."""
        return jit_cache_size(getattr(self, "_paged_prefill_fn", None))

    def serving_seq_prefill_compile_count(self):
        """Compiled signatures behind prefill_sequence_parallel —
        bounded by the scheduler's chunk bucket set (one per distinct
        chunk length), never by request churn: slot / n_valid /
        positions are traced data, the chunk length is the only shape
        in the signature."""
        return jit_cache_size(getattr(self, "_paged_prefill_sp_fn", None))

    def serving_decode_compile_count(self):
        """Number of compiled signatures behind decode_step (the
        no-per-step-recompilation guarantee: stays 1 across churn)."""
        return jit_cache_size(getattr(self, "_paged_decode_fn", None))

    def serving_decode_multi_compile_count(self):
        """Compiled signatures behind decode_multi (legacy greedy +
        policy twin summed) — bounded by the scheduler's horizon bucket
        set (one per distinct horizon per path), never by request churn
        or per-request sampling-param churn: policy knobs are traced
        arrays, so a greedy/sampled/penalized mix re-uses the bucket's
        one executable."""
        return (jit_cache_size(getattr(self, "_paged_decode_multi_fn",
                                       None)) +
                jit_cache_size(getattr(self, "_paged_decode_policy_fn",
                                       None)))

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 max_length=None, stream=False, **kwargs):
        """Autoregressive generation with device-resident KV cache.

        Default path runs the whole decode loop as a single fused dispatch
        (lax.scan) — the per-token host round-trip of a Python loop
        dominates latency on TPU. ``stream=True`` keeps the token-at-a-time
        loop (early eos exit, per-token latencies in model_times())."""
        assert self.params is not None, "set_params/init_params first"
        if kwargs:
            raise TypeError(
                f"generate() got unsupported arguments {sorted(kwargs)}; "
                "supported: max_new_tokens, do_sample, temperature, top_k, "
                "top_p, eos_token_id, max_length, stream")
        ids = np.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        b, prompt_len = ids.shape
        if max_length is not None:
            max_new_tokens = max(int(max_length) - prompt_len, 0)
        if max_new_tokens == 0:
            return ids
        max_len = prompt_len + max_new_tokens
        if max_len > self._config.max_out_tokens:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_out_tokens={self._config.max_out_tokens}; "
                "raise max_out_tokens in the inference config")

        if self._cache_module() is None:
            return self._generate_nocache(ids, max_new_tokens, do_sample,
                                          temperature, top_k, top_p,
                                          eos_token_id)

        # bucket the cache length so calls with nearby lengths share one
        # compiled prefill/decode (the reference sizes its workspace to
        # max_out_tokens once, inference_context.h)
        bucket = 128
        cache_len = min(-(-max_len // bucket) * bucket,
                        self._config.max_out_tokens)
        cache_len = max(cache_len, max_len)
        cache = self._init_cache(b, cache_len)
        if self._prefill_fn is None:
            self._build_gen_fns()

        t0 = time.time()
        with dist.mesh_scope(self.mesh):
            logits, cache = self._prefill_fn(self.params, jnp.asarray(ids),
                                             cache)
        self._rng, rng = jax.random.split(self._rng)
        tok = _sample_tokens(logits, rng, do_sample, temperature, top_k, top_p)
        first = np.asarray(jax.device_get(tok))
        self._model_times.append(time.time() - t0)
        n_rest = max_new_tokens - 1

        if not stream and n_rest > 0:
            # bucket the step count too: scan a rounded-up length and slice,
            # so varying max_new_tokens shares one compiled loop (extra
            # steps only write cache slots past the returned tokens)
            n_bucket = min(-(-n_rest // 32) * 32, cache_len - prompt_len - 1)
            n_bucket = max(n_bucket, n_rest)
            t0 = time.time()
            self._rng, rng = jax.random.split(self._rng)
            finished = jnp.asarray(first == eos_token_id) \
                if eos_token_id is not None else jnp.zeros(b, bool)
            with dist.mesh_scope(self.mesh):
                toks, cache, _ = self._decode_loop_fn(
                    self.params, jnp.asarray(first), cache, finished, rng,
                    int(n_bucket), bool(do_sample), float(temperature),
                    int(top_k), float(top_p),
                    None if eos_token_id is None else int(eos_token_id),
                    0 if eos_token_id is None else int(eos_token_id))
            rest = np.asarray(jax.device_get(toks))[:, :n_rest]
            dt = time.time() - t0
            # aggregate dispatch: spread the loop time over the *emitted*
            # tokens so the recorded times sum to the measured wall time
            # even when the scan length was rounded up past n_rest
            self._model_times.extend([dt / n_rest] * n_rest)
            gen = np.concatenate([first[:, None], rest], axis=1)
            return np.concatenate([ids, gen], axis=1)

        out = [first]
        finished = np.zeros(b, bool)
        if eos_token_id is not None:
            finished |= first == eos_token_id
        tok = jnp.asarray(first)
        for _ in range(n_rest):
            if eos_token_id is not None and finished.all():
                break
            t0 = time.time()
            self._rng, rng = jax.random.split(self._rng)
            with dist.mesh_scope(self.mesh):
                tok, cache = self._decode_fn(self.params, tok, cache, rng,
                                             bool(do_sample),
                                             float(temperature),
                                             int(top_k), float(top_p))
            host_tok = np.asarray(jax.device_get(tok))
            self._model_times.append(time.time() - t0)
            if eos_token_id is not None:
                # rows that finished earlier emit eos fill, not garbage
                host_tok = np.where(finished, eos_token_id, host_tok)
                out.append(host_tok)
                finished |= host_tok == eos_token_id
            else:
                out.append(host_tok)
        gen = np.stack(out, axis=1)
        return np.concatenate([ids, gen], axis=1)

    def _generate_nocache(self, ids, max_new_tokens, do_sample, temperature,
                          top_k, top_p, eos_token_id):
        """Fallback for models without a KV-cache contract: full re-forward
        per token (correct, O(n^2); the reference non-injected path).

        The working buffer is padded to the final length once so the jitted
        forward compiles for a single shape instead of once per emitted
        token (causal models ignore positions past the read index)."""
        module = self.module

        if self._fwd is None:
            materialize = self._materialize
            self._fwd = jax.jit(
                lambda params, ids: module.apply(
                    {"params": materialize(params)}, ids))
        ids = np.asarray(ids)
        b, l0 = ids.shape
        total = l0 + max_new_tokens
        buf = np.zeros((b, total), ids.dtype)
        buf[:, :l0] = ids
        finished = np.zeros(b, bool)
        pos = l0
        for _ in range(max_new_tokens):
            with dist.mesh_scope(self.mesh):
                logits = self._fwd(self.params, jnp.asarray(buf))
            self._rng, rng = jax.random.split(self._rng)
            tok = _sample_tokens(logits[:, pos - 1], rng, do_sample,
                                 temperature, top_k, top_p)
            host_tok = np.asarray(jax.device_get(tok))
            if eos_token_id is not None:
                host_tok = np.where(finished, eos_token_id, host_tok)
            buf[:, pos] = host_tok
            pos += 1
            if eos_token_id is not None:
                finished |= host_tok == eos_token_id
                if finished.all():
                    break
        return buf[:, :pos]
