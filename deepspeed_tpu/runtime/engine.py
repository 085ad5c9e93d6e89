"""DeepSpeedEngine: the central training wrapper, TPU-native.

Reference: ``deepspeed/runtime/engine.py`` (3268 LoC) — ``forward`` :1653,
``backward`` :1795, ``step`` :1991, ``save_checkpoint`` :2818,
``load_checkpoint`` :2513. The torch engine mutates module state and drives
collectives through hooks; here the train state (params, optimizer state,
loss-scale state) is a pytree of **globally-sharded jax.Arrays** and each
micro batch is exactly ONE jitted dispatch:

  gas == 1:    _step_gas1(state, batch, rng, lr) -> loss, state', metrics
  gas > 1:     _micro_first(params, scale, batch, rng)      -> loss, acc
               _micro_next(params, scale, acc, batch, rng)  -> loss, acc
               _step_last(state, acc, batch, rng, lr) -> loss, state', metrics

The boundary step fuses forward+backward+optimizer-apply into one XLA
program: grads never round-trip through a persistent fp32 accumulator for
gas=1 and the optimizer update fuses into the backward epilogue. The fp32
optimizer moments are donated and alias in place; master params are NOT
donated so they stay readable between backward() and step() (reference
engine semantics: state mutates at step).

ZeRO stages are sharding choices (parallel/sharding.py), not code paths:
grads/optimizer state/params pick up a `data`-axis dimension at stages 2/1/3
and XLA emits the reduce-scatters and all-gathers the reference implements
manually (stage_1_and_2.py:894, stage3.py:1076, utils.py:918). The fp16
overflow check + skip-step + dynamic loss scale update run **inside** the
jitted step (no host sync), reproducing the reference's skip semantics.

The user-facing ``forward()/backward()/step()`` trio keeps reference call
shape: forward computes loss+grads in one fused pass (JAX can't backprop an
already-returned loss), backward accumulates, step applies at the gradient
accumulation boundary.
"""

import json
import os
import time
from typing import Any, Optional

import flax.struct
import flax.traverse_util
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu.parallel import sharding as shd
from deepspeed_tpu.runtime.zero.gather import (GatherPlan,
                                               scope as zero_gather_scope)
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader
from deepspeed_tpu.runtime.fp16.loss_scaler import (LossScaleState, has_overflow,
                                                    make_loss_scale_state,
                                                    update_scale)
from deepspeed_tpu.runtime.lr_schedules import LRScheduler, get_lr_schedule
from deepspeed_tpu.runtime.optimizers import build_optimizer
from deepspeed_tpu.tracing import NULL_TRACER, annotation, jit_cache_size
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (BACKWARD_GLOBAL_TIMER,
                                       FORWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER,
                                       NoopTimer, SynchronizedWallClockTimer,
                                       ThroughputTimer)

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray                 # i32: global (optimizer) steps attempted
    skipped_steps: jnp.ndarray        # i32: overflow-skipped steps
    params: Any                       # fp32 master params
    opt_state: Any
    scaler: LossScaleState


class DeepSpeedEngine:
    """Training engine. Build through :func:`deepspeed_tpu.initialize`."""

    def __init__(self, model, config, loss_fn=None, mesh=None,
                 training_data=None, lr_scheduler=None, collate_fn=None,
                 example_batch=None, seed=0, dont_change_device=False,
                 model_input_fn=None, client_optimizer=None):
        self.module = model
        self.client_lr_scheduler = lr_scheduler
        self.model_input_fn = model_input_fn

        # --- mesh first: the batch invariant needs the data-axis size ---
        raw = config if isinstance(config, dict) else None
        if raw is None and isinstance(config, str):
            with open(config) as f:
                raw = json.load(f)
        if mesh is None:
            from deepspeed_tpu.runtime.config import MeshConfig
            mesh = make_mesh(MeshConfig(**(raw or {}).get("mesh", {}) or {}))
        self.mesh = mesh
        dist.set_mesh(mesh)
        self.dp_world_size = mesh.shape["data"]
        self.mp_world_size = mesh.shape["model"]

        self._config = DeepSpeedConfig(raw if raw is not None else config,
                                       dp_world_size=self.dp_world_size)
        self.zero_stage = self._config.zero_optimization_stage
        # ZeRO-Offload / ZeRO-Infinity: host-RAM (or NVMe) optimizer state
        # (runtime/zero/offload.py; reference stage_1_and_2.py CPU path)
        def _dev(cfg):
            if cfg is None:
                return "none"
            return str(cfg.device.value if hasattr(cfg.device, "value")
                       else cfg.device)

        _oc = self._config.zero_config.offload_optimizer
        self._offload_cfg = _oc if _dev(_oc) != "none" else None
        # Training-time ZeRO-3 parameter offload (reference stage3.py:445-480
        # + swap_tensor/partitioned_param_swapper.py): the at-rest compute
        # copy of the params lives in PINNED HOST memory and streams to HBM
        # inside the jitted step (XLA schedules each leaf's transfer next to
        # its consumer); gradients stream back out to host memory, where the
        # host optimizer consumes them.
        _pc = self._config.zero_config.offload_param
        self._offload_param = _dev(_pc) != "none"
        if self._offload_param and self.zero_stage < 3:
            logger.warning("offload_param requires ZeRO stage 3 (reference "
                           "zero/config.py); ignoring for stage "
                           f"{self.zero_stage}")
            self._offload_param = False
        if self._offload_param and self._offload_cfg is None:
            # params on host with optimizer state on device would free the
            # small fraction and keep the big one: optimizer state (fp32
            # master + moments, 12B/param) dwarfs the bf16 compute copy.
            # Imply the host-optimizer tier, like ZeRO-Infinity.
            logger.warning(
                "offload_param without offload_optimizer: enabling host "
                "optimizer offload (optimizer state is 6x the bytes of the "
                "bf16 params)")
            from deepspeed_tpu.runtime.zero.config import \
                DeepSpeedZeroOffloadOptimizerConfig
            self._offload_cfg = DeepSpeedZeroOffloadOptimizerConfig(
                device=_dev(_pc), nvme_path=_pc.nvme_path)
        self._offload = None
        self._params_nvme = False   # set by _ensure_initialized when
        # offload_param.device == "nvme" (ZeRO-Infinity param tier)
        if self._offload_cfg is not None:
            # single worker = FIFO grad accumulation off the main thread
            from concurrent.futures import ThreadPoolExecutor
            self._offload_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="zero_offload")
            self._offload_futs = []
        self.compute_dtype = DTYPES[self._config.precision_dtype]
        self.fp16_enabled = self._config.fp16.enabled
        self.bfloat16_enabled = self._config.bf16.enabled
        jax.config.update("jax_default_matmul_precision",
                          self._config.matmul_precision) \
            if self._config.matmul_precision != "default" else None

        if loss_fn is None:
            from deepspeed_tpu.runtime.pipe.module import PipelineModule
            if isinstance(model, PipelineModule) and \
                    model.schedule == "1f1b":
                loss_fn = model.make_loss_fn()
        self.loss_fn = loss_fn or self._default_loss_fn()
        # pre-wrap reference: the activation-checkpointing wrapper takes
        # **kw, which would defeat signature checks (e.g. pld_theta)
        self._raw_loss_fn = self.loss_fn
        # activation checkpointing section (reference checkpointing.py:474):
        # remat the whole loss under a named policy / host-offload the
        # saved dot products (cpu_checkpointing)
        from deepspeed_tpu.runtime.activation_checkpointing import \
            wrap_loss_fn
        self.loss_fn = wrap_loss_fn(self.loss_fn,
                                    self._config.activation_checkpointing,
                                    mesh=self.mesh)
        self._rng = jax.random.PRNGKey(seed)
        self._example_batch = example_batch

        # optimizer: a client-supplied optax transform wins over the config
        # one (reference engine.py:1176 "client vs config optimizer")
        opt_cfg = self._config.optimizer
        if client_optimizer is not None:
            self.optimizer_name = "client"
            self.tx = client_optimizer
            self._base_lr = float(opt_cfg.params.get("lr", 0.0)) \
                if opt_cfg.params else 0.0
            # a client optimizer owns its own hyperparams unless the client
            # also handed us a schedule to drive
            self._drive_lr = lr_scheduler is not None or \
                (self._config.scheduler.type is not None)
        else:
            self.optimizer_name = opt_cfg.type or "adamw"
            self.tx, self._base_lr = build_optimizer(
                self.optimizer_name, opt_cfg.params,
                gradient_clipping=self._config.gradient_clipping)
            self._drive_lr = True

        # 1-bit compressed gradient sync (reference runtime/comm/nccl.py:15,
        # the comm backend behind the onebit optimizers): a onebit
        # optimizer type + params.comm_backend_name routes the
        # data-parallel gradient reduction through compressed_allreduce
        # under shard_map instead of the XLA psum — sign bits + one scale
        # on the wire (BASELINE.md: up to 5x comm reduction on
        # Ethernet-class links; on TPU this targets the DCN hop).
        self._compressed_axis = None
        _onebit_types = ("onebitadam", "onebitlamb", "zerooneadam")
        _cbn = (opt_cfg.params or {}).get("comm_backend_name")
        if client_optimizer is None and _cbn and \
                (opt_cfg.type or "").lower() in _onebit_types:
            _other = [a for a in ("model", "expert", "pipe", "sequence")
                      if self.mesh.shape.get(a, 1) > 1]
            if _other:
                logger.warning(
                    "comm_backend_name: compressed grad sync supports pure "
                    f"data parallelism; mesh has {_other} — using XLA psum")
            elif self._offload_cfg is not None:
                logger.warning(
                    "comm_backend_name: compressed grad sync does not "
                    "compose with the host-offload grad path — using "
                    "XLA psum")
            elif self.mesh.shape["data"] > 1:
                self._compressed_axis = "data"

        # lr schedule
        self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)

        # bookkeeping
        self.micro_steps = 0           # micro batches seen since init
        self.global_steps = 0          # optimizer steps taken (host mirror)
        self.global_samples = 0
        self.state: Optional[TrainState] = None
        self._grad_acc = None          # running grad sum (gas > 1 windows)
        self._pending = None           # forward() result awaiting backward()
        self._census_probe = None      # first whole-step dispatch, by shapes
        self._census = None            # its collective census, once read
        self._next_state = None        # boundary result awaiting step()
        self._next_metrics = None
        self._last_metrics = {}
        self.gas = self._config.gradient_accumulation_steps

        self._data_sampler = None        # data-efficiency v2 sampler
        self._data_sampler_state = None  # restored before deepspeed_io runs
        # pluggable checkpoint backend (checkpoint/backend.py; reference
        # checkpoint_engine.py:9 ABC + Nebula variant)
        from deepspeed_tpu.checkpoint.backend import get_checkpoint_engine
        self.checkpoint_engine = get_checkpoint_engine(
            self._config.checkpoint_engine)

        # progressive layer drop: theta(t) computed host-side per forward
        # and handed to the model through the loss fn (reference
        # engine.py:1139 progressive_layer_drop + :2021 update_state)
        self.progressive_layer_drop = None
        if self._config.pld.enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import (
                ProgressiveLayerDrop)
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self._config.pld.theta, gamma=self._config.pld.gamma)
            import inspect
            try:
                ps = inspect.signature(self._raw_loss_fn).parameters
                accepts = "pld_theta" in ps or any(
                    p.kind == p.VAR_KEYWORD for p in ps.values())
            except (TypeError, ValueError):
                accepts = True
            if not accepts:
                raise ValueError(
                    "progressive_layer_drop is enabled but the loss_fn "
                    "does not accept a pld_theta kwarg — add "
                    "`pld_theta=None` to its signature and pass it into "
                    "the model call (models/gpt2.py consumes it)")
        # random-LTD (reference data_routing/basic_layer.py:14 wired at
        # engine.py:1698): the kept-token count is a SHAPE, so it enters
        # the program as a build-time constant; each schedule milestone
        # rebuilds the jitted fns (one recompile per milestone — size
        # step_size so a full run pays a handful)
        self._rltd_cfg = None
        self._rltd = None
        self._rltd_keep = None
        de = self._config.data_efficiency or {}
        # same falsy defaults as the data_sampling gate in deepspeed_io
        # and the reference data_pipeline/config.py: every level of the
        # data_efficiency section is off unless explicitly enabled
        dr = de.get("data_routing", {}) if de.get("enabled") else {}
        rl = dr.get("random_ltd", {}) if dr.get("enabled") else {}
        if rl.get("enabled"):
            self._rltd_cfg = rl
            import inspect
            try:
                ps = inspect.signature(self._raw_loss_fn).parameters
                accepts = "rltd_keep" in ps or any(
                    p.kind == p.VAR_KEYWORD for p in ps.values())
            except (TypeError, ValueError):
                accepts = True
            if not accepts:
                raise ValueError(
                    "random_ltd is enabled but the loss_fn does not "
                    "accept an rltd_keep kwarg — add `rltd_keep=None` "
                    "to its signature and pass it into the model call "
                    "(models/gpt2.py consumes it)")
        # compression-aware training: runtime built once params exist
        # (_ensure_initialized); strengths ride the batch as traced
        # scalars so schedule changes never recompile
        self._compression = None
        # MoQ: eigenvalue-scheduled quantization periods (reference
        # engine.py:2014-2026)
        self.eigenvalue = None
        self._gas_boundary_ctr = 0
        if self._config.eigenvalue.enabled:
            from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
            ev = self._config.eigenvalue
            self.eigenvalue = Eigenvalue(
                verbose=ev.verbose, max_iter=ev.max_iter, tol=ev.tol,
                stability=ev.stability,
                gas_boundary_resolution=ev.gas_boundary_resolution)
        # PLD / compression / random-LTD compose with the 1-bit path:
        # the reserved schedule scalars ride the batch REPLICATED into
        # the shard_map (batch_specs in _build_jitted_fns) and the local
        # loss threads them exactly like the SPMD fwd_bwd does

        self.timers = SynchronizedWallClockTimer() \
            if self._config.wall_clock_breakdown else NoopTimer()

        # monitor
        from deepspeed_tpu.monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(self._config.monitor_config)

        # throughput reporting rides the monitor event stream when a
        # sink is enabled (train/samples_per_s*), else the legacy print
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self._config.steps_per_print,
            monitor=self.monitor)

        # host-side span tracing (deepspeed_tpu/tracing.py): the shared
        # no-op singleton unless a supervisor/caller installs a real
        # tracer — tracing off must stay byte-identical (no device op,
        # no new jit signature; pinned by tests/unit/test_train_trace.py)
        self.tracer = NULL_TRACER

        dist.configure(self._config)
        # comm.log_summary's periodic report rides the same monitor
        # stream as ThroughputTimer when the engine's sinks are
        # enabled (comm/<op>/* gauges); without one the legacy print
        # is preserved byte-for-byte.  Last engine wins (weakly held —
        # a discarded engine's monitor detaches with it)
        dist.attach_monitor(self.monitor if self.monitor.enabled
                            else None)

        self.training_dataloader = self.deepspeed_io(training_data, collate_fn) \
            if training_data is not None else None

        if example_batch is not None:
            self._ensure_initialized(example_batch)

    # ------------------------------------------------------------------ config
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self.zero_stage

    def get_global_grad_norm(self):
        return self._last_metrics.get("grad_norm")

    # reference accessor surface (engine.py:480-857 exposes ~120 of
    # these; the ones client code commonly touches)
    def get_mom(self):
        """Current (beta1, beta2) per param group (reference get_mom)."""
        betas = (self._config.optimizer.params or {}).get(
            "betas", (0.9, 0.999))
        return [tuple(betas)]

    def global_rank(self):
        return jax.process_index()

    def world_size(self):
        return jax.process_count()

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def fp16_enabled(self):
        return bool(self._config.fp16.enabled)

    def bfloat16_enabled(self):
        return bool(self._config.bf16.enabled)

    def zero_offload_optimizer(self):
        return self._offload is not None

    def wall_clock_breakdown(self):
        return bool(self._config.wall_clock_breakdown)

    def steps_per_print(self):
        return self._config.steps_per_print

    def monitor_enabled(self):
        return bool(self.monitor.enabled)

    @property
    def loss_scale(self):
        if self._offload is not None:
            return float(self._offload.scaler.loss_scale)
        if self.state is None:
            return 1.0
        return float(jax.device_get(self._live_state().scaler.loss_scale))

    @property
    def skipped_steps(self):
        if self._offload is not None:
            return self._offload.skipped_steps
        if self.state is None:
            return 0
        return int(jax.device_get(self._live_state().skipped_steps))

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps + 1) % self.gas == 0

    def _default_loss_fn(self):
        """Default contract: module(input_ids) -> logits, next-token CE.
        MoE aux losses sown under "intermediates" (moe/layer.py) are added
        with the model's `moe_loss_coef` (reference adds l_aux in the client
        loss; the engine folds it in for the default path)."""
        from deepspeed_tpu.models.gpt2 import gpt2_loss_fn
        module = self.module
        coef = getattr(getattr(module, "cfg", None), "moe_loss_coef", None)
        moe_coef = 0.01 if coef is None else float(coef)

        def loss_fn(params, batch, rng, pld_theta=None, rltd_keep=None):
            rngs = None
            kw = {}
            if rng is not None:
                # "gating" feeds MoE's stochastic drop policies (RTS /
                # RSample); unused rngs are free in flax
                rngs = {"dropout": rng,
                        "gating": jax.random.fold_in(rng, 3)}
            if pld_theta is not None:   # progressive layer drop active
                r = rng if rng is not None else jax.random.PRNGKey(0)
                rngs = dict(rngs or {})
                rngs["pld"] = jax.random.fold_in(r, 1)
                kw["pld_theta"] = pld_theta
            if rltd_keep is not None:   # random-LTD token dropping
                r = rng if rng is not None else jax.random.PRNGKey(0)
                rngs = dict(rngs or {})
                rngs["rltd"] = jax.random.fold_in(r, 2)
                kw["rltd_keep"] = rltd_keep
            logits, mut = module.apply(
                {"params": params}, batch["input_ids"], rngs=rngs,
                mutable=["intermediates"], **kw)
            with jax.named_scope("loss"):
                loss = gpt2_loss_fn(logits, batch)
                aux = [v for path, v in
                       flax.traverse_util.flatten_dict(
                           mut.get("intermediates", {})).items()
                       if path[-1] == "moe_aux_loss"]
                if aux:
                    # sow stores a tuple per call site
                    terms = [jnp.asarray(x) for tup in aux for x in tup]
                    loss = loss + moe_coef * sum(terms)
            return loss

        return loss_fn

    def _configure_lr_scheduler(self, client_scheduler):
        if client_scheduler is not None:
            # a bare schedule callable (step -> lr) gets the LRScheduler
            # interface; an LRScheduler (or duck-typed object with
            # get_lr/step) passes through
            if not isinstance(client_scheduler, LRScheduler) and \
                    callable(client_scheduler) and \
                    not hasattr(client_scheduler, "get_lr"):
                return LRScheduler(client_scheduler)
            return client_scheduler
        s = self._config.scheduler
        if s.type:
            return LRScheduler(get_lr_schedule(s.type, s.params))
        return None

    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_lr()
        return [self._base_lr]

    # ------------------------------------------------------------- init params
    def _ensure_initialized(self, batch):
        if self.state is not None:
            return
        t0 = time.time()
        mesh = self.mesh
        host_batch = jax.tree.map(np.asarray, batch)
        init_rng, self._rng = jax.random.split(self._rng)

        example_input = self._model_input(host_batch)

        def init_fn(rng):
            return self.module.init(rng, self._example_like(example_input))

        boxed_shapes = jax.eval_shape(init_fn, init_rng)
        boxed_shapes = boxed_shapes.get("params", boxed_shapes)
        logical = shd.get_logical_specs(boxed_shapes)
        shapes = shd.unbox(boxed_shapes)

        persist = int(self._config.zero_config
                      .stage3_param_persistence_threshold) \
            if self.zero_stage >= 3 else 0
        self.param_pspecs = shd.tree_pspecs(mesh, shapes, logical,
                                            self.zero_stage, kind="param",
                                            persist_threshold=persist)
        opt_param_pspecs = shd.tree_pspecs(mesh, shapes, logical,
                                           self.zero_stage, kind="opt")
        if self._offload_cfg is not None:
            self.opt_pspecs = ()   # optimizer state lives on the host
        else:
            opt_shapes = jax.eval_shape(self.tx.init, shapes)
            self.opt_pspecs = shd.opt_state_pspecs(opt_shapes, shapes,
                                                   opt_param_pspecs)
        self.grad_pspecs = opt_param_pspecs if self.zero_stage >= 2 \
            else self.param_pspecs
        # Stage 3: ``param_pspecs`` is a leaf's spec AT REST (sharded
        # over `data`; ``cast()`` makes the compute-dtype copy on the
        # shard).  AT USE a leaf has its stage-2 spec, the
        # tensor-parallel axes alone: under the gather plan the matmul
        # or lookup that consumes a leaf all-gathers the cast shard
        # where it runs, and its backward computes the weight gradient
        # in float32 and constrains it to the at-rest spec — the
        # reduce-scatter (runtime/zero/gather.py).  The plan holds the
        # leaves whose at-rest spec has the `data` axis, so it is empty
        # (None) at stage <= 2 and on a `data` axis of size 1.
        self._gather_plan = GatherPlan(
            mesh, shapes, self.param_pspecs,
            shd.tree_pspecs(mesh, shapes, logical,
                            min(self.zero_stage, 2), kind="param")) or None

        param_sh = shd.tree_shardings(mesh, self.param_pspecs)
        opt_sh = shd.tree_shardings(mesh, self.opt_pspecs)
        self._grad_sh = shd.tree_shardings(mesh, self.grad_pspecs)

        def init_params(r):
            variables = init_fn(r)
            return shd.unbox(variables.get("params", variables))

        params = jax.jit(init_params, out_shardings=param_sh)(init_rng)
        if self._offload_cfg is not None:
            # ZeRO-Offload: pull the fp32 master to host, keep only the
            # compute-dtype copy on the chip, moments live host/NVMe.
            from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
            _pc = self._config.zero_config.offload_param
            self._params_nvme = bool(
                self._offload_param and _pc is not None and
                str(getattr(_pc, "device", "none")) == "nvme")
            param_nvme_path = None
            if self._params_nvme:
                param_nvme_path = _pc.nvme_path or \
                    getattr(self._offload_cfg, "nvme_path", None)
                assert param_nvme_path, \
                    "offload_param.device=nvme needs offload_param." \
                    "nvme_path (or offload_optimizer.nvme_path)"
            self._offload = HostOffloadOptimizer(
                self.optimizer_name, self._config.optimizer.params,
                gradient_clipping=self._config.gradient_clipping,
                fp16_cfg=self._config.fp16, fp16_enabled=self.fp16_enabled,
                offload_cfg=self._offload_cfg,
                aio_config=self._config.aio_config,
                param_nvme_path=param_nvme_path,
                param_dtype={jnp.bfloat16: "bf16",
                             jnp.float16: "f16"}.get(self.compute_dtype,
                                                     "f32"))
            from deepspeed_tpu.checkpoint.engine import param_leaf_names
            leaf_names = param_leaf_names(params)
            # sparse embedding grads (reference sparse_gradients +
            # SparseTensor, engine.py:2303): embedding-table leaves ship
            # their grads D2H as (touched-row indices, rows) instead of
            # the dense [vocab, d] table. Decided from names + shapes of
            # the (still-device) tree — host_leaves may be a one-shot
            # generator below.
            self._sparse_positions = frozenset(
                i for i, (n, l) in enumerate(
                    zip(leaf_names, jax.tree.leaves(params)))
                if self._config.sparse_gradients_enabled and l.ndim == 2
                and any(t in n.lower()
                        for t in ("wte", "wpe", "embed"))) or None
            if self._params_nvme:
                # one leaf in RAM at a time: each master streams to NVMe
                # before the next device_get lands
                host_leaves = (np.asarray(jax.device_get(l))
                               for l in jax.tree.leaves(params))
            else:
                host_leaves = [np.asarray(jax.device_get(l))
                               for l in jax.tree.leaves(params)]
            self._offload.init_master(host_leaves, names=leaf_names)
            compute_dtype = self.compute_dtype
            if self._params_nvme:
                # ZeRO-Infinity param tier: the device/pinned copies are
                # dropped entirely — state.params becomes the tier's
                # memmap views over the NVMe files (written in compute
                # dtype by init_master; no on-device cast needed). Each
                # dispatch device_puts them to the (device-kind)
                # shardings, so pages stream NVMe -> page cache -> HBM
                # on demand and the buffers die with the dispatch; the
                # optimizer sweep rewrites the files through the SAME
                # page cache, so the next dispatch reads the updated
                # bytes. RAM holds the evictable page cache, never a
                # pinned full copy.
                treedef = jax.tree.structure(params)
                del params
                params = jax.tree_util.tree_unflatten(
                    treedef, self._offload.param_tier.param_memmaps())
                self._param_mat_sh = param_sh
                self._injit_materialize = False
                log_dist("ZeRO-Infinity: at-rest params on NVMe "
                         f"({self._offload.param_tier.dir}); per-dispatch "
                         "page-cached streaming", ranks=[0])
            else:
                cast_fn = jax.jit(
                    lambda p: jax.tree.map(
                        lambda x: x.astype(compute_dtype), p),
                    out_shardings=param_sh, donate_argnums=(0,))
                params = cast_fn(params)
            if not self._params_nvme and self._offload_param:
                # at-rest compute copy in pinned host memory; the jitted
                # step streams leaves to HBM per use (same mechanism the
                # inference engine proves for ZeRO-Inference,
                # inference/engine.py _materialize) and writes grads back
                # to host memory. Between steps the chip holds no params.
                host_sh = jax.tree.map(
                    lambda s: s.with_memory_kind("pinned_host"), param_sh)
                params = jax.tree.map(jax.device_put, params, host_sh)
                self._param_mat_sh = param_sh   # device-kind shardings
                # Streaming strategy: prefer materializing INSIDE the
                # jitted step (XLA schedules each leaf's — or, with
                # scan_layers, each layer slice's — transfer next to its
                # consumer and frees it after last use: params larger
                # than HBM train). Some backends reject memory-space
                # transfers of sharded arrays under SPMD ("side-effect
                # ops cannot be replicated"); probe once and fall back to
                # an eager pre-dispatch transfer (full bf16 tree resident
                # for the dispatch) when unsupported.
                self._injit_materialize = self._probe_injit_materialize(
                    params, param_sh, host_sh)
                self._grad_sh_dev = self._grad_sh
                if self._injit_materialize:
                    # host-kind grad shardings: _micro_offload device_puts
                    # each grad leaf to these inside the program, so grads
                    # leave HBM before the dispatch returns and the host
                    # optimizer reads pinned memory directly
                    self._grad_sh = jax.tree.map(
                        lambda s: s.with_memory_kind("pinned_host"),
                        self._grad_sh)
                log_dist("ZeRO-3 param offload: at-rest params in pinned "
                         "host memory, "
                         + ("in-program streaming"
                            if self._injit_materialize else
                            "per-dispatch transfer (backend rejects "
                            "in-program memory-space moves)"), ranks=[0])
                param_sh = host_sh
            self._param_treedef = jax.tree.structure(params)
            self._param_sh_flat = jax.tree.leaves(param_sh)
            opt_state = ()      # optimizer state lives on the host
        else:
            opt_state = jax.jit(self.tx.init, out_shardings=opt_sh)(params)

        scaler = make_loss_scale_state(self._config.fp16, self.fp16_enabled)
        self.state = TrainState(step=jnp.int32(0), skipped_steps=jnp.int32(0),
                                params=params, opt_state=opt_state,
                                scaler=scaler)
        # pin state shardings so the apply step can't silently reshard params,
        # and commit the scalar fields to the mesh (replicated) so every leaf
        # lives on the same device set
        rep = NamedSharding(mesh, P())
        self._state_sh = jax.tree.map(lambda _: rep, self.state).replace(
            params=param_sh, opt_state=opt_sh)
        if getattr(self, "_params_nvme", False):
            # the memmap leaves must NOT be committed to devices here:
            # they stream per dispatch (a device_put now would pin the
            # full model in HBM for the run)
            mm_params = self.state.params
            scalars = jax.tree.map(
                jax.device_put, self.state.replace(params=()),
                self._state_sh.replace(params=()))
            self.state = scalars.replace(params=mm_params)
        else:
            self.state = jax.tree.map(jax.device_put, self.state,
                                      self._state_sh)
        if self._compressed_axis:
            # per-worker error-feedback buffers for the compressed
            # collective (reference worker_error/server_error,
            # runtime/comm/nccl.py): leading dp axis = one slice per
            # worker. Not checkpointed — the residual re-accumulates
            # within a step after resume.
            n = mesh.shape[self._compressed_axis]

            def we_leaf(s):
                sh = NamedSharding(mesh, P(self._compressed_axis,
                                           *([None] * len(s.shape))))
                return jax.device_put(
                    jnp.zeros((n,) + tuple(s.shape), jnp.float32), sh)

            def se_leaf(s):
                size = int(np.prod(s.shape or (1,)))
                chunk = (size + (-size) % (n * 8)) // n
                sh = NamedSharding(mesh, P(self._compressed_axis, None))
                return jax.device_put(jnp.zeros((n, chunk), jnp.float32),
                                      sh)

            self._onebit_we = jax.tree.map(we_leaf, shapes)
            self._onebit_se = jax.tree.map(se_leaf, shapes)
        if self._config.compression_training:
            from deepspeed_tpu.compression.compress import CompressionRuntime
            self._compression = CompressionRuntime(
                self._config.compression_training, self.state.params,
                num_heads=getattr(getattr(self.module, "cfg", None),
                                  "num_heads", None))
            log_dist("compression-aware training: "
                     f"{len(self._compression)} config groups active",
                     ranks=[0])
        # sparse embedding gradients on the dense-DP path (reference
        # engine.py:2303 sparse allreduce in plain DP; the offload path
        # has its own D2H variant). Engaged when data parallelism is
        # real and the fused gas window / onebit / offload are not
        # claiming the step.
        self._sparse_dp = False
        if self._config.sparse_gradients_enabled and \
                self._offload is None and not self._compressed_axis and \
                mesh.shape.get("data", 1) > 1 and self.gas == 1 and \
                self.zero_stage <= 2 and \
                self.progressive_layer_drop is None and \
                self._compression is None and self._rltd_cfg is None and \
                not self._config.compression_training:
            if getattr(getattr(self.module, "cfg", None),
                       "tie_embeddings", False):
                raise ValueError(
                    "sparse_gradients with a TIED embedding head: the "
                    "lm head's backward produces a DENSE [vocab, d] "
                    "grad on wte every step, so there is nothing "
                    "sparse to ship — untie the embeddings or disable "
                    "sparse_gradients")
            from deepspeed_tpu.checkpoint.engine import param_leaf_names
            names = param_leaf_names(self.state.params)
            lv = jax.tree.leaves(self.state.params)
            self._sparse_dp_positions = frozenset(
                i for i, (nm, l) in enumerate(zip(names, lv))
                if l.ndim == 2 and any(t in nm.lower()
                                       for t in ("wte", "wpe", "embed")))
            ids = self._model_input(batch)
            self._sparse_dp_tokens = int(
                np.prod(np.shape(ids)) // mesh.shape["data"])
            self._sparse_dp = bool(self._sparse_dp_positions)
            if self._sparse_dp:
                log_dist(
                    "sparse_gradients: dense-DP embedding grads sync as "
                    f"(indices, rows) over 'data' — "
                    f"{len(self._sparse_dp_positions)} leaves, "
                    f"{self._sparse_dp_tokens} rows/shard budget",
                    ranks=[0])
        self._build_jitted_fns()
        n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        log_dist(f"engine initialized: {n_params / 1e6:.2f}M params, mesh="
                 f"{dict(mesh.shape)}, zero_stage={self.zero_stage}, "
                 f"dtype={self._config.precision_dtype}, "
                 f"init took {time.time() - t0:.1f}s", ranks=[0])

    def _model_input(self, batch):
        """The tensor the module's __call__ consumes, for shape inference.
        Override with model_input_fn for exotic batch layouts."""
        if self.model_input_fn is not None:
            return self.model_input_fn(batch)
        if isinstance(batch, dict):
            for key in ("input_ids", "x", "inputs", "tokens"):
                if key in batch:
                    return batch[key]
            return next(iter(batch.values()))
        if isinstance(batch, (tuple, list)):
            return batch[0]
        return batch

    def _example_like(self, x):
        return jnp.asarray(x)

    def _batch_sharding(self, batch):
        mesh = self.mesh
        def f(leaf):
            arr = np.asarray(leaf)
            spec = P("data") if arr.ndim >= 1 and \
                arr.shape[0] % mesh.shape["data"] == 0 else P()
            return NamedSharding(mesh, spec)
        return jax.tree.map(f, batch)

    def _put_batch(self, batch):
        sh = self._batch_sharding(batch)
        return jax.tree.map(lambda x, s: jax.device_put(jnp.asarray(x), s),
                            batch, sh)

    # --------------------------------------------------------------- jitted fns
    def _build_jitted_fns(self):
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype
        gas = float(self.gas)
        tx = self.tx
        clip_norm = float(self._config.gradient_clipping or 0.0)
        predivide = float(self._config.gradient_predivide_factor or 1.0)
        drive_lr = self._drive_lr

        def cast(p):
            return jax.tree.map(
                lambda x: x.astype(compute_dtype)
                if x.dtype == jnp.float32 and compute_dtype != jnp.float32 else x, p)

        # ZeRO-3 gather-at-use: installed around the trace of the SPMD
        # loss, never inside the 1-bit / sparse shard_maps, whose bodies
        # hold whole local parameters already (there stage 3 keeps the
        # program it had: the shard_map's own boundary gathers)
        plan = self._gather_plan

        rltd_keep_static = self._rltd_keep

        # in-program param streaming (ZeRO-3 param offload): host-kind
        # params enter the program; XLA places each transfer next to its
        # consumer and frees the device buffer after last use
        mat_sh = self._param_mat_sh \
            if getattr(self, "_injit_materialize", False) else None

        def materialize(p):
            if mat_sh is None:
                return p
            return jax.tree.map(jax.device_put, p, mat_sh)


        # pipeline loss_fns hand back (loss, grads) from one interleaved
        # 1F1B scan — cheaper than value_and_grad, which would run the
        # forward-only pipeline AND the backward's forward slots
        loss_and_grads = getattr(loss_fn, "loss_and_grads", None)

        comp = self._compression

        RESERVED = ("_ds_pld_theta", "_ds_comp")

        def pop_reserved(batch):
            """Split the reserved schedule scalars (injected by
            forward() as TRACED values, so per-step changes never
            recompile) out of the batch: -> (clean_batch, extras,
            loss_kw). ONE implementation shared by the SPMD fwd_bwd and
            the 1-bit shard_map local loss."""
            extras = {}
            if isinstance(batch, dict) and any(k in batch
                                               for k in RESERVED):
                batch = dict(batch)
                for k in RESERVED:
                    if k in batch:
                        extras[k] = batch.pop(k)
            loss_kw = {"pld_theta": extras["_ds_pld_theta"]} \
                if "_ds_pld_theta" in extras else {}
            if rltd_keep_static is not None:
                # a shape constant: baked into this build of the
                # jitted fns (forward() rebuilds at schedule milestones)
                loss_kw["rltd_keep"] = rltd_keep_static
            return batch, extras, loss_kw

        def make_prep(extras, mat=True):
            """The shared param-preparation closure (cast [+ in-jit
            materialize] + compression apply) — ONE implementation for
            the SPMD and 1-bit paths; ``mat=False`` on the per-worker
            path, where offload streaming is excluded by construction."""
            def prep(p):
                p = cast(materialize(p) if mat else p)
                if comp is not None and "_ds_comp" in extras:
                    p = comp.apply(p, extras["_ds_comp"])
                return p
            return prep

        def fwd_bwd(params, scale, batch, rng):
            batch, extras, loss_kw = pop_reserved(batch)
            prep = make_prep(extras)

            if loss_and_grads is not None:
                assert not extras and rltd_keep_static is None, \
                    "compression/pld/random_ltd do not compose with the " \
                    "fused 1F1B pipeline loss yet"
                loss, grads = loss_and_grads(cast(materialize(params)), batch)
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) * (scale / gas), grads)
                return loss, grads

            def scaled_loss(p):
                with zero_gather_scope(plan):
                    loss = loss_fn(prep(p), batch, rng, **loss_kw)
                with jax.named_scope("loss"):
                    return loss.astype(jnp.float32) * scale / gas, loss

            (s_loss, loss), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(params)
            return loss, grads

        # Overflow check + skip-step are fp16 loss-scaling machinery
        # (reference FP16_Optimizer); bf16/fp32 training never skips
        # (reference BF16_Optimizer has no CheckOverflow). Gating it out
        # also deletes a full isfinite pass over the grad tree that the
        # fused gas window can't fuse into the adam update (~2.4ms/window
        # at GPT-2-small bench shapes).
        check_overflow = self.fp16_enabled

        def apply_grads(state, acc, lr):
            with jax.named_scope("optimizer"):
                scale = state.scaler.loss_scale
                grads = jax.tree.map(lambda g: g / (scale * predivide), acc)
                overflow = has_overflow(grads) if check_overflow \
                    else jnp.bool_(False)

                gnorm = optax.global_norm(grads)
                if clip_norm > 0.0:
                    factor = jnp.minimum(1.0, clip_norm / (gnorm + 1e-6))
                    grads = jax.tree.map(lambda g: g * factor, grads)

                opt_state = state.opt_state
                # drive the LR schedule value into inject_hyperparams state
                # (skipped for a client optimizer with no schedule: its own
                # hyperparams stand)
                if drive_lr and hasattr(opt_state, "hyperparams"):
                    hp = dict(opt_state.hyperparams)
                    hp["learning_rate"] = jnp.asarray(lr, jnp.float32)
                    opt_state = opt_state._replace(hyperparams=hp)

                updates, new_opt = tx.update(grads, opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)

                # skip-step on overflow (reference
                # stage_1_and_2.py:1636 semantics)
                if check_overflow:
                    new_params = jax.tree.map(
                        lambda n, o: jnp.where(overflow, o, n), new_params,
                        state.params)
                    new_opt = jax.tree.map(
                        lambda n, o: jnp.where(overflow, o, n), new_opt,
                        opt_state)

                scaler = update_scale(state.scaler, overflow)
                new_state = state.replace(
                    step=state.step + 1,
                    skipped_steps=state.skipped_steps +
                    overflow.astype(jnp.int32),
                    params=new_params, opt_state=new_opt, scaler=scaler)
                metrics = {"grad_norm": gnorm, "overflow": overflow,
                           "loss_scale": scaler.loss_scale}
                return new_state, metrics

        # One fused dispatch per micro batch; the boundary step folds the
        # optimizer apply into the same XLA program so the whole train step
        # is a single executable (no persistent fp32 accumulator at gas=1).
        # Only opt_state is donated: params must stay readable between
        # backward() and step() (reference engine semantics — state mutates
        # at step), and the optimizer moments are the bulk of the bytes.
        def step_gas1(params, opt_state, rest, batch, rng, lr):
            state = rest.replace(params=params, opt_state=opt_state)
            loss, grads = fwd_bwd(params, state.scaler.loss_scale, batch, rng)
            new_state, metrics = apply_grads(state, grads, lr)
            return loss, new_state, metrics

        self._step_gas1 = jax.jit(
            step_gas1, donate_argnums=(1,),
            out_shardings=(None, self._state_sh, None))

        def micro_first(params, scale, batch, rng):
            return fwd_bwd(params, scale, batch, rng)

        self._micro_first = jax.jit(
            micro_first, out_shardings=(None, self._grad_sh))

        # offload-mode micro dispatch: flat per-leaf grads, with
        # embedding leaves row-sparsified on device so only touched rows
        # cross the host link (reference sparse_allreduce, engine.py:2303).
        # When the backend supports in-program memory-space moves
        # (_injit_materialize), each grad leaf is moved to pinned host
        # memory INSIDE the program — the leaves never sit in HBM between
        # dispatch and the host optimizer. The output structure depends on
        # the traced batch shape (sparse leaves become (idx, rows, n)
        # tuples), so this is an in-body device_put rather than jit
        # out_shardings.
        sparse_pos = getattr(self, "_sparse_positions", None)
        injit_grads_to_host = (self._offload is not None and
                               getattr(self, "_injit_materialize", False))
        if injit_grads_to_host:
            grad_host_sh = jax.tree.leaves(self._grad_sh)  # host-kind
            host_rep = NamedSharding(
                self.mesh, P(), memory_kind="pinned_host")

        def micro_offload(params, scale, batch, rng):
            loss, grads = fwd_bwd(params, scale, batch, rng)
            leaves = jax.tree.leaves(grads)
            if sparse_pos:
                tokens = int(np.prod(
                    jnp.shape(self._model_input(batch)))) or 1
                out = []
                for i, g in enumerate(leaves):
                    k = min(tokens, g.shape[0]) if g.ndim == 2 else 0
                    if i in sparse_pos and 0 < k < g.shape[0]:
                        rn = jnp.sum(jnp.abs(g), axis=1)
                        n_touched = jnp.sum(rn > 0).astype(jnp.int32)
                        idx = jnp.nonzero(rn > 0, size=k,
                                          fill_value=0)[0]
                        # mask pad slots POSITIONALLY: nonzero's fill
                        # index 0 may itself be a touched row, so a
                        # value-based mask would scatter row 0's grad
                        # once per pad slot
                        valid = (jnp.arange(k) <
                                 jnp.minimum(n_touched, k)).astype(g.dtype)
                        # n_touched rides along so the host can detect a
                        # DENSE grad hitting this leaf (tied-embedding
                        # head) and fail loudly instead of truncating
                        out.append((idx, g[idx] * valid[:, None],
                                    n_touched))
                    else:
                        out.append(g)
                leaves = out
            if injit_grads_to_host:
                leaves = [
                    tuple(jax.device_put(part, host_rep) for part in g)
                    if isinstance(g, tuple)
                    else jax.device_put(g, grad_host_sh[i])
                    for i, g in enumerate(leaves)]
            return loss, leaves

        self._micro_offload = jax.jit(micro_offload)

        def micro_next(params, scale, acc, batch, rng):
            loss, grads = fwd_bwd(params, scale, batch, rng)
            with jax.named_scope("optimizer"):
                return loss, jax.tree.map(jnp.add, acc, grads)

        self._micro_next = jax.jit(
            micro_next, donate_argnums=(2,),
            out_shardings=(None, self._grad_sh))

        def step_last(params, opt_state, rest, acc, batch, rng, lr):
            state = rest.replace(params=params, opt_state=opt_state)
            loss, grads = fwd_bwd(params, state.scaler.loss_scale, batch, rng)
            with jax.named_scope("optimizer"):
                acc = jax.tree.map(jnp.add, acc, grads)
            new_state, metrics = apply_grads(state, acc, lr)
            return loss, new_state, metrics

        self._step_last = jax.jit(
            step_last, donate_argnums=(1, 3),
            out_shardings=(None, self._state_sh, None))

        # Fused full accumulation window: all gas micro batches + the
        # optimizer apply in ONE dispatch (train_batch uses this when the
        # whole window's data is available). Kills the 3-dispatch pattern
        # for the gas>1 regime every large-model config runs (VERDICT r2
        # weak #2); the fp32 accumulator lives only inside the program.
        # The micro loop is UNROLLED, not lax.scan: a scan carrying the
        # params-sized fp32 accumulator measures ~19x slower on v5e (the
        # loop-carried buffer defeats in-place accumulation), while the
        # unrolled body runs at the gas=1 rate.
        n_micro = self.gas

        def step_gasN(params, opt_state, rest, batches, rng, lr):
            state = rest.replace(params=params, opt_state=opt_state)
            scale = state.scaler.loss_scale
            rngs = jax.random.split(rng, n_micro)
            acc, losses = None, []
            for i in range(n_micro):
                b = jax.tree.map(lambda x: x[i], batches)
                loss, grads = fwd_bwd(params, scale, b, rngs[i])
                with jax.named_scope("optimizer"):
                    acc = grads if acc is None else \
                        jax.tree.map(jnp.add, acc, grads)
                losses.append(loss)
            new_state, metrics = apply_grads(state, acc, lr)
            # mean computed in-program: fetching per-micro losses would
            # cost a host round trip per step
            with jax.named_scope("loss"):
                return jnp.mean(jnp.stack(losses)), new_state, metrics

        # params donated too: _train_batch_fused commits the new state
        # before control returns, so no caller can observe the donated
        # buffer, and the old tree hosts the new one instead of a fresh
        # params-sized allocation per window. The forward()/step() split
        # paths do NOT donate params — users legitimately read
        # state.params between backward() and step().
        self._step_gasN = jax.jit(
            step_gasN, donate_argnums=(0, 1),
            out_shardings=(None, self._state_sh, None))

        # Multi-STEP fused driver (train_loop): lax.scan over K complete
        # optimizer steps (windows, when gas > 1) in one dispatch.
        # Per-dispatch host overhead (arg marshaling + runtime round
        # trip) amortizes over K. Unlike the gasN accumulator
        # (unrolled above — its loop-carried fp32 accumulator defeated
        # in-place updates), the scan carry here is the full train state
        # and every carried buffer is rewritten each iteration, so XLA
        # aliases it in place: measured at the per-step device rate.
        win_fn = step_gas1 if n_micro == 1 else step_gasN

        def step_loop(params, opt_state, rest, batches, rngs, lrs):
            def body(carry, xs):
                p, o, r = carry
                b, rng_i, lr_i = xs
                loss, new_state, metrics = win_fn(p, o, r, b, rng_i, lr_i)
                return (new_state.params, new_state.opt_state,
                        new_state.replace(params=None, opt_state=None)), \
                    (loss, metrics)
            with jax.named_scope("train_loop"):
                (p, o, r), (losses, metrics) = jax.lax.scan(
                    body, (params, opt_state, rest), (batches, rngs, lrs))
                last = jax.tree.map(lambda m: m[-1], metrics)
            return losses, r.replace(params=p, opt_state=o), last

        self._step_loop = jax.jit(
            step_loop, donate_argnums=(0, 1),
            out_shardings=(None, self._state_sh, None))

        if getattr(self, "_sparse_dp", False):
            # sparse_gradients on the DENSE data-parallel path
            # (reference sparse_allreduce_no_retain, engine.py:2303): the
            # fwd+bwd runs under shard_map so the embedding grads stay
            # per-worker; embedding leaves sync as (touched-row indices,
            # rows) via all_gather + scatter-add — traffic scales with
            # tokens, not vocab — while every other leaf takes a plain
            # pmean. Tied-embedding heads produce DENSE wte grads, which
            # would overflow the row budget: the sync poisons the result
            # with NaN in that case so training fails loudly instead of
            # silently dropping gradient mass.
            from jax import lax
            mesh = self.mesh
            sparse_pos = self._sparse_dp_positions

            def sparse_sync(grads, k):
                # k (row budget) comes from the TRACED batch shape, so a
                # curriculum/packing change retraces with the right
                # budget instead of NaN-poisoning legitimate grads
                leaves = jax.tree.leaves(grads)
                out = []
                for i, g in enumerate(leaves):
                    if i in sparse_pos and g.ndim == 2 and \
                            0 < k < g.shape[0]:
                        rn = jnp.sum(jnp.abs(g), axis=1)
                        n_touched = jnp.sum(rn > 0)
                        idx = jnp.nonzero(rn > 0, size=k,
                                          fill_value=0)[0]
                        valid = (jnp.arange(k) <
                                 jnp.minimum(n_touched, k)).astype(g.dtype)
                        vals = g[idx] * valid[:, None]
                        all_idx = lax.all_gather(idx, "data")
                        all_vals = lax.all_gather(vals, "data")
                        dense = jnp.zeros_like(g).at[
                            all_idx.reshape(-1)].add(
                            all_vals.reshape(-1, g.shape[1]))
                        dp = all_idx.shape[0]
                        bad = (n_touched > k).astype(g.dtype)
                        out.append(dense / dp +
                                   bad * jnp.float32(jnp.nan).astype(
                                       g.dtype))
                    else:
                        out.append(lax.pmean(g, "data"))
                return jax.tree.unflatten(jax.tree.structure(grads), out)

            def local_fwd_bwd_sparse(params, scale, batch, rng):
                def scaled_loss(p):
                    loss = loss_fn(cast(p), batch, rng)
                    return loss.astype(jnp.float32) * scale, loss

                (_, loss), grads = jax.value_and_grad(
                    scaled_loss, has_aux=True)(params)
                k = int(np.prod(np.shape(self._model_input(batch))))
                return lax.pmean(loss, "data"), sparse_sync(grads, k)

            sm_sparse = jax.shard_map(
                local_fwd_bwd_sparse, mesh=mesh,
                in_specs=(P(), P(), P("data"), P()),
                out_specs=(P(), P()),
                check_vma=False)   # the all_gather makes grads
            # replicated; the rep checker cannot prove it

            def step_sparse_dp(params, opt_state, rest, batch, rng, lr):
                state = rest.replace(params=params, opt_state=opt_state)
                loss, grads = sm_sparse(params, state.scaler.loss_scale,
                                        batch, rng)
                new_state, metrics = apply_grads(state, grads, lr)
                return loss, new_state, metrics

            self._step_sparse_dp = jax.jit(
                step_sparse_dp, donate_argnums=(1,),
                out_shardings=(None, self._state_sh, None))

        if self._compressed_axis:
            # 1-bit compressed grad sync: the whole fwd+bwd runs under
            # shard_map so gradients stay per-worker (no SPMD psum);
            # compressed_allreduce exchanges sign bits + one scale with
            # error feedback, then the boundary apply runs on the
            # (bitwise-identical) synced grads. check_vma off: the
            # all_gather in phase 2 makes outputs replicated, which the
            # rep checker cannot prove.
            from deepspeed_tpu.runtime.comm.compressed import \
                compressed_allreduce
            from jax import lax
            shard_map = jax.shard_map
            ca = self._compressed_axis
            mesh = self.mesh

            def compress_sync(grads, we, se):
                """Error-feedback sign-allreduce over a grad tree; the
                we/se buffers carry a leading per-worker axis inside the
                shard_map ([0] strips it, [None] restores it)."""
                outs = [compressed_allreduce(g, w[0], s_[0], ca)
                        for g, w, s_ in zip(jax.tree.leaves(grads),
                                            jax.tree.leaves(we),
                                            jax.tree.leaves(se))]
                tdef = jax.tree.structure(grads)
                return (jax.tree.unflatten(tdef, [o[0] for o in outs]),
                        jax.tree.unflatten(tdef,
                                           [o[1][None] for o in outs]),
                        jax.tree.unflatten(tdef,
                                           [o[2][None] for o in outs]))

            def batch_specs(batch, stacked=False):
                """Per-leaf specs: the reserved schedule scalars
                (compression strengths, pld theta) ride the batch
                REPLICATED — only real data leaves shard over 'data'.
                This is what lets PLD/compression compose with the
                1-bit path (r4 weak #5). ``stacked`` adds the fused
                window's leading [n_micro] axis to every spec."""
                data_spec = P(None, "data") if stacked else P("data")
                rep_spec = P(None) if stacked else P()
                if not isinstance(batch, dict):
                    return jax.tree.map(lambda _: data_spec, batch)
                return {k: (rep_spec if k in RESERVED
                            else jax.tree.map(lambda _: data_spec, v))
                        for k, v in batch.items()}

            def local_loss(params, batch, rng, scale, div=1.0):
                """One micro's scaled loss + grads for the per-worker
                (shard_map) path; reserved-key and prep handling are the
                shared pop_reserved/make_prep."""
                batch, extras, loss_kw = pop_reserved(batch)
                prep = make_prep(extras, mat=False)

                def scaled_loss(p):
                    loss = loss_fn(prep(p), batch, rng, **loss_kw)
                    return loss.astype(jnp.float32) * scale / div, loss

                return jax.value_and_grad(scaled_loss,
                                          has_aux=True)(params)

            def local_fwd_bwd(params, scale, batch, rng, we, se):
                (_, loss), grads = local_loss(params, batch, rng, scale)
                g_sync, new_we, new_se = compress_sync(grads, we, se)
                return lax.pmean(loss, ca), g_sync, new_we, new_se

            def step_onebit(params, opt_state, rest, batch, rng, lr,
                            we, se):
                state = rest.replace(params=params, opt_state=opt_state)
                # the shard_map builds INSIDE the trace so its in_specs
                # can follow the batch's structure (reserved keys
                # replicated, data leaves sharded)
                sm = shard_map(
                    local_fwd_bwd, mesh=mesh,
                    in_specs=(P(), P(), batch_specs(batch), P(), P(ca),
                              P(ca)),
                    out_specs=(P(), P(), P(ca), P(ca)),
                    check_vma=False)   # phase-2 all_gather makes
                # loss/grads replicated; the rep checker cannot prove it
                loss, grads, we, se = sm(params, state.scaler.loss_scale,
                                         batch, rng, we, se)
                new_state, metrics = apply_grads(state, grads, lr)
                return loss, new_state, metrics, we, se

            self._step_onebit = jax.jit(
                step_onebit, donate_argnums=(1, 6, 7),
                out_shardings=(None, self._state_sh, None, None, None))

            if n_micro > 1:
                # 1-bit x gradient accumulation (reference
                # fp16/onebit/adam.py:13 semantics: error feedback per
                # OPTIMIZER step): micro grads accumulate LOCALLY inside
                # the shard_map — no per-micro sync of any kind — and
                # ONE compressed allreduce fires at the boundary over
                # the accumulated grads
                def local_fwd_bwd_gasN(params, scale, batches, rng,
                                       we, se):
                    rngs = jax.random.split(rng, n_micro)
                    acc, losses = None, []
                    for i in range(n_micro):
                        b = jax.tree.map(lambda x: x[i], batches)
                        (_, loss), grads = local_loss(
                            params, b, rngs[i], scale, div=gas)
                        acc = grads if acc is None else \
                            jax.tree.map(jnp.add, acc, grads)
                        losses.append(loss)
                    g_sync, new_we, new_se = compress_sync(acc, we, se)
                    return (lax.pmean(jnp.mean(jnp.stack(losses)), ca),
                            g_sync, new_we, new_se)

                def step_onebit_gasN(params, opt_state, rest, batches,
                                     rng, lr, we, se):
                    state = rest.replace(params=params,
                                         opt_state=opt_state)
                    sm_n = shard_map(
                        local_fwd_bwd_gasN, mesh=mesh,
                        in_specs=(P(), P(),
                                  batch_specs(batches, stacked=True),
                                  P(), P(ca), P(ca)),
                        out_specs=(P(), P(), P(ca), P(ca)),
                        check_vma=False)
                    loss, grads, we, se = sm_n(
                        params, state.scaler.loss_scale, batches, rng,
                        we, se)
                    new_state, metrics = apply_grads(state, grads, lr)
                    return loss, new_state, metrics, we, se

                self._step_onebit_gasN = jax.jit(
                    step_onebit_gasN, donate_argnums=(1, 6, 7),
                    out_shardings=(None, self._state_sh, None, None,
                                   None))

    # -------------------------------------------------------------- profiling
    def module_profile(self, batch=None, depth=3, n_steps=3):
        """Per-module measured flops/bytes/latency of one train step
        (reference print_model_profile, profiler.py:23 — but from a real
        device trace: every XLA op's measured time, flop count and HBM
        bytes, attributed to its flax module path via the HLO metadata).
        Returns (records, formatted_table). Trains ``n_steps`` real
        steps on ``batch``."""
        from deepspeed_tpu.profiling.module_profiler import (
            capture_trace, format_profile)
        if batch is None:
            batch = getattr(self, "_last_batch", None)
        if batch is None:
            batch = self._example_batch
        assert batch is not None, "module_profile needs a batch"
        self._ensure_initialized(batch)

        def step():
            # a COMPLETE optimizer step per traced iteration: with
            # gradient accumulation the window's micro dispatches AND
            # the boundary apply (fp32 accumulator + Adam traffic) all
            # land inside the trace
            return self.train_batch(batches=[batch] * self.gas,
                                    sync=False)

        step()   # compile outside the trace window
        records = capture_trace(step, n_steps=n_steps)
        return records, format_profile(records, depth=depth)

    def _step_probe_args(self, batch=None):
        """Inputs of the step executables for the analysis-only
        lower->compile seam (flops / comm / HLO text): ``(batch, live
        state, state minus params and opt_state, device batch, rng,
        lr)``.  ``batch`` defaults to the last trained batch."""
        if batch is None:
            batch = getattr(self, "_last_batch", None)
        if batch is None:
            batch = self._example_batch
        assert batch is not None, "profiling the step needs a batch"
        self._ensure_initialized(batch)
        state = self._live_state()
        return (batch, state, state.replace(params=None, opt_state=None),
                self._put_batch(batch), jax.random.PRNGKey(0),
                float(self.get_lr()[0]))

    def flops_profile(self, batch=None):
        """Exact flops/bytes of one optimizer step from the compiled XLA
        executables (reference FlopsProfiler.get_total_flops — but from
        the optimizer's own post-fusion HLO, so remat and fusion are
        accounted). Returns a dict; gas>1 sums the micro dispatches."""
        from deepspeed_tpu.profiling.flops_profiler.profiler import (
            cost_analysis, params_count)
        cached = getattr(self, "_flops_profile_cache", None)
        if cached is not None:
            return cached
        batch, state, rest, dev_batch, rng, lr = self._step_probe_args(batch)
        if self._offload is not None:
            micro = cost_analysis(self._micro_offload,
                                  self._materialize_params(state.params),
                                  jnp.float32(1.0), dev_batch, rng)
            flops = micro["flops"] * self.gas
            bytes_ = micro["bytes_accessed"] * self.gas
        elif self.gas == 1:
            c = cost_analysis(self._step_gas1, state.params,
                              state.opt_state, rest, dev_batch, rng, lr)
            flops, bytes_ = c["flops"], c["bytes_accessed"]
        else:
            first = cost_analysis(self._micro_first, state.params,
                                  state.scaler.loss_scale, dev_batch, rng)
            grads_sds = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                state.params)
            last = cost_analysis(self._step_last, state.params,
                                 state.opt_state, rest, grads_sds,
                                 dev_batch, rng, lr)
            nxt = cost_analysis(self._micro_next, state.params,
                                state.scaler.loss_scale, grads_sds,
                                dev_batch, rng)
            flops = first["flops"] + (self.gas - 2) * nxt["flops"] + \
                last["flops"]
            bytes_ = first["bytes_accessed"] + \
                (self.gas - 2) * nxt["bytes_accessed"] + \
                last["bytes_accessed"]
        n_params = params_count(state.params)
        tokens_per_step = self.gas * max(
            int(np.prod(np.shape(self._model_input(batch)))), 1)
        out = {"flops_per_step": flops, "bytes_accessed": bytes_,
               "params": n_params,
               "flops_per_token": flops / tokens_per_step}
        self._flops_profile_cache = out   # shapes are fixed per engine
        return out

    def comm_profile(self, batch=None):
        """Static HLO communication ledger of one optimizer step — the
        comm twin of :meth:`flops_profile`, reading the same compiled
        executables through the same lower->compile seam
        (``profiling/comm_ledger.py``): collective counts and
        per-device bytes per mesh axis, ICI vs DCN tier split, loop
        trip counts accounted.  gas>1 sums the micro dispatches exactly
        like the flops accounting.  Analysis-only (one extra compile
        per executable, cached per engine); it can never change tokens,
        losses or compile counts — pinned by
        ``tests/unit/test_comm_telemetry.py``."""
        from deepspeed_tpu.profiling import comm_ledger as _cl
        cached = getattr(self, "_comm_profile_cache", None)
        if cached is not None:
            return cached
        batch, state, rest, dev_batch, rng, lr = self._step_probe_args(batch)
        mesh = self.mesh
        if self._offload is not None:
            micro = _cl.ledger_for(
                self._micro_offload,
                self._materialize_params(state.params),
                jnp.float32(1.0), dev_batch, rng, mesh=mesh)
            out = _cl.scale_ledger(micro, self.gas)
        elif self.gas == 1:
            out = _cl.ledger_for(self._step_gas1, state.params,
                                 state.opt_state, rest, dev_batch, rng,
                                 lr, mesh=mesh)
        else:
            first = _cl.ledger_for(self._micro_first, state.params,
                                   state.scaler.loss_scale, dev_batch,
                                   rng, mesh=mesh)
            grads_sds = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                state.params)
            last = _cl.ledger_for(self._step_last, state.params,
                                  state.opt_state, rest, grads_sds,
                                  dev_batch, rng, lr, mesh=mesh)
            nxt = _cl.ledger_for(self._micro_next, state.params,
                                 state.scaler.loss_scale, grads_sds,
                                 dev_batch, rng, mesh=mesh)
            out = _cl.merge_ledgers(
                [first, _cl.scale_ledger(nxt, max(self.gas - 2, 0)),
                 last])
        self._comm_profile_cache = out
        return out

    def compiled_step_text(self, batch=None):
        """Optimized HLO text of the gas=1 optimizer-step executable —
        the same lower->compile seam :meth:`flops_profile` and
        :meth:`comm_profile` read.  What a chip check greps to prove a
        kernel is ON the compiled path (a Pallas kernel is a
        ``tpu_custom_call`` custom call) rather than assumed from
        config."""
        assert self.gas == 1 and self._offload is None, \
            "compiled_step_text reads the fused gas=1 step executable"
        _, state, rest, dev_batch, rng, lr = self._step_probe_args(batch)
        return self._step_gas1.lower(
            state.params, state.opt_state, rest, dev_batch, rng,
            lr).compile().as_text()

    def _dispatch_step(self, name, *args):
        """Run the whole-step executable ``name`` and, the first time,
        keep what :meth:`collective_census` needs to find the program
        again: the callable and its arguments' shapes and shardings."""
        first = self._census_probe is None
        if first:
            self._census_probe = (name, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, weak_type=x.weak_type,
                    sharding=x.sharding if x.committed else None)
                if isinstance(x, jax.Array) else x, args))
        out = getattr(self, name)(*args)
        if first and self.mesh.size > 1:    # one device: no collectives
            self.collective_census()
        return out

    def collective_census(self):
        """Collective census of the step program this engine compiled
        and ran first — the count and per-device bytes of every
        ``all_gather`` / ``reduce_scatter`` / ``all_reduce`` /
        ``all_to_all`` / ``collective_permute`` by shape class (``param``:
        an operand or result has a parameter's shape or a shard's;
        ``other``: an activation, a scalar), the heaviest movers that
        are no parameter's by name, and the leaves and bytes the ZeRO-3
        plan gathers at use.  Under gather-at-use the all-gathers and
        reduce-scatters are ``param`` and nothing large is ``other``.

        Reads the executable the first dispatch compiled (lowering the
        same callable for the same shapes and shardings finds it in
        jit's own caches: no second compile); None before any whole
        step ran.  Computed once, logged once."""
        if self._census is None and self._census_probe is not None:
            from deepspeed_tpu.profiling.comm_ledger import \
                collective_census
            name, args = self._census_probe
            text = getattr(self, name).lower(*args).compile().as_text()
            # a parameter's shapes: whole, one scanned layer's slice,
            # and their shards under the at-rest and the gradient specs
            shapes = set()
            is_spec = lambda x: isinstance(x, P)
            leaves = jax.tree.leaves(self.state.params)
            for specs in (self.param_pspecs, self.grad_pspecs):
                for leaf, spec in zip(
                        leaves, jax.tree.leaves(specs, is_leaf=is_spec)):
                    spec = tuple(spec) + (None,) * (leaf.ndim - len(spec))
                    for sh, sp in ((leaf.shape, spec),
                                   (leaf.shape[1:], spec[1:])):
                        shapes.add(tuple(sh))
                        shapes.add(NamedSharding(
                            self.mesh, P(*sp)).shard_shape(tuple(sh)))
            census = collective_census(text, shapes, mesh=self.mesh)
            census["program"] = name.lstrip("_")
            census["gather_at_use"] = self._gather_plan.summary() \
                if self._gather_plan is not None else None
            self._census = census
            log_dist("collective census of " + census["program"] + ": " +
                     json.dumps(census, sort_keys=True), ranks=[0])
        return self._census

    def set_tracer(self, tracer):
        """Install a host-side span tracer (None restores the shared
        no-op singleton).  Tracing is host bookkeeping only — it can
        never change tokens, losses or compile counts."""
        self.tracer = tracer if tracer is not None else NULL_TRACER

    # jitted train callables whose signature-cache sizes define "the
    # compile count" of a training run (the goodput ledger's
    # compile_warmup detector and the tracing-off parity pin both
    # consume this; mirrors the serving-side *_compile_count methods)
    _TRAIN_JIT_FNS = ("_step_gas1", "_micro_first", "_micro_next",
                      "_step_last", "_step_gasN", "_step_loop",
                      "_micro_offload", "_step_sparse_dp",
                      "_step_onebit", "_step_onebit_gasN")

    def train_compile_counts(self):
        """Compiled-signature counts per jitted train callable (only
        the ones this configuration has built).  Counts come from
        ``tracing.jit_cache_size`` — the ONE compile-count definition
        the serving engine, the goodput ledger's ``compile_warmup``
        detector and the recompile watchdog all share."""
        out = {}
        for name in self._TRAIN_JIT_FNS:
            fn = getattr(self, name, None)
            if fn is not None and hasattr(fn, "_cache_size"):
                out[name.lstrip("_")] = jit_cache_size(fn)
        return out

    def train_compile_count(self):
        """Total compiled train-step signatures (cheap per-step probe)."""
        return sum(self.train_compile_counts().values())

    def _maybe_log_flops(self):
        cfg = self._config.flops_profiler
        if not cfg.enabled or self.global_steps != cfg.profile_step:
            return
        prof = self.flops_profile()
        tflops = prof["flops_per_step"] / 1e12
        log_dist(
            f"flops_profiler @ step {self.global_steps}: "
            f"{tflops:.3f} TFLOPs/step, "
            f"{prof['params'] / 1e6:.1f}M params, "
            f"{prof['bytes_accessed'] / 1e9:.2f} GB accessed/step",
            ranks=[0])

    # ------------------------------------------------------------------ train
    def _probe_injit_materialize(self, host_params, dev_sh, host_sh):
        """True when this backend *executes* memory-space transfers of
        arrays with this param tree's shardings in BOTH directions inside
        jit — host->device for the streamed weights, device->host for the
        grad cotangents. Probes tiny stand-ins carrying each distinct
        PartitionSpec (the failure mode — "side-effect ops cannot be
        replicated" under SPMD — depends on the sharding, not the size,
        and only surfaces at execution)."""
        distinct = {}
        for sh in set(jax.tree.leaves(
                jax.tree.map(lambda s: s, dev_sh),
                is_leaf=lambda x: isinstance(x, NamedSharding))):
            # minimal shape divisible by every mesh axis in the spec
            dims = tuple(
                int(np.prod([self.mesh.shape[a] for a in
                             ((e,) if isinstance(e, str) else e)]))
                if e is not None else 1
                for e in sh.spec)
            distinct[sh] = jnp.zeros(dims or (), self.compute_dtype)
        try:
            def round_trip(ps):
                dev = [jax.device_put(p, s) for p, s in
                       zip(ps, distinct.keys())]
                return [jax.device_put(d, s.with_memory_kind("pinned_host"))
                        for d, s in zip(dev, distinct.keys())]
            host_ins = [jax.device_put(
                v, s.with_memory_kind("pinned_host"))
                for s, v in distinct.items()]
            jax.block_until_ready(jax.jit(round_trip)(host_ins))
            return True
        except Exception:
            return False

    def _fallback_to_eager_streaming(self, err):
        """Some backends accept the tiny probe but reject the real step's
        in-program memory-space moves at execution ("side-effect ops
        cannot be replicated" from the SPMD partitioner). Flip to the
        eager per-dispatch transfer once and rebuild the jitted fns."""
        if not (self._offload_param and
                getattr(self, "_injit_materialize", False)) or \
                "annotate_device_placement" not in str(err):
            return False
        log_dist("ZeRO-3 param offload: backend rejected in-program "
                 "streaming at execution; falling back to per-dispatch "
                 "transfers", ranks=[0])
        self._injit_materialize = False
        self._grad_sh = self._grad_sh_dev
        self._build_jitted_fns()
        if hasattr(self, "_eval_fn"):
            del self._eval_fn
        return True

    def _materialize_params(self, params):
        """ZeRO-3 param offload, eager-fallback path: move the pinned-host
        compute copy to HBM for one dispatch (reference fetch_sub_module,
        partitioned_param_coordinator.py:218). The transfer is async; the
        device buffers die with the dispatch's last use, so between steps
        the chip holds no parameters. When `_injit_materialize` is set the
        transfer happens inside the program instead and this is a no-op."""
        if not self._offload_param or \
                getattr(self, "_param_mat_sh", None) is None or \
                getattr(self, "_injit_materialize", False):
            return params
        return jax.device_put(params, self._param_mat_sh)

    def _live_state(self):
        """The most recent state tree with live (non-donated) buffers.

        At a GAS boundary the fused train step donates the old opt-state
        buffers at forward() dispatch; until step() commits, the
        fully-readable tree is the pending result (params stay live either
        way)."""
        if self._next_state is not None:
            return self._next_state
        if self._pending is not None and self._pending[0] == "commit":
            return self._pending[2]
        return self.state

    def _advance_random_ltd(self, batch):
        """Advance the random-LTD schedule; a new kept-token milestone
        rebuilds the jitted fns (shape constant). Returns quickly when
        the feature is off or the milestone is unchanged."""
        if self._rltd_cfg is None:
            return
        if self._rltd is None:
            from deepspeed_tpu.runtime.data_pipeline.random_ltd import (
                RandomLTDScheduler)
            seq = int(np.shape(self._model_input(batch))[-1])
            rl = self._rltd_cfg
            # 128-aligned milestones keep the gathered subsequence on
            # the flash kernel's block grid
            default_step = 128 if seq % 128 == 0 else 16
            self._rltd = RandomLTDScheduler(
                seq_len=seq,
                start_tokens=rl.get("start_tokens"),
                schedule_steps=rl.get("schedule_steps", 1000),
                step_size=rl.get("step_size", default_step))
        keep = self._rltd.keep_tokens(self.global_steps)
        if keep >= self._rltd.seq_len:
            keep = None      # schedule complete: full sequence
        if keep != self._rltd_keep:
            self._rltd_keep = keep
            if self.state is not None:
                self._build_jitted_fns()
                log_dist(f"random-LTD milestone: keeping "
                         f"{keep or self._rltd.seq_len}/"
                         f"{self._rltd.seq_len} tokens per middle layer",
                         ranks=[0])

    def forward(self, batch, rng=None):
        """One micro batch: fused forward+backward (+optimizer apply at the
        gradient-accumulation boundary), a single jitted dispatch."""
        self._advance_random_ltd(batch)
        self._ensure_initialized(batch)
        assert self._next_state is None, \
            "step() must run before the next forward(): the previous " \
            "boundary step donated the old optimizer-state buffers"
        assert self._pending is None, \
            "backward() must run between forward() calls: forward donates " \
            "buffers that only backward() re-homes (for a loss-only pass " \
            "use eval_batch)"
        self.timers(FORWARD_GLOBAL_TIMER).start()
        self._last_batch = batch   # for flops_profile / diagnostics
        dev_batch = self._inject_reserved_keys(self._put_batch(batch))
        if rng is None:
            rng, self._rng = jax.random.split(self._rng)
        if self._offload is not None:
            # offload mode: grads ship to host in backward(), the host
            # optimizer applies in step() — the jit graph is fwd+bwd only
            scale = jnp.float32(self._offload.scaler.loss_scale)
            try:
                loss, grads = self._micro_offload(
                    self._materialize_params(self.state.params), scale,
                    dev_batch, rng)
            except jax.errors.JaxRuntimeError as e:
                if not self._fallback_to_eager_streaming(e):
                    raise
                loss, grads = self._micro_offload(
                    self._materialize_params(self.state.params), scale,
                    dev_batch, rng)
            self._pending = ("offload", loss, grads)
            self.timers(FORWARD_GLOBAL_TIMER).stop()
            return loss
        if self._compressed_axis and self.gas > 1:
            raise RuntimeError(
                "1-bit compressed sync with gradient accumulation runs "
                "through train_batch(batches=[...]) — the fused window "
                "accumulates micro grads locally and compresses ONCE at "
                "the boundary; the per-micro forward() path would psum "
                "every micro batch, defeating the compression")
        boundary = (self.micro_steps + 1) % self.gas == 0
        rest = self.state.replace(params=None, opt_state=None)
        if self.gas == 1 and self._compressed_axis:
            loss, new_state, metrics, self._onebit_we, self._onebit_se = \
                self._step_onebit(
                    self.state.params, self.state.opt_state, rest,
                    dev_batch, rng, float(self.get_lr()[0]),
                    self._onebit_we, self._onebit_se)
            self._pending = ("commit", loss, new_state, metrics)
        elif self.gas == 1 and getattr(self, "_sparse_dp", False):
            loss, new_state, metrics = self._step_sparse_dp(
                self.state.params, self.state.opt_state, rest,
                dev_batch, rng, float(self.get_lr()[0]))
            self._pending = ("commit", loss, new_state, metrics)
        elif self.gas == 1:
            loss, new_state, metrics = self._dispatch_step(
                "_step_gas1", self.state.params, self.state.opt_state,
                rest, dev_batch, rng, float(self.get_lr()[0]))
            self._pending = ("commit", loss, new_state, metrics)
        elif boundary:
            loss, new_state, metrics = self._step_last(
                self.state.params, self.state.opt_state, rest,
                self._grad_acc, dev_batch, rng, float(self.get_lr()[0]))
            self._grad_acc = None
            self._pending = ("commit", loss, new_state, metrics)
        elif self.micro_steps % self.gas == 0:
            loss, acc = self._micro_first(
                self.state.params, self.state.scaler.loss_scale,
                dev_batch, rng)
            self._pending = ("acc", loss, acc)
        else:
            loss, acc = self._micro_next(
                self.state.params, self.state.scaler.loss_scale,
                self._grad_acc, dev_batch, rng)
            self._grad_acc = None
            self._pending = ("acc", loss, acc)
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    def backward(self, loss=None, retain_graph=False, scale_wrt_gas=True):
        """Commit the gradients (or the fused boundary result) of forward()."""
        assert self._pending is not None, \
            "backward() must follow forward() (grads are computed jointly)"
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        kind = self._pending[0]
        if kind == "acc":
            self._grad_acc = self._pending[2]
        elif kind == "offload":
            # async D2H of the (compute-dtype) grads, then host fp32
            # accumulation ON A WORKER THREAD — the main thread returns
            # immediately so the next micro batch dispatches while the
            # grads drain and accumulate (the reference's
            # async_accumulate_grad_in_cpu_via_gpu + side stream,
            # stage_1_and_2.py:1031); step() joins the queue.
            grads = self._pending[2]   # flat list; embedding leaves are
            jax.tree.map(lambda g: g.copy_to_host_async(), grads)

            def drain(ls=grads):
                t0 = time.perf_counter()
                host = []
                for g in ls:
                    if isinstance(g, tuple):
                        idx, vals, n_touched = g
                        if int(n_touched) > idx.shape[0]:
                            raise RuntimeError(
                                f"sparse_gradients: {int(n_touched)} "
                                f"rows of an embedding grad are nonzero "
                                f"but only {idx.shape[0]} fit the "
                                "sparse transfer — the table receives "
                                "dense gradient (tied lm head?); "
                                "disable sparse_gradients")
                        host.append((np.asarray(idx), np.asarray(vals)))
                    else:
                        host.append(np.asarray(g))
                self._offload.accumulate(host)
                self._offload.phase["d2h_accum_s"] += \
                    time.perf_counter() - t0
                self._offload.phase["accum_calls"] += 1

            # backpressure: each queued future pins a device grad tree;
            # bound in-flight trees to 2 (double buffer) so a long gas
            # window can't stack gas grad-sized buffers in HBM
            while len(self._offload_futs) >= 2:
                self._offload_futs.pop(0).result()
            self._offload_futs.append(self._offload_pool.submit(drain))
        else:
            self._next_state = self._pending[2]
            self._next_metrics = self._pending[3]
        self._pending = None
        self.micro_steps += 1
        self.global_samples += self.train_micro_batch_size_per_gpu() * \
            self.dp_world_size
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def step(self):
        """Commit the optimizer step at the gradient-accumulation boundary.

        The update itself was computed (fused with the last backward) in
        forward(); this publishes the new state and advances schedules."""
        if self.micro_steps % self.gas != 0:
            return  # mid-accumulation: nothing to do (reference no-ops too)
        if self._offload is not None:
            return self._offload_step()
        assert self._next_state is not None, \
            "step() must follow forward()+backward() at the GAS boundary"
        self.timers(STEP_GLOBAL_TIMER).start()
        # host share only: the optimizer math itself was fused into the
        # boundary dispatch — this publishes state + advances schedules
        with self.tracer.span("optimizer_step", cat="train",
                              args={"step": self.global_steps}):
            self.state = self._next_state
            metrics = self._next_metrics
            self._next_state = None
            self._next_metrics = None
            lr = float(self.get_lr()[0])  # the lr this step was taken with
            self.global_steps += 1
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            self._last_metrics = metrics
            self._maybe_update_moq()
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._maybe_log_flops()

        if self.monitor.enabled and self.global_steps % \
                self._config.steps_per_print == 0:
            m = jax.device_get(metrics)
            self.monitor.write_events(
                [("Train/Samples/lr", lr, self.global_samples),
                 ("Train/Samples/loss_scale", float(m["loss_scale"]),
                  self.global_samples)])
        return metrics

    def _inject_reserved_keys(self, dev_batch, n_micro=None):
        """Add the compression/pld reserved keys to a device batch
        (fwd_bwd pops them): scalars for the per-micro path, stacked
        [n_micro, ...] for the fused window so the per-micro slice
        ``x[i]`` works. One theta/strength set per optimizer step,
        matching the reference's per-boundary updates."""
        if self._compression is None and \
                self.progressive_layer_drop is None:
            return dev_batch
        assert isinstance(dev_batch, dict), \
            "compression/pld need dict batches (reserved keys ride the " \
            "batch into the jitted step)"
        dev_batch = dict(dev_batch)
        if self.progressive_layer_drop is not None:
            theta = self.progressive_layer_drop.update_state(
                self.global_steps)
            dev_batch["_ds_pld_theta"] = jnp.float32(theta) \
                if n_micro is None else jnp.full((n_micro,), theta,
                                                 jnp.float32)
        if self._compression is not None:
            vec = self._compression.strength_vector(self.global_steps)
            # while every group is still inactive (pre-offset) skip the
            # key entirely: comp.apply would sort/quantize every matched
            # kernel only to return it unchanged. The structure change
            # costs one recompile when the schedule activates.
            if np.any(vec):
                vec = jnp.asarray(vec)
                dev_batch["_ds_comp"] = vec if n_micro is None else \
                    jnp.tile(vec, (n_micro, 1))
        return dev_batch

    def _maybe_update_moq(self):
        """At a gas boundary: recompute MoQ eigenvalue factors every
        ``gas_boundary_resolution`` boundaries."""
        self._gas_boundary_ctr += 1
        if self.eigenvalue is not None and self._compression is not None \
                and self._gas_boundary_ctr % \
                self.eigenvalue.gas_boundary_resolution == 0:
            self._update_moq_eigenvalues()

    def _update_moq_eigenvalues(self):
        """MoQ: per-group Hessian max-eigenvalues stretch each
        weight-quantization group's period, so high-curvature parameters
        quantize slower (reference engine.py:2014-2026 computing
        block_eigenvalue at gas boundaries + quantize.py:70 factor)."""
        import flax.traverse_util
        from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
        wq = [gi for gi, g in enumerate(self._compression.groups)
              if g[0] == "weight_quantization"]
        if not wq or self._last_batch is None:
            return
        batch = self._put_batch(self._last_batch)
        params = self._live_state().params
        if self._offload is not None and \
                getattr(self, "_param_mat_sh", None) is not None:
            # ZeRO-3 param offload: power-iterate on a device copy (the
            # pinned-host at-rest tree can't feed the jitted HVP on
            # backends without in-program memory-space moves)
            params = jax.device_put(params, self._param_mat_sh)

        # STABLE loss identity across boundaries/groups: the batch rides
        # extra_args so the eigenvalue's jitted power step caches
        if not hasattr(self, "_eig_loss"):
            self._eig_loss = lambda p, b: self.loss_fn(p, b, None)

        flat = flax.traverse_util.flatten_dict(params, sep="/")
        keys, vals = list(flat.keys()), list(flat.values())
        evs = []
        rng = jax.random.PRNGKey(self.global_steps)
        for gi in wq:
            # masks are TRANSIENT device fills (freed after the group's
            # power iteration — caching them would pin groups x
            # model-size of HBM), in the param dtype so the bf16
            # tangents aren't promoted inside jvp
            posset = set(self._compression.groups[gi][4])
            mask = flax.traverse_util.unflatten_dict(
                {k: ((jnp.ones if i in posset else jnp.zeros)(
                    jnp.shape(v), jnp.asarray(v).dtype))
                 for i, (k, v) in enumerate(zip(keys, vals))}, sep="/")
            ev, _ = self.eigenvalue.compute_eigenvalue(
                self._eig_loss, params, rng=rng, mask=mask,
                extra_args=(batch,))
            evs.append(ev)
        normed = Eigenvalue.normalize_eigenvalues(evs)
        self._compression.set_eigenvalue_factors(dict(zip(wq, normed)))
        log_dist(f"MoQ eigenvalues (normalized): "
                 f"{dict(zip(wq, [round(v, 3) for v in normed]))}",
                 ranks=[0])

    def _join_offload(self):
        """Drain the grad-accumulation worker queue (exceptions surface
        here). The measured wait is the portion of the D2H/accumulate
        work NOT hidden behind device compute."""
        futs, self._offload_futs = self._offload_futs, []
        t0 = time.perf_counter()
        # the host-visible share of grad sync in offload mode: D2H +
        # fp32 accumulate not hidden behind device compute
        with self.tracer.span("grad_sync", cat="train", track="device",
                              args={"joined": len(futs)}):
            for f in futs:
                f.result()
        if self._offload is not None:
            self._offload.phase.setdefault("join_stall_s", 0.0)
            self._offload.phase["join_stall_s"] += \
                time.perf_counter() - t0

    def offload_phase_stats(self):
        """Per-phase wall-time breakdown since the last call (ZeRO-
        Offload instrumentation; bench embeds it). ``overlap_fraction``
        = share of the D2H+accumulate host work hidden behind device
        compute (1 - join_stall / d2h_accum)."""
        if self._offload is None:
            return {}
        st = self._offload.pop_phase_stats()
        if self._offload.param_tier is not None:
            tier = self._offload.param_tier.pop_stats()
            st.update({f"param_tier_{k}": v for k, v in tier.items()})
            adam = st.get("host_adam_s", 0.0)
            # share of the NVMe leaf-state reads hidden behind the
            # previous leaf's Adam update (prefetch-next-leaf pipeline)
            st["nvme_prefetch_overlap"] = round(
                max(1.0 - tier["nvme_wait_s"] / adam, 0.0), 4) \
                if adam else None
        d2h = st.get("d2h_accum_s", 0.0)
        stall = st.get("join_stall_s", 0.0)
        st["overlap_fraction"] = round(max(1.0 - stall / d2h, 0.0), 4) \
            if d2h else None
        return {k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in st.items()}

    def _offload_step(self):
        """Boundary step in ZeRO-Offload mode: host Adam over the
        accumulated grads, then push the new compute-dtype params back.
        Each leaf's H2D starts (async) the moment its host update
        finishes, so the DMA of leaf i overlaps the Adam of leaf i+1 and
        total time ~ max(host step, transfer), not the sum."""
        self.timers(STEP_GLOBAL_TIMER).start()
        self._join_offload()
        _t_opt = time.monotonic()
        lr = float(self.get_lr()[0])
        if self._params_nvme:
            # ZeRO-Infinity param tier: the sweep rewrites the NVMe
            # files in place; state.params (memmap views) read the new
            # bytes at the next dispatch — nothing to emit or rebuild
            _, metrics = self._offload.step(lr)
            self.state = self.state.replace(
                step=self.state.step + 1,
                skipped_steps=jnp.int32(self._offload.skipped_steps))
        else:
            emit_bf16 = self.compute_dtype == jnp.bfloat16
            if emit_bf16:
                import ml_dtypes

                def put_leaf(i, flat_u16):
                    return jax.device_put(
                        flat_u16.view(ml_dtypes.bfloat16),
                        self._param_sh_flat[i])
                put, metrics = self._offload.step(lr, on_leaf=put_leaf)
            else:
                dt = np.dtype(self.compute_dtype)

                def put_leaf(i, _leaf):
                    arr = self._offload.master[i].reshape(
                        self._offload.shapes[i]).astype(dt)
                    return jax.device_put(arr, self._param_sh_flat[i])
                put, metrics = self._offload.step(lr, on_leaf=put_leaf)
            new_params = jax.tree_util.tree_unflatten(self._param_treedef,
                                                      put)
            self.state = self.state.replace(
                params=new_params, step=self.state.step + 1,
                skipped_steps=jnp.int32(self._offload.skipped_steps))
        self.global_steps += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._last_metrics = metrics
        self._maybe_update_moq()
        # the host Adam sweep + H2D push IS the optimizer step here
        self.tracer.complete("optimizer_step", _t_opt, time.monotonic(),
                             cat="train",
                             args={"step": self.global_steps,
                                   "offload": True})
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._maybe_log_flops()
        if self.monitor.enabled and self.global_steps % \
                self._config.steps_per_print == 0:
            self.monitor.write_events(
                [("Train/Samples/lr", lr, self.global_samples),
                 ("Train/Samples/loss_scale", float(metrics["loss_scale"]),
                  self.global_samples)])
        return metrics

    def train_batch(self, data_iter=None, batches=None, sync=True):
        """Full step: GAS micro-batches -> one optimizer step. Returns mean
        loss. With gas>1 and the whole window's data in hand, the fused
        single-dispatch step runs instead of gas separate dispatches
        (identical math: same fp32 accumulation and boundary apply).
        ``sync=False`` returns the loss as a device scalar without
        blocking on the transfer.

        NOTE: the fused window DONATES the previous params buffers (they
        alias the new tree in place). A reference obtained via
        ``engine.get_params()`` / ``engine.state.params`` BEFORE the call
        is dead afterwards — re-read it from ``engine.state`` after the
        window (the per-micro forward()/backward()/step() path does not
        donate params and has no such hazard)."""
        assert data_iter is not None or batches is not None or \
            self.training_dataloader is not None
        # fault point: raise / sleep / SIGTERM-self on an exact step —
        # the step about to run (global_steps is pre-increment here)
        fstep = self.global_steps
        faults.fire("train.step", step=fstep)
        if data_iter is None and batches is None:
            data_iter = iter(self.training_dataloader)
        tr = self.tracer
        if batches is None and self.gas > 1:
            with tr.span("data_load", cat="train", track="data",
                         args={"n_micro": self.gas, "step": fstep}):
                batches = [next(data_iter) for _ in range(self.gas)]
        if batches is not None:
            # init BEFORE deciding on the fused path: initialization is
            # what instantiates the offload optimizer that rules it out
            self._ensure_initialized(batches[0])
        if self._can_fuse_window():
            return faults.transform(
                "train.loss", self._train_batch_fused(batches, sync=sync),
                step=fstep)
        losses = []
        self.tput_timer.start()
        for i in range(self.gas):
            if batches is not None:
                batch = batches[i]
            else:
                with tr.span("data_load", cat="train", track="data",
                             args={"micro": i, "step": fstep}):
                    batch = next(data_iter)
            # one span per micro dispatch; gas>1 gets per-micro tracks
            # so the accumulation window reads as parallel timeline rows
            with tr.span("fwd_bwd_dispatch", cat="train",
                         track=f"micro{i}" if self.gas > 1 else "scheduler",
                         args={"micro": i, "step": fstep}):
                loss = self.forward(batch)
                self.backward(loss)
            losses.append(loss)
        metrics = self.step()
        self.tput_timer.stop(global_step=True)
        if not sync and self.global_steps % \
                self._config.steps_per_print != 0:
            # window-mean as a device scalar; no host round trip (same
            # metric the fused path reports)
            return faults.transform("train.loss",
                                    jnp.mean(jnp.stack(losses)), step=fstep)
        with tr.span("device_wait", cat="train", track="device",
                     args={"step": fstep}):
            mean_loss = float(np.mean([jax.device_get(l) for l in losses]))
        self._log_train_step(mean_loss, metrics)
        # fault transform: force a NaN loss on an exact step so the
        # supervisor's divergence watchdog is testable end to end
        return faults.transform("train.loss", mean_loss, step=fstep)

    def _log_train_step(self, mean_loss, metrics):
        """THE steps_per_print train-step log + monitor events (shared by
        the fused and micro train_batch paths so the emitted fields can't
        drift apart)."""
        if self.global_steps % self._config.steps_per_print != 0:
            return
        m = jax.device_get(metrics) if metrics else {}
        lr = float(self.get_lr()[0])
        log_dist(f"step={self.global_steps} loss={mean_loss:.4f} "
                 f"lr={lr:.3e} "
                 f"loss_scale={float(m.get('loss_scale', 1.0)):.0f} "
                 f"grad_norm={float(m.get('grad_norm', 0.0)):.3f}",
                 ranks=[0])
        if self.monitor.enabled:
            self.monitor.write_events(
                [("Train/Samples/train_loss", mean_loss,
                  self.global_samples),
                 ("Train/Samples/lr", lr, self.global_samples),
                 ("Train/Samples/loss_scale",
                  float(m.get("loss_scale", 1.0)), self.global_samples)])

    def _can_fuse_window(self):
        """The scan-fused window applies when a full, aligned window is
        in hand and state lives on device (offload mode accumulates on
        the host instead)."""
        return self.gas > 1 and self._offload is None and \
            self._pending is None and self._next_state is None and \
            self.micro_steps % self.gas == 0

    def _stack_batches(self, batches):
        """Stack gas micro batches along a new leading axis, sharded by
        the per-micro batch rule (_batch_sharding) shifted one axis."""
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
        base = self._batch_sharding(batches[0])
        return jax.tree.map(
            lambda x, s: jax.device_put(
                jnp.asarray(x), NamedSharding(self.mesh, P(None, *s.spec))),
            stacked, base)

    def _train_batch_fused(self, batches, sync=True):
        assert len(batches) == self.gas, \
            f"need {self.gas} micro batches, got {len(batches)}"
        self._advance_random_ltd(batches[0])
        self._ensure_initialized(batches[0])
        if not self._can_fuse_window():
            # state became engine-managed mid-window; fall back
            raise RuntimeError("fused window requires an aligned boundary")
        self.tput_timer.start()
        self._last_batch = batches[0]
        tr = self.tracer
        # the whole fused window (fwd+bwd+optimizer apply, grad sync
        # fused inside the XLA program) is ONE async dispatch: batch
        # staging + launch is the host's share; the blocking fetch below
        # is the device's
        fused_span = tr.span("fwd_bwd_dispatch", cat="train",
                             args={"gas": self.gas, "fused": True,
                                   "step": self.global_steps})
        with fused_span:
            dev = self._inject_reserved_keys(self._stack_batches(batches),
                                             n_micro=self.gas)
            rng, self._rng = jax.random.split(self._rng)
            if self._compressed_axis:
                mean_loss_dev, new_state, metrics, self._onebit_we, \
                    self._onebit_se = self._step_onebit_gasN(
                        self.state.params, self.state.opt_state,
                        self.state.replace(params=None, opt_state=None),
                        dev, rng, float(self.get_lr()[0]),
                        self._onebit_we, self._onebit_se)
            else:
                mean_loss_dev, new_state, metrics = self._dispatch_step(
                    "_step_gasN", self.state.params, self.state.opt_state,
                    self.state.replace(params=None, opt_state=None),
                    dev, rng, float(self.get_lr()[0]))
        self.state = new_state
        self.micro_steps += self.gas
        self.global_samples += self.train_micro_batch_size_per_gpu() * \
            self.dp_world_size * self.gas
        self.global_steps += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._last_metrics = metrics
        self._maybe_update_moq()
        self.tput_timer.stop(global_step=True)
        self._maybe_log_flops()
        if sync or self.global_steps % self._config.steps_per_print == 0:
            with tr.span("device_wait", cat="train", track="device",
                         args={"step": self.global_steps}):
                mean_loss_host = float(jax.device_get(mean_loss_dev))
            if self.global_steps % self._config.steps_per_print == 0:
                self._log_train_step(mean_loss_host, metrics)
        # sync=False returns the device scalar (async): a float() fetch
        # per step costs a full host round trip
        return mean_loss_host if sync else mean_loss_dev

    def train_loop(self, batches, sync=False):
        """Run ``len(batches) // gas`` complete optimizer steps in a
        SINGLE jitted dispatch — a lax.scan over full train steps (over
        fused gas windows when gas > 1). Identical math to calling
        forward()/backward()/step() per micro batch; what changes is host
        cost: one dispatch amortizes the per-call overhead (arg
        marshaling + runtime round trip) over the whole span. The old
        state is donated, like the fused gas window.

        Returns the per-window mean losses as a device array ([K],
        async) unless ``sync=True``. PLD / compression / MoQ / 1-bit /
        offload schedules advance per engine-driven step, so they
        require the per-step APIs.
        """
        assert len(batches) % self.gas == 0, \
            f"train_loop needs whole windows: {len(batches)} micro " \
            f"batches with gas={self.gas}; with partial windows use " \
            "train_batch"
        # init BEFORE the composition gates: initialization is what
        # instantiates the offload optimizer / compression runtime the
        # gates check (same ordering rationale as train_batch)
        self._ensure_initialized(batches[0])
        assert self._offload is None and not self._compressed_axis, \
            "train_loop does not compose with host offload or 1-bit sync"
        assert self._compression is None and \
            self.progressive_layer_drop is None and \
            self.eigenvalue is None and self._rltd_cfg is None, \
            "compression/PLD/MoQ/random-LTD schedules advance per " \
            "engine step; drive those through forward()/backward()/step()"
        assert self._pending is None and self._next_state is None, \
            "train_loop cannot start mid-step (pending forward state)"
        assert not getattr(self, "_sparse_dp", False), \
            "sparse_gradients' shard_map grad sync does not ride the " \
            "scan-fused train_loop yet; drive it through " \
            "forward()/backward()/step()"
        k = len(batches) // self.gas
        # host phases of one call as events of a device profile: the
        # whole call, staging the batches, launching the fused steps,
        # and (sync=True) the blocking pull of the losses
        with annotation("ds.train.loop", steps=k):
            self.tput_timer.start()
            self._last_batch = batches[0]
            with annotation("ds.train.stage"):
                if self.gas == 1:
                    dev = self._stack_batches(batches)
                else:
                    # [K, gas, ...]: scan axis over windows, unrolled
                    # micro axis
                    stacked = jax.tree.map(
                        lambda *xs: np.stack(xs).reshape(
                            (k, self.gas) + np.shape(xs[0])), *batches)
                    base = self._batch_sharding(batches[0])
                    dev = jax.tree.map(
                        lambda x, s: jax.device_put(
                            jnp.asarray(x),
                            NamedSharding(self.mesh,
                                          P(None, None, *s.spec))),
                        stacked, base)
            rngs = jax.random.split(self._rng, k + 1)
            self._rng = rngs[0]
            lrs = []
            # the loop really takes k steps: advance the schedule as it
            # goes
            for _ in range(k):
                lrs.append(float(self.get_lr()[0]))
                if self.lr_scheduler is not None:
                    self.lr_scheduler.step()
            with annotation("ds.train.launch"):
                losses, new_state, metrics = self._dispatch_step(
                    "_step_loop", self.state.params, self.state.opt_state,
                    self.state.replace(params=None, opt_state=None),
                    dev, rngs[1:], jnp.asarray(lrs, jnp.float32))
            self.state = new_state
            self.micro_steps += k * self.gas
            self.global_steps += k
            self.global_samples += self.train_micro_batch_size_per_gpu() * \
                self.dp_world_size * k * self.gas
            self._last_metrics = metrics
            self.tput_timer.stop(global_step=True, steps=k)
            self._maybe_log_flops()
            if self.global_steps % self._config.steps_per_print == 0:
                self._log_train_step(float(jax.device_get(losses[-1])),
                                     metrics)
            if not sync:
                return losses
            with annotation("ds.train.sync"):
                return jax.device_get(losses)

    def eval_batch(self, batch, _retried=False):
        """Loss-only forward (no grads). Compression-aware training
        evaluates the COMPRESSED model (same strengths the train step
        uses) — validation tracks the network redundancy_clean will
        bake, not the raw fp weights. PLD evaluates at full depth
        (theta=1 semantics), matching the reference."""
        self._ensure_initialized(batch)
        if not hasattr(self, "_eval_fn"):
            loss_fn = self.loss_fn
            compute_dtype = self.compute_dtype
            comp = self._compression
            mat_sh = self._param_mat_sh \
                if getattr(self, "_injit_materialize", False) else None

            def ev(params, batch):
                if mat_sh is not None:
                    params = jax.tree.map(jax.device_put, params, mat_sh)
                p = jax.tree.map(
                    lambda x: x.astype(compute_dtype)
                    if x.dtype == jnp.float32 and compute_dtype != jnp.float32
                    else x, params)
                if isinstance(batch, dict) and "_ds_comp" in batch:
                    batch = dict(batch)
                    p = comp.apply(p, batch.pop("_ds_comp"))
                return loss_fn(p, batch, None)

            self._eval_fn = jax.jit(ev)
        dev_batch = self._put_batch(batch)
        if self._compression is not None:
            vec = self._compression.strength_vector(self.global_steps)
            if np.any(vec):
                assert isinstance(dev_batch, dict)
                dev_batch = dict(dev_batch)
                dev_batch["_ds_comp"] = jnp.asarray(vec)
        try:
            return jax.block_until_ready(self._eval_fn(
                self._materialize_params(self._live_state().params),
                dev_batch))
        except jax.errors.JaxRuntimeError as e:
            if _retried or not self._fallback_to_eager_streaming(e):
                raise
            return self.eval_batch(batch, _retried=True)

    # ------------------------------------------------------------------- io
    def deepspeed_io(self, dataset, collate_fn=None, route="train"):
        de = self._config.data_efficiency or {}
        ds_cfg = de.get("data_sampling", {}) if de.get("enabled") else {}
        if route == "train" and ds_cfg.get("enabled") and \
                ds_cfg.get("curriculum_learning", {}).get("enabled"):
            # data-efficiency v2: difficulty-indexed curriculum sampling
            # (reference data_sampler.py:36, wired at engine.py:1561).
            # Single-controller JAX: the sampler emits the GLOBAL micro
            # batch (dp_rank 0 of 1); the jitted step shards it over the
            # data axis. Sampler state rides in the checkpoint for exact
            # mid-epoch resume.
            from deepspeed_tpu.runtime.data_pipeline.data_sampling import (
                CurriculumIndexLoader, DeepSpeedDataSampler)
            sampler = DeepSpeedDataSampler(
                de, one_epoch_total_samples=len(dataset),
                micro_batch_size=self.train_micro_batch_size_per_gpu()
                * self.dp_world_size,
                gradient_accumulation_steps=self.gas,
                drop_last=self._config.dataloader_drop_last)
            if self._data_sampler_state is not None:
                sampler.load_state_dict(self._data_sampler_state)
                self._data_sampler_state = None
            self._data_sampler = sampler
            return CurriculumIndexLoader(dataset, sampler,
                                         collate_fn=collate_fn)
        return DeepSpeedDataLoader(
            dataset,
            batch_size=self.train_micro_batch_size_per_gpu() * self.dp_world_size,
            collate_fn=collate_fn,
            drop_last=self._config.dataloader_drop_last)

    # ------------------------------------------------------------ checkpoint
    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True, async_save=False):
        """Reference layout (engine.py:2818): <dir>/<tag>/ + `latest` file.
        Each process writes only its own shards (reference per-rank
        ``*_optim_states.pt``); ``async_save`` drains to disk on a
        background thread (the Nebula-engine capability) — call
        ``wait_checkpoint()`` before relying on the files. The backend
        is pluggable (checkpoint/backend.py, reference
        checkpoint_engine.py:9): ``checkpoint_engine.type`` in the
        config swaps the native npz format for a custom engine."""
        assert self.state is not None, "nothing to save before first forward"
        if async_save and self._params_nvme:
            # state.params are live memmap views over the tier's NVMe
            # files; a background writer racing the next step's in-place
            # file rewrite would snapshot a torn mix of two steps
            logger.warning("async_save is unavailable with the NVMe "
                           "param tier (params are live file views); "
                           "saving synchronously")
            async_save = False
        tag = tag or f"global_step{self.global_steps}"
        path = os.path.join(save_dir, str(tag))
        client = dict(client_state or {})
        client.update({
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "global_samples": self.global_samples,
            "lr_scheduler": self.lr_scheduler.state_dict()
            if isinstance(self.lr_scheduler, LRScheduler) else None,
            "data_sampler": self._data_sampler.state_dict()
            if self._data_sampler is not None else None,
            "compression": self._compression.state_dict()
            if self._compression is not None else None,
        })
        self.wait_checkpoint()

        if self._offload is not None:
            self._join_offload()   # grads in flight mutate the snapshot
            # fp32 master + moments live host-side (reference per-rank
            # *_optim_states.pt). Written NOW, synchronously, THROUGH
            # the backend (the pluggable-engine seam — a Nebula-style
            # backend must see every artifact): the offload optimizer
            # mutates its buffers in place on the next step, and the
            # entry stream reads one leaf at a time, so the
            # ZeRO-Infinity tier never materializes a model-sized dict.
            if jax.process_index() == 0:
                os.makedirs(path, exist_ok=True)
                self.checkpoint_engine.save_aux(
                    path, "host_optim_states",
                    self._offload.iter_state_entries())

        def finalize():
            # save_state runs on_done on PROCESS 0 ONLY, after the
            # durability barrier — single writer for everything below
            if self._config.zero_config \
                    .stage3_gather_16bit_weights_on_model_save:
                # reference engine.py:754: emit one unpartitioned 16-bit
                # weights file next to the sharded checkpoint (shard files
                # are durable here — finalize runs after the barrier);
                # routed through the backend so a remote engine owns it
                self.checkpoint_engine.consolidate_16bit(
                    path, "weights_16bit.npz", dtype=np.float16)
            if save_latest:
                with open(os.path.join(save_dir, "latest"), "w") as f:
                    f.write(str(tag))
            self.checkpoint_engine.commit(tag)

        self.checkpoint_engine.create(tag)
        self._ckpt_writer = self.checkpoint_engine.save(
            path, self._live_state(), client, async_write=async_save,
            on_done=finalize)
        log_dist(f"saved checkpoint {path}", ranks=[0])
        return path

    def wait_checkpoint(self):
        """Join any in-flight async checkpoint write."""
        writer = getattr(self, "_ckpt_writer", None)
        if writer is not None:
            self._ckpt_writer = None  # a failed write must not wedge retries
            writer.wait()

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                        load_lr_scheduler_states=True, example_batch=None):
        self.wait_checkpoint()
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.exists(latest):
                logger.warning(f"no 'latest' file in {load_dir}; nothing loaded")
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        path = os.path.join(load_dir, str(tag))
        if self.state is None:
            batch = example_batch if example_batch is not None \
                else self._example_batch
            assert batch is not None, \
                "load_checkpoint before init needs example_batch"
            self._ensure_initialized(batch)
        self.state, client = self.checkpoint_engine.load(
            path, self.state, mesh=self.mesh)
        have_host_opt = False
        if self._offload is not None:
            with self.checkpoint_engine.load_aux(
                    path, "host_optim_states") as d:
                have_host_opt = d is not None
                if d is not None and load_optimizer_states:
                    # lazy mapping: load_state_dict pulls one entry at
                    # a time (the tier streams each straight to NVMe)
                    self._offload.load_state_dict(d)
            if have_host_opt and not load_optimizer_states:
                # params are authoritative: refresh the master from them
                from deepspeed_tpu.checkpoint.engine import param_leaf_names
                self._offload.init_master(
                    (np.asarray(jax.device_get(l))
                     for l in jax.tree.leaves(self.state.params)),
                    names=param_leaf_names(self.state.params))
        if self._params_nvme:
            if not have_host_opt:
                # checkpoint without host optimizer state: the restored
                # params are authoritative — rebuild the tier from them
                from deepspeed_tpu.checkpoint.engine import \
                    param_leaf_names
                self._offload.init_master(
                    (np.asarray(l)
                     for l in jax.tree.leaves(self.state.params)),
                    names=param_leaf_names(self.state.params))
            # the restore materialized plain arrays; re-point
            # state.params at the tier's (just-refreshed) memmap views
            # so dispatches stream from NVMe again
            self.state = self.state.replace(
                params=jax.tree_util.tree_unflatten(
                    self._param_treedef,
                    self._offload.param_tier.param_memmaps()))
        self.global_steps = client.get("global_steps", 0)
        self.micro_steps = client.get("micro_steps", 0)
        self.global_samples = client.get("global_samples", 0)
        if load_lr_scheduler_states and client.get("lr_scheduler") and \
                isinstance(self.lr_scheduler, LRScheduler):
            self.lr_scheduler.load_state_dict(client["lr_scheduler"])
        if client.get("data_sampler") is not None:
            # restore into the live sampler, or stash for the sampler a
            # later deepspeed_io() builds
            if self._data_sampler is not None:
                self._data_sampler.load_state_dict(client["data_sampler"])
            else:
                self._data_sampler_state = client["data_sampler"]
        if client.get("compression") is not None and \
                self._compression is not None:
            self._compression.load_state_dict(client["compression"])
        log_dist(f"loaded checkpoint {path}", ranks=[0])
        return path, client

    def load_universal_checkpoint(self, path, example_batch=None,
                                  load_optimizer_states=True):
        """Resume TRAINING from a universal checkpoint — per-param fp32
        fragments produced by ``ds_to_universal`` from either a native
        checkpoint or a foreign Megatron tp/pp one (reference
        universal_checkpoint.py:12 + reshape_3d_utils.py: re-slice any
        source partitioning for training resume). Each fragment is
        device_put straight onto the live leaf's sharding, so the
        current mesh/ZeRO stage needs no reshape logic; Adam moments
        load when the source carried them (else the optimizer starts
        fresh, reference load_universal semantics for param-only
        sources)."""
        from deepspeed_tpu.checkpoint.engine import param_leaf_names
        from deepspeed_tpu.checkpoint.universal import load_universal
        self.wait_checkpoint()   # an in-flight async writer reads the
        # live offload buffers this load mutates in place
        if self.state is None:
            batch = example_batch if example_batch is not None \
                else self._example_batch
            assert batch is not None, \
                "load_universal_checkpoint before init needs example_batch"
            self._ensure_initialized(batch)
        meta, frags, moments = load_universal(path)
        names = param_leaf_names(self.state.params)
        missing = [n for n in names if n not in frags]
        if missing:
            raise KeyError(
                f"universal checkpoint at {path} lacks fragments for "
                f"{missing[:5]}{'...' if len(missing) > 5 else ''} "
                f"(has {len(frags)} leaves)")
        leaves = jax.tree.leaves(self.state.params)
        treedef = jax.tree.structure(self.state.params)
        new_leaves = []
        for name, live in zip(names, leaves):
            frag = frags[name]
            if tuple(np.shape(frag)) != tuple(np.shape(live)):
                raise ValueError(
                    f"fragment {name} has shape {np.shape(frag)} but the "
                    f"live leaf is {np.shape(live)}")
            if self._offload is not None:
                new_leaves.append(frag)
            else:
                new_leaves.append(jax.device_put(
                    np.asarray(frag, jax.dtypes.canonicalize_dtype(
                        live.dtype)), live.sharding))
        if self._offload is not None:
            # masters refresh from the fragments; compute copies rebuild
            self._offload.init_master(iter(new_leaves), names=names)
            if self._params_nvme:
                self.state = self.state.replace(
                    params=jax.tree_util.tree_unflatten(
                        self._param_treedef,
                        self._offload.param_tier.param_memmaps()))
            else:
                put = [jax.device_put(
                    np.asarray(l, np.dtype(self.compute_dtype)
                               if self.compute_dtype != jnp.bfloat16
                               else "bfloat16"), s)
                    for l, s in zip(new_leaves, self._param_sh_flat)]
                self.state = self.state.replace(
                    params=jax.tree_util.tree_unflatten(
                        self._param_treedef, put))
            if load_optimizer_states and self._offload.nvme is not None:
                for i, n in enumerate(names):
                    if moments.get(n) is not None:
                        self._offload.nvme.writeback(
                            i, np.ascontiguousarray(moments[n][0]),
                            np.ascontiguousarray(moments[n][1]))
                self._offload.nvme.flush()
            elif load_optimizer_states and self._offload.moments:
                for i, n in enumerate(names):
                    if moments.get(n) is not None:
                        self._offload.moments[i][0][:] = \
                            moments[n][0].reshape(-1)
                        self._offload.moments[i][1][:] = \
                            moments[n][1].reshape(-1)
        else:
            params = jax.tree_util.tree_unflatten(treedef, new_leaves)
            opt_state = self.state.opt_state
            if load_optimizer_states and any(
                    m is not None for m in moments.values()):
                mu = jax.tree_util.tree_unflatten(
                    treedef, [moments[n][0] if moments.get(n) is not None
                              else np.zeros_like(frags[n])
                              for n in names])
                nu = jax.tree_util.tree_unflatten(
                    treedef, [moments[n][1] if moments.get(n) is not None
                              else np.zeros_like(frags[n])
                              for n in names])
                opt_state = self._inject_adam_moments(
                    opt_state, mu, nu,
                    count=int(meta.get("global_steps", 0)))
            self.state = self.state.replace(params=params,
                                            opt_state=opt_state)
        self.global_steps = int(meta.get("global_steps", 0))
        if self._offload is not None:
            # Adam bias correction must continue from the source's step
            # (t=1 would scale the loaded moments ~1/(1-beta) wrong)
            self._offload.step_count = self.global_steps
        if self.lr_scheduler is not None and \
                hasattr(self.lr_scheduler, "step"):
            # fast-forward the schedule to the restored step — a
            # universal source carries no scheduler state (it may come
            # from a different framework), but replaying warmup on a
            # converged model is strictly worse
            try:
                self.lr_scheduler.step(self.global_steps)
            except TypeError:   # client scheduler without increment arg
                for _ in range(self.global_steps):
                    self.lr_scheduler.step()
        log_dist(f"loaded universal checkpoint {path} "
                 f"({len(names)} fragments, source="
                 f"{meta.get('source', 'native')})", ranks=[0])
        return meta

    def _inject_adam_moments(self, opt_state, mu, nu, count=0):
        """Replace the ScaleByAdamState mu/nu trees (optax chain walk)
        and advance its bias-correction count, preserving shardings."""
        import optax

        def put_like(new, old):
            return jax.device_put(
                np.asarray(new, old.dtype),
                old.sharding if hasattr(old, "sharding") else None)

        found = [0]

        def walk(node):
            if isinstance(node, optax.ScaleByAdamState):
                found[0] += 1
                return node._replace(
                    count=jax.device_put(
                        jnp.asarray(count, node.count.dtype),
                        getattr(node.count, "sharding", None)),
                    mu=jax.tree.map(put_like, mu, node.mu),
                    nu=jax.tree.map(put_like, nu, node.nu))
            if isinstance(node, tuple) and not hasattr(node, "_fields"):
                return tuple(walk(c) for c in node)
            if hasattr(node, "_fields"):   # other NamedTuple states
                return type(node)(*(walk(c) for c in node))
            return node

        new = walk(opt_state)
        if not found[0]:
            logger.warning(
                "load_universal_checkpoint: the source carries Adam "
                "moments but no optax ScaleByAdamState was found in "
                "this optimizer's state (wrapped/custom optimizer?) — "
                "optimizer state starts FRESH")
            return opt_state
        if jax.tree.structure(new) == jax.tree.structure(opt_state):
            return new
        logger.warning(
            "load_universal_checkpoint: rebuilding the optimizer state "
            "around the loaded Adam moments changed its tree structure "
            "— moments DISCARDED, optimizer state starts FRESH")
        return opt_state

    # ------------------------------------------------------------------ misc
    def get_params(self):
        return self._live_state().params if self.state is not None else None

    def __call__(self, batch):
        return self.forward(batch)
