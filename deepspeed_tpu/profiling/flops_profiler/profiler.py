"""FLOPS profiler on XLA HLO cost analysis.

Reference: ``deepspeed/profiling/flops_profiler/profiler.py:23`` (1294
LoC) — it monkey-patches ``torch.nn.functional`` ops with flop-counting
wrappers and walks the module tree. On TPU the compiler already knows the
exact operation counts: ``jit(fn).lower(...).compile().cost_analysis()``
reports flops/bytes for the *optimized* HLO, so the numbers include
fusion and rematerialization — more truthful than op-by-op counting.

Public surface mirrors the reference:
  * ``get_model_profile(model, input_shape | args)`` -> (flops, macs,
    params), with ``as_string`` formatting and a per-submodule table.
  * ``FlopsProfiler(model/engine)`` with start/stop/print hooks; the
    engine consults ``flops_profiler.profile_step`` and logs the step's
    flops + achieved TFLOPS at that step.

MACs are reported as flops/2 (XLA counts one fused multiply-add as two
flops; the reference counts MACs natively).
"""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.utils.logging import logger


def _num(x, suffix=""):
    for unit in ("", "K", "M", "G", "T", "P"):
        if abs(x) < 1000.0:
            return f"{x:.2f} {unit}{suffix}"
        x /= 1000.0
    return f"{x:.2f} E{suffix}"


# bf16 dense peak FLOP/s per chip, keyed by the ``device_kind`` string
# the device reports (the spellings jax's own
# ``_src/pallas/mosaic/tpu_info.py`` matches on).  Figures: Google Cloud
# TPU documentation ("TPU v5e": 197 TFLOP/s; "TPU v5p": 459; "TPU v4":
# 275).  "cpu" is a NOMINAL 1 TFLOP/s so MFU stays a defined,
# comparable number in the CPU tests — absolute CPU MFU values are
# meaningless, their TRENDS are not.
PEAK_FLOPS_PER_CHIP = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "cpu": 1e12,
}


def peak_flops_per_device(device=None):
    """Peak model flops of one device, for MFU accounting (live gauge:
    ``ResilientTrainer``; offline: ``bench.py``).  A TPU whose
    ``device_kind`` is not in the table is an error, never another
    chip's figure; non-TPU platforms get the nominal CPU row."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return PEAK_FLOPS_PER_CHIP["cpu"]
    try:
        return PEAK_FLOPS_PER_CHIP[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak-flops row for device_kind {device.device_kind!r}; "
            f"add it to PEAK_FLOPS_PER_CHIP with its source "
            f"(known: {sorted(PEAK_FLOPS_PER_CHIP)})") from None


def cost_analysis(fn, *args, static_argnums=(), **kwargs):
    """flops / bytes-accessed of `fn` compiled for the given args
    (concrete arrays or ShapeDtypeStructs)."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, static_argnums=static_argnums)
    compiled = jitted.lower(*args, **kwargs).compile()
    costs = compiled.cost_analysis()
    return {
        "flops": float(costs.get("flops", 0.0)),
        "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
    }


def params_count(params):
    return sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))


def get_model_profile(model, input_shape=None, args=None, kwargs=None,
                      params=None, as_string=False, print_profile=True,
                      detailed=True, output_file=None, top_modules=3,
                      seed=0):
    """Profile a flax module's forward (reference ``get_model_profile``).

    input_shape: shape of an int32 token batch (causal-LM contract), or
    pass explicit `args`/`kwargs` for the module's __call__. Returns
    (flops, macs, params) — formatted strings when ``as_string``.
    """
    if args is None:
        assert input_shape is not None, "need input_shape or args"
        args = (jnp.zeros(input_shape, jnp.int32),)
    kwargs = kwargs or {}
    if params is None:
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(seed), *args, **kwargs))
        params = variables.get("params", variables)
        params = jax.tree.map(
            lambda x: x.value if hasattr(x, "value") else x, params,
            is_leaf=lambda x: hasattr(x, "value"))

    def fwd(p, *a):
        return model.apply({"params": p}, *a, **kwargs)

    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), params)
    costs = cost_analysis(fwd, shapes, *args)
    total_flops = costs["flops"]
    total_params = params_count(params)
    macs = total_flops / 2.0

    lines = ["", "-" * 72,
             "DeepSpeed-TPU Flops Profiler (XLA HLO cost analysis)",
             "-" * 72,
             f"params:               {_num(float(total_params))}",
             f"fwd flops:            {_num(total_flops, 'FLOPs')}",
             f"fwd MACs:             {_num(macs, 'MACs')}",
             f"bytes accessed (fwd): {_num(costs['bytes_accessed'], 'B')}",
             f"flops per param:      {total_flops / max(total_params, 1):.1f}"]
    if detailed and isinstance(params, dict):
        lines += ["", "per-module parameters (depth 1):"]
        rows = sorted(((params_count(v), k) for k, v in params.items()),
                      reverse=True)
        for n, k in rows:
            pct = 100.0 * n / max(total_params, 1)
            lines.append(f"  {k:<28} {_num(float(n)):>12}  {pct:5.1f}%")
    report = "\n".join(lines)
    if print_profile:
        if output_file:
            with open(output_file, "w") as f:
                f.write(report + "\n")
        else:
            logger.info(report)
    if as_string:
        return (_num(total_flops, "FLOPs"), _num(macs, "MACs"),
                _num(float(total_params)))
    return total_flops, macs, total_params


class FlopsProfiler:
    """Engine-attached profiler (reference ``FlopsProfiler`` — start/stop
    around a step): the engine's compiled step executables are
    cost-analyzed once; wall-clock between start/stop gives achieved
    TFLOPS."""

    def __init__(self, engine, config=None):
        self.engine = engine
        self.config = config
        self._t0 = None
        self._dt = 0.0
        self.started = False

    def start_profile(self):
        import time
        self.started = True
        self._t0 = time.time()

    def stop_profile(self):
        import time
        if self._t0 is not None:
            self._dt = time.time() - self._t0
        self.started = False

    def get_total_flops(self):
        return self.engine.flops_profile()["flops_per_step"]

    def get_total_params(self):
        return self.engine.flops_profile()["params"]

    def get_total_duration(self):
        return self._dt

    def print_profile(self, loss=None, step=None):
        self.stop_profile()
        prof = self.engine.flops_profile()
        achieved = prof["flops_per_step"] / max(self._dt, 1e-9) / 1e12
        logger.info(
            f"flops_profiler: step={step} wall={self._dt * 1e3:.1f}ms "
            f"{prof['flops_per_step'] / 1e12:.3f} TFLOPs/step "
            f"({achieved:.2f} achieved TFLOPS), "
            f"{prof['params'] / 1e6:.1f}M params")


def profile_train_step(step_fn, *example_args):
    """Cost-analyze a jitted train-step callable with example args
    (arrays or ShapeDtypeStructs); returns {'flops', 'bytes_accessed'}."""
    return cost_analysis(step_fn, *example_args)
