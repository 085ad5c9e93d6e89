"""QTensor-aware Dense layer — the serving-side "kernel-injected Linear".

Reference: the quantized Linear the GPU inference kernels swap in during
module injection (``module_inject/replace_module.py:138`` GroupQuantizer
+ ``csrc/transformer/inference/csrc/pt_binding.cpp`` int8 GEMM). The TPU
design keeps ONE module for both regimes: the param tree decides. A
float ``kernel`` leaf reproduces ``nn.Dense`` numerics bit-for-bit (same
promote_dtype + dot_general), and a :class:`QTensor` leaf routes through
the int8 path, so quantization is a pure tree transformation
(``quantize_tree``) with no module surgery.

Quantized matmul implementation is chosen at trace time:

* ``pallas`` — the tiled dequant-in-VMEM kernel (kernels.int8_matmul);
  the int8 weight streams from HBM, halving decode bandwidth (measured
  1.8x faster than the bf16 matmul at HBM-streaming decode shapes on
  v5e).
* ``xla`` — ``x @ dequant`` under jit. XLA materializes the bf16 weight
  (measured 2-4x slower than bf16 at decode), but every op is standard,
  so it partitions under SPMD sharding.
* ``auto`` (default) — pallas on a single TPU device, xla otherwise
  (pallas_call does not auto-partition under jit SPMD; multi-chip
  quantized serving takes the xla path until the kernel grows a
  custom_partitioning rule).
"""

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import errors as flax_errors

from deepspeed_tpu.ops.quant.quantizer import QTensor
from deepspeed_tpu.runtime.zero import gather as zero_gather


def _quant_impl(impl):
    if impl != "auto":
        return impl
    return "pallas" if (jax.default_backend() == "tpu"
                        and jax.device_count() == 1) else "xla"


def quant_matmul(x, qt, impl="auto"):
    """x [..., k] @ dequant(qt) -> [..., n], impl per module docstring."""
    from deepspeed_tpu.ops.quant.kernels import int8_matmul
    k = x.shape[-1]
    # a trailing partial group (k % scale rows != 0, or an explicit
    # group_size the rows don't tile) has no legal Pallas k-blocking —
    # the dequant-matmul kernel owns whole scale rows per k step.  Route
    # those tensors through the XLA dequant path instead of asserting
    # inside the kernel.
    trailing = k % qt.scale.shape[0] != 0 or (
        qt.group_size is not None and
        qt.group_size * qt.scale.shape[0] != k)
    if trailing or _quant_impl(impl) == "xla":
        return x @ qt.dequant().astype(x.dtype)
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    y = int8_matmul(x.reshape(m, x.shape[-1]), qt.q, qt.scale)
    return y.reshape(*lead, y.shape[-1])


def shaped_param(module, name, init_fn, shape, dtype):
    """``module.param(name, init_fn, shape, dtype)`` for an initializer
    whose output has the shape it is given, with the apply-time shape
    check made directly.  For a parameter that already exists flax
    re-traces ``init_fn`` under ``jax.eval_shape`` just to learn the
    shape it would have — a third of the Python trace time of a
    16-layer Llama program, paid again for every compiled signature
    (each prefill row bucket and decode horizon bucket) at every
    start-up, which no compile cache holds."""
    if not module.has_variable("params", name):
        return module.param(name, init_fn, shape, dtype)
    value = nn.meta.unbox(module.get_variable("params", name))
    # a QTensor's first leaf is its int8 payload, kernel-shaped
    got = jnp.shape(jax.tree_util.tree_leaves(value)[0])
    if got != shape:
        raise flax_errors.ScopeParamShapeError(
            name, module.scope.path_text, got, shape)
    return value


class QDense(nn.Module):
    """Drop-in ``nn.Dense`` with a QTensor fast path (see module doc)."""

    features: int
    use_bias: bool = True
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()
    quant_impl: str = "auto"

    @nn.compact
    def __call__(self, inputs):
        kernel = shaped_param(self, "kernel", self.kernel_init,
                              (jnp.shape(inputs)[-1], self.features),
                              self.param_dtype)
        bias = shaped_param(self, "bias", self.bias_init, (self.features,),
                            self.param_dtype) if self.use_bias else None
        if isinstance(kernel, QTensor):
            x = inputs.astype(self.dtype or kernel.dtype)
            y = quant_matmul(x, kernel, impl=self.quant_impl)
            if bias is not None:
                y = y + jnp.asarray(bias, y.dtype)
            return y
        # float path: exactly nn.Dense (promote + dot_general + bias)
        inputs, kernel, bias = nn.dtypes.promote_dtype(
            inputs, kernel, bias, dtype=self.dtype)
        plan = zero_gather.active()
        if plan is not None:
            # ZeRO-3 gather-at-use: the kernel comes sharded over `data`
            # and is gathered for this matmul alone (same contraction)
            lead = "abcdefgh"[:inputs.ndim - 1]
            y = plan.einsum(f"{lead}k,kn->{lead}n", inputs, kernel,
                            self.path + ("kernel",))
            if bias is not None:
                bias = plan.gather(bias, self.path + ("bias",))
        else:
            y = jax.lax.dot_general(inputs, kernel,
                                    (((inputs.ndim - 1,), (0,)), ((), ())))
        if bias is not None:
            y = y + jnp.reshape(bias, (1,) * (y.ndim - 1) + (-1,))
        return y
