"""Cross-lower every Pallas kernel for the TPU platform on CPU.

``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic lowering with no chip, so a BlockSpec or an op the
lowering refuses (the GQA paged kernel's per-kv-head block, before it
was merged into the MHA kernel) fails HERE, not inside the scheduler's
containment on the chip.  The cases are ``benchmarks/kernel_check.py``'s
— the same list the chip compiles and compares numerically.  Lowering
is only the first gate: Mosaic's own compiler (scoped-VMEM limits,
layouts) runs in libtpu when the executable is built.
"""

import jax
import pytest

from benchmarks.kernel_check import CASES
from deepspeed_tpu import comm as dist


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_kernel_lowers_for_tpu(case):
    with dist.mesh_scope(None):   # a mesh another test left installed
        lowered = jax.jit(case.fn).trace(*case.specs()).lower(
            lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def test_flash_on_a_multi_device_mesh_lowers_under_shard_map():
    """GSPMD cannot partition a Mosaic kernel: a compiled (non-
    interpret) ``pallas_call`` in a jit over more than one device
    raises "Mosaic kernels cannot be automatically partitioned".  With
    a multi-device mesh active ``flash_attention`` must therefore run
    the kernel per shard under ``shard_map`` — fwd and bwd."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops.attention.flash import flash_attention
    from deepspeed_tpu.parallel.topology import make_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    mesh = make_mesh(MeshConfig(data=4, model=2))
    x = jax.ShapeDtypeStruct(
        (8, 256, 4, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, "model", None)))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False)
                       .astype(jnp.float32))
    with dist.mesh_scope(mesh):
        lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            x, x, x).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") >= 2
