"""Ahead-of-time compiles for the v5e of the benchmark cells' kernels at
their published widths, one case per file under aot/ (a later PR adds a
geometry by adding a file): the paged decode kernel at the Mistral-7B
cells' geometry, flash forward+backward at GPT-2 medium and XL head
geometry.  Nothing runs; a Mosaic or memory refusal fails here at no
chip time.  All in this one file, the topology inside a module-scoped
fixture (on-chip-measurement guide, section 2)."""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

AOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "aot")
CASES = sorted(f[:-5] for f in os.listdir(AOT) if f.endswith(".json"))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def paged_decode(c, spec):
    # the kernel itself, below the dispatch that asks the process-wide
    # mesh (which other tests of the same worker leave set to CPU devices)
    from deepspeed_tpu.ops.attention.decode import _paged_decode_pallas
    dt = jnp.dtype(c["dtype"])
    pool = (c["pages"], c["page_size"], c["kv_heads"], c["head_dim"])
    args = (spec((c["slots"], 1, c["heads"], c["head_dim"]), dt),
            spec(pool, dt), spec(pool, dt),
            spec((c["slots"], c["max_pages"]), jnp.int32),
            spec((c["slots"],), jnp.int32))

    def f(q, k, v, table, pos):
        return _paged_decode_pallas(
            q, k, v, table, pos, scale=c["head_dim"] ** -0.5,
            interpret=False, k_scale=None, v_scale=None)
    return f, args, 1


def flash_fwd_bwd(c, spec):
    from deepspeed_tpu.ops.attention.flash import (_pick_block,
                                                   flash_attention_with_lse)
    x = spec((c["batch"] * c["heads"], c["seq"], c["head_dim"]),
             jnp.dtype(c["dtype"]))

    def loss(q, k, v):
        o, _ = flash_attention_with_lse(
            q, k, v, causal=True, scale=c["head_dim"] ** -0.5,
            block=_pick_block(c["seq"]), interpret=False)
        return jnp.sum(o.astype(jnp.float32))
    # forward and backward kernels are both Mosaic custom calls
    return jax.grad(loss, argnums=(0, 1, 2)), (x, x, x), 2


KERNELS = {"paged_decode": paged_decode, "flash_fwd_bwd": flash_fwd_bwd}


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_the_v5e(one_chip, case):
    with open(os.path.join(AOT, case + ".json")) as f:
        c = json.load(f)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, args, min_calls = KERNELS[c["kernel"]](c, spec)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') >= min_calls
