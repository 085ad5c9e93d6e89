"""Ahead-of-time compiles for the v5e of the benchmark cells' kernels at
their published widths, one case per file under aot/ (a later PR adds a
geometry by adding a file): the paged decode kernel at the serving
cells' geometries, flash forward+backward at GPT-2 medium and XL head
geometry, the paged prefill kernel at the MiMo cell's.  A case's
``"kernel"`` is a name of KERNELS below or, for a kind of kernel this
file has never seen, ``"module:function"`` of a file the same PR puts
beside these tests (``kernel_builder``).  Nothing runs; a Mosaic or
memory refusal fails here at no chip time.  All in this one file, the
topology inside a module-scoped fixture (on-chip-measurement guide,
section 2)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HERE = os.path.dirname(os.path.abspath(__file__))
AOT = os.path.join(HERE, "aot")
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


def paged_prefill(c, spec):
    """A ``[rows, chunk]`` prefill dispatch over the rows' live pages;
    keys may be wider than values (``k_dim`` as the pool stores them,
    scaled by ``scale_dim``, the published key width)."""
    from deepspeed_tpu.ops.attention.paged_prefill import paged_prefill
    dt = jnp.dtype(c["dtype"])
    args = (spec((c["rows"], c["chunk"], c["heads"], c["k_dim"]), dt),
            spec((c["pages"], c["page_size"], c["kv_heads"], c["k_dim"]), dt),
            spec((c["pages"], c["page_size"], c["kv_heads"], c["v_dim"]), dt),
            spec((c["rows"], c["max_pages"]), jnp.int32),
            spec((c["rows"],), jnp.int32), spec((c["rows"],), jnp.int32))

    def f(q, k, v, table, start, count):
        return paged_prefill(q, k, v, None, None, table, start, count,
                             scale=c["scale_dim"] ** -0.5, interpret=False)
    return f, args, 1


KERNELS = {"paged_decode": paged_decode, "flash_fwd_bwd": flash_fwd_bwd,
           "paged_prefill": paged_prefill}


def kernel_builder(name, beside=HERE):
    """The builder a case names: a bare name is one of KERNELS;
    ``"module:function"`` is ``function`` of ``<module>.py`` in
    ``beside`` (the directory of these tests), loaded by file as
    ``run.Context.reference`` loads a configuration's reference — so the
    PR that brings a kind of kernel brings its ahead-of-time case as
    files, and edits nothing here."""
    mod, _, fn = name.rpartition(":")
    if not mod:
        return KERNELS[fn]
    spec = importlib.util.spec_from_file_location(
        mod, os.path.join(beside, mod + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, fn)


def load_case(path):
    with open(path) as f:
        return json.load(f)


def compile_case(c, sharding, beside=HERE):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    fn, args, min_calls = kernel_builder(c["kernel"], beside)(c, spec)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') >= min_calls


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_the_v5e(one_chip, case):
    compile_case(load_case(os.path.join(AOT, case + ".json")), one_chip)


def test_a_case_may_bring_its_kernel_as_module_function(one_chip):
    """data/paged_prefill.opt-tiny.json names a builder in
    data/aot_kernels_opt.py, a file KERNELS knows nothing of."""
    data = os.path.join(HERE, "data")
    c = load_case(os.path.join(data, "paged_prefill.opt-tiny.json"))
    assert ":" in c["kernel"] and c["kernel"].split(":")[0] not in KERNELS
    compile_case(c, one_chip, beside=data)


@pytest.mark.parametrize("name,error", [
    ("no_such_kernel", KeyError),
    ("aot_kernels_nowhere:f", OSError),
    ("aot_kernels_opt:no_such_function", AttributeError),
])
def test_a_kernel_that_resolves_nowhere_is_an_error(name, error):
    with pytest.raises(error):
        kernel_builder(name, beside=os.path.join(HERE, "data"))


def test_every_case_names_a_kernel_that_resolves():
    """Without the topology: every committed case resolves and its
    builder's function traces to an output at the case's shapes."""
    assert "paged_prefill.mimo-longctx" in CASES
    for case in CASES:
        c = load_case(os.path.join(AOT, case + ".json"))
        fn, args, min_calls = kernel_builder(c["kernel"])(
            c, jax.ShapeDtypeStruct)
        assert callable(fn) and args and min_calls >= 1
