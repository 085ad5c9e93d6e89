"""ZeRO-3 gather-at-use (runtime/zero/gather.py): under stage 3 on a
`data` axis larger than 1 a sharded parameter is all-gathered where a
matmul or a lookup uses it, its gradient is reduce-scattered in
float32, and the batch stays on `data` — on the 8 virtual CPU devices
of the test platform, where the SPMD partitioner runs as it does for
the chip.  Counts and shapes only: a CPU run says nothing of time.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, GPTConfig
from deepspeed_tpu.models.llama import Llama, llama_tiny
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.runtime import engine as engine_mod
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.runtime.zero import gather as zero_gather

SEQ, VOCAB, HIDDEN = 32, 256, 64


def _model(kind, dtype=jnp.float32, remat=True):
    if kind == "llama":
        return Llama(llama_tiny(remat=remat, dtype=dtype, max_seq_len=SEQ))
    return GPT2(GPTConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=2, num_heads=4,
        max_seq_len=SEQ, remat=remat, dtype=dtype,
        scan_layers=kind == "gpt2-scan"))


def _engine(kind, stage, mesh_shape, micro=2, dtype="float32",
            optimizer=None, **zero):
    n = int(np.prod(list(mesh_shape.values())))
    mesh = make_mesh(MeshConfig(**mesh_shape), devices=jax.devices()[:n])
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": optimizer or {
            "type": "AdamW", "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "bf16": {"enabled": dtype == "bfloat16"},
        # the tiny fixture's matrices are a few thousand elements: a
        # threshold of 1,000 plans them and persists norms and biases
        "zero_optimization": dict(
            {"stage": stage, "stage3_param_persistence_threshold": 1000},
            **zero),
        "mesh": mesh_shape, "steps_per_print": 10 ** 9}
    rng = np.random.default_rng(0)
    rows = micro * mesh_shape.get("data", 1)
    batches = [{"input_ids": rng.integers(0, VOCAB, (rows, SEQ))
                .astype(np.int32)} for _ in range(3)]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_model(kind, jnp.dtype(dtype)), config=cfg, mesh=mesh,
        example_batch=batches[0], seed=3)
    return engine, batches


def _per_collective(census):
    """(op, class, count, bytes, dtypes) rows of a census."""
    return [(op, cls, v["count"], v["bytes"], v["dtypes"])
            for op, by in census["per_op"].items() for cls, v in by.items()]


@pytest.mark.parametrize("kind", ["gpt2", "gpt2-scan", "llama"])
def test_stage3_step_gathers_weights_and_leaves_the_batch(kind):
    """The census of the compiled step: no all-to-all, no permute, no
    collective that moves anything of an activation's size; the
    all-gathers and the gradient reductions carry parameter shapes."""
    engine, batches = _engine(kind, 3, {"data": 4})
    engine.train_loop(batches[:1], sync=True)
    census = engine.collective_census()
    assert census["program"] == "step_loop"
    assert "all_to_all" not in census["per_op"], census
    assert "collective_permute" not in census["per_op"], census
    # the smallest thing an activation can be here: one chip's share of
    # one [batch, seq, hidden] tensor in float32... and nothing that is
    # no parameter's comes near it (scalars of the loss and the norm)
    activation = 2 * SEQ * HIDDEN * 4
    for key, moved in census["other_shapes"].items():
        dims = key[key.index("[") + 1:-1]
        elems = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        assert elems * 4 < activation // 8, (key, moved)
    rows = _per_collective(census)
    gathers = [r for r in rows if r[0] == "all_gather" and r[1] == "param"]
    assert gathers and gathers[0][2] >= 6, census
    # the CPU compiler leaves a reduce-scatter as all-reduce + slice;
    # the chip's fuses them (comm_ledger names that reduce_scatter)
    sums = [r for r in rows
            if r[0] in ("reduce_scatter", "all_reduce") and r[1] == "param"]
    assert sums and sum(r[3] for r in sums) > 0, census
    plan = census["gather_at_use"]
    assert plan["gathered_leaves"] >= 6      # scan stacks the layers
    assert plan["gathered_bytes"] > 0
    json.dumps(census)      # it is logged as JSON


@pytest.mark.parametrize("kind", ["gpt2", "gpt2-scan", "llama"])
def test_stage3_over_four_devices_trains_like_stage0_on_one(kind):
    """Three float32 steps: same losses, same parameters (the same
    casts, matmuls and AdamW; only the order of partial sums differs)."""
    e3, batches = _engine(kind, 3, {"data": 4}, micro=2)
    e0, _ = _engine(kind, 0, {"data": 1}, micro=8)
    assert e3._gather_plan is not None and e0._gather_plan is None
    l3 = [float(e3.train_loop([b], sync=True)[0]) for b in batches]
    l0 = [float(e0.train_loop([b], sync=True)[0]) for b in batches]
    np.testing.assert_allclose(l3, l0, atol=1e-5, rtol=0)
    for a, b in zip(jax.tree.leaves(e3.state.params),
                    jax.tree.leaves(e0.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=0)


class _NoScope:
    """``zero_gather.scope`` taken out: what the engine traced before
    there was a hook."""

    def __init__(self, plan):
        pass

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("stage,mesh_shape", [
    (1, {"data": 1}), (3, {"data": 1}), (2, {"data": 4}), (0, {"data": 4})])
def test_no_sharded_leaf_no_hook_same_program(stage, mesh_shape,
                                              monkeypatch):
    """On a one-device mesh and at stage <= 2 no leaf holds the `data`
    axis: no plan, and the lowered step is the text it is with the hook
    taken out of the engine (the guard for `gpt2-medium.train`)."""
    def lowered():
        engine, batches = _engine("gpt2", stage, mesh_shape)
        assert engine._gather_plan is None
        _, state, rest, dev, rng, lr = engine._step_probe_args(batches[0])
        return engine._step_gas1.lower(
            state.params, state.opt_state, rest, dev, rng, lr).as_text()

    with_hook = lowered()
    monkeypatch.setattr(engine_mod, "zero_gather_scope", _NoScope)
    assert lowered() == with_hook
    assert "sharding_constraint" not in with_hook.lower() or \
        mesh_shape["data"] > 1


def test_gathered_spec_keeps_model_axis_and_small_leaves_persist():
    """`mesh {"data": 2, "model": 2}`: a leaf is gathered into its
    stage-2 spec (the tensor-parallel axes alone), and what stage 3 left
    replicated is not in the plan."""
    engine, batches = _engine("gpt2", 3, {"data": 2, "model": 2})
    plan = engine._gather_plan
    assert plan
    specs = {"/".join(k): v for k, v in plan.leaves.items()}
    rest, use, shape = specs["h_0/mlp/fc_in/kernel"]
    assert shape == (HIDDEN, 4 * HIDDEN)
    assert "data" in rest and "model" in rest, rest
    assert "data" not in use and "model" in use, use
    for rest, use, _ in plan.leaves.values():
        assert "data" in rest and "data" not in use
        assert [a for a in rest if a not in (None, "data")] == \
            [a for a in use if a is not None]
    flat = jax.tree_util.tree_flatten_with_path(engine.state.params)[0]
    small = ["/".join(zero_gather._path_key(p)) for p, leaf in flat
             if leaf.size < 1000]
    assert small and not set(small) & set(specs), small
    # the at-rest specs are untouched: still what the state is pinned to
    for (path, leaf), spec in zip(flat, jax.tree.leaves(
            engine.param_pspecs, is_leaf=lambda x: isinstance(x, P))):
        assert leaf.sharding.is_equivalent_to(
            NamedSharding(engine.mesh, spec), leaf.ndim), path
    losses = [float(engine.train_loop([b], sync=True)[0]) for b in batches]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0] + 0.05
    census = engine.collective_census()
    # tensor parallelism reshards heads over `model` as it always did;
    # nothing but parameters crosses `data`
    # (a few bias-sized rows apart: all of it together is less than one
    # chip's share of one residual-stream tensor)
    for op in ("all_to_all", "collective_permute"):
        other = census["per_op"].get(op, {}).get("other", {"axes": {}})
        over_data = sum(b for a, b in other["axes"].items() if "data" in a)
        assert over_data < 2 * SEQ * HIDDEN * 4, census
    assert census["gather_at_use"]["gathered_leaves"] >= 8


def test_weight_gradient_reaches_the_reduce_scatter_in_float32():
    """bf16 compute: each chip's partial weight gradient is a float32
    dot output, constrained to the at-rest spec as float32 (the
    reduce-scatter), and only then given the leaf's dtype; the weight
    itself is gathered as bf16."""
    mesh = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
    shapes = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32)}
    plan = zero_gather.GatherPlan(
        mesh, shapes, {"w": P("data", None)}, {"w": P(None, None)})
    at_rest = NamedSharding(mesh, P("data", None))
    at_use = NamedSharding(mesh, P(None, None))

    def loss(w, x):
        y = plan.einsum("abk,kn->abn", x, w.astype(jnp.bfloat16), ("w",))
        return jnp.sum(y.astype(jnp.float32))

    w = jnp.ones((64, 128), jnp.float32)
    x = jnp.ones((8, 16, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(w, x)

    def eqns(jp):
        for e in jp.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    pinned = [(e.params["sharding"], e.invars[0].aval.dtype,
               e.invars[0].aval.shape)
              for e in eqns(jaxpr.jaxpr) if e.primitive.name ==
              "sharding_constraint"]
    to_use = [d for s, d, sh in pinned if s == at_use and sh == (64, 128)]
    assert to_use and all(d == jnp.bfloat16 for d in to_use), pinned
    to_rest_grads = [d for s, d, sh in pinned
                     if s == at_rest and sh == (64, 128)
                     and d != jnp.bfloat16]
    assert to_rest_grads == [jnp.dtype("float32")], pinned
    dots = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name ==
            "dot_general" and e.outvars[0].aval.shape == (64, 128)]
    assert [e.outvars[0].aval.dtype for e in dots] == \
        [jnp.dtype("float32")], dots
    # and it is the gradient: ones @ ones over 8 * 16 rows
    g = jax.jit(jax.grad(loss))(w, x)
    assert g.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(g), 128.0)


def test_take_scatters_in_float32_and_matches_plain_indexing():
    mesh = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
    shapes = {"t": jax.ShapeDtypeStruct((50, 64), jnp.float32)}
    plan = zero_gather.GatherPlan(
        mesh, shapes, {"t": P(None, "data")}, {"t": P(None, None)})
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((50, 64)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 50, (8, 16)), jnp.int32)
    weight = jnp.asarray(rng.standard_normal((8, 16, 64)), jnp.float32)

    def planned(t):
        return jnp.sum(plan.take(t, ids, ("t",)) * weight)

    def plain(t):
        return jnp.sum(t[ids] * weight)

    np.testing.assert_allclose(jax.jit(planned)(table), plain(table),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.jit(jax.grad(planned))(table)),
        np.asarray(jax.grad(plain)(table)), atol=1e-5)
    assert plan.summary()["gathered_leaves"] == 1
    # a leaf the plan does not hold passes through
    assert plan.gather(weight, ("nope",)) is weight


def test_onebit_at_stage3_keeps_its_own_program():
    """The 1-bit path takes whole parameters into a shard_map of its
    own: the hook is never installed there, and stage 3 trains as it
    did."""
    engine, batches = _engine(
        "gpt2", 3, {"data": 4}, optimizer={
            "type": "OnebitAdam",
            "params": {"lr": 1e-3, "freeze_step": 2,
                       "comm_backend_name": "nccl"}})
    assert engine._compressed_axis == "data"
    losses = []
    for b in batches:
        loss = engine.forward(b)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert np.all(np.isfinite(losses))
    plan = engine._gather_plan
    assert plan is not None and plan.summary()["gathered_leaves"] == 0
    assert zero_gather.active() is None


def test_census_reads_the_compiled_step_without_compiling_again():
    engine, batches = _engine("gpt2", 3, {"data": 4})
    assert engine.collective_census() is None     # nothing ran yet
    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events.append(name))
    engine.train_loop(batches[:1], sync=True)
    counts = engine.train_compile_counts()
    assert counts["step_loop"] == 1
    events.clear()
    census = engine.collective_census()
    assert census is engine.collective_census()
    assert not [e for e in events if "backend_compile" in e], events
    assert engine.train_compile_counts() == counts
    engine.train_loop(batches[1:2], sync=True)
    assert engine.train_compile_counts() == counts
