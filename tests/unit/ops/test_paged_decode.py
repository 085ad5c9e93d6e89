"""The paged decode kernel (ops/attention/decode.py) held against the
jnp reference path of ``paged_decode_attention``.

The kernel runs in interpret mode on the CPU (``force_kernel=True``).
It walks the live pages of the ACTIVE slots and nothing else, so beside
parity on active rows the cases pin what it may not do: read a page an
inactive slot's table names, read past a slot's cursor, or leave an
inactive row anything but zeros.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm as dist
from deepspeed_tpu.models import GPT2, Llama, gpt2_tiny, llama_tiny
from deepspeed_tpu.models.falcon_h1 import FalconH1, falcon_h1_tiny
from deepspeed_tpu.models.nemotron_h import NemotronH, nemotron_h_tiny
from deepspeed_tpu.ops.attention.decode import (_live_pairs,
                                                paged_decode_attention)
from deepspeed_tpu.ops.quant.kv import paged_pool_layer
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.serving import ServingScheduler

SLOTS, MAXP, PS, D = 8, 4, 16, 32
PAGES = SLOTS * MAXP + 2          # page 0: the table's null page
POISON = PAGES - 1                # a page nothing live may name
CAP = MAXP * PS


def _inputs(rng, h, kv_h, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((SLOTS, 1, h, D)), jnp.float32)
    pools = {}
    for name, a in paged_pool_layer(PAGES, PS, kv_h, D, dtype).items():
        if a.dtype == jnp.int8:
            pools[name] = jnp.asarray(rng.integers(-127, 128, a.shape),
                                      jnp.int8)
        elif name.endswith("scale"):
            pools[name] = jnp.asarray(rng.uniform(0.005, 0.02, a.shape),
                                      jnp.float32)
        else:
            pools[name] = jnp.asarray(rng.standard_normal(a.shape), a.dtype)
    table = rng.permutation(PAGES - 2)[:SLOTS * MAXP] \
        .reshape(SLOTS, MAXP).astype(np.int32) + 1
    return q, pools, table


def _attend(q, pools, table, pos, active=None, mesh=None, **kw):
    """Under ``mesh`` (None: one device, whatever mesh an earlier test
    of this worker left installed)."""
    with dist.mesh_scope(mesh):
        return np.asarray(jax.jit(
            lambda q, pools, table, pos, active: paged_decode_attention(
                q, pools["k_pages"], pools["v_pages"], table, pos,
                k_scale=pools.get("k_scale"), v_scale=pools.get("v_scale"),
                active=active, **kw))(q, pools, jnp.asarray(table),
                                      jnp.asarray(pos, jnp.int32), active))


def _hide_dead_pages(pools, table, pos, active):
    """The table with every entry the kernel has no business reading —
    an inactive slot's whole row, an active slot's pages past its
    cursor — pointed at a page of NaNs."""
    live = np.arange(MAXP)[None, :] <= (np.asarray(pos) // PS)[:, None]
    live &= np.asarray(active)[:, None]
    poisoned = {n: a.at[POISON].set(jnp.nan) if a.dtype != jnp.int8
                else a for n, a in pools.items()}
    return poisoned, np.where(live, table, POISON).astype(np.int32)


ALL = np.ones(SLOTS, bool)
MIXED = np.array([1, 0, 1, 1, 0, 0, 1, 0], bool)
# cursors at k * page_size - 1, k * page_size, 0 and capacity - 1
EDGES = np.array([PS - 1, PS, 0, CAP - 1, 2 * PS - 1, 2 * PS, 5, CAP - PS])

CASES = {
    # name: (heads, kv heads, positions, active, pool dtype)
    "mha_group1": (4, 4, EDGES, ALL, jnp.float32),
    "gqa_group4": (8, 2, EDGES, ALL, jnp.float32),
    "gqa_group5": (10, 2, EDGES, ALL, jnp.float32),
    "gqa_group16": (32, 2, EDGES, ALL, jnp.float32),
    "inactive_mixed_in": (8, 2, EDGES, MIXED, jnp.float32),
    "inactive_mixed_in_group5": (10, 2, EDGES[::-1], ~MIXED, jnp.float32),
    "one_slot_active": (8, 2, EDGES, np.arange(SLOTS) == 5, jnp.float32),
    "all_slots_full": (8, 2, np.full(SLOTS, CAP - 1), ALL, jnp.float32),
    "all_slots_at_zero": (4, 4, np.zeros(SLOTS, int), ALL, jnp.float32),
    "int8_pool": (8, 2, EDGES, ALL, "int8"),
    "int8_pool_inactive_mixed_in": (8, 2, EDGES, MIXED, "int8"),
    "bf16_pool": (8, 2, EDGES, MIXED, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_reference_on_active_rows(case):
    """Active rows equal the reference's; inactive rows are zeros; and
    neither depends on what an inactive slot's table, or an active
    slot's entries past its cursor, point at."""
    h, kv_h, pos, active, dtype = CASES[case]
    q, pools, table = _inputs(np.random.default_rng(0), h, kv_h, dtype)
    ref = _attend(q, pools, table, pos)
    got = _attend(q, pools, table, pos, jnp.asarray(active),
                  force_kernel=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2 * np.abs(ref).max()
    assert np.abs(got - ref)[active].max() <= tol
    assert not got[~active].any()
    hidden, dead_table = _hide_dead_pages(pools, table, pos, active)
    again = _attend(q, hidden, dead_table, pos, jnp.asarray(active),
                    force_kernel=True)
    assert np.array_equal(again, got)


def test_no_mask_means_every_slot_active():
    q, pools, table = _inputs(np.random.default_rng(1), 10, 2)
    got = _attend(q, pools, table, EDGES, None, force_kernel=True)
    assert np.array_equal(
        got, _attend(q, pools, table, EDGES, jnp.asarray(ALL),
                     force_kernel=True))
    assert np.abs(got - _attend(q, pools, table, EDGES)).max() <= 1e-5


def test_all_slots_inactive_reads_nothing_and_writes_zeros():
    q, pools, table = _inputs(np.random.default_rng(2), 8, 2)
    nowhere = np.full_like(table, PAGES + 7)         # out of range
    got = _attend(q, {n: jnp.full_like(a, jnp.nan) for n, a in pools.items()},
                  nowhere, EDGES, jnp.zeros(SLOTS, bool), force_kernel=True)
    assert got.shape == q.shape and not got.any()


def test_inactive_rows_may_hold_out_of_range_page_ids():
    q, pools, table = _inputs(np.random.default_rng(3), 8, 2)
    want = _attend(q, pools, table, EDGES, jnp.asarray(MIXED),
                   force_kernel=True)
    wild = np.where(MIXED[:, None], table, PAGES + 1000).astype(np.int32)
    got = _attend(q, pools, wild, EDGES, jnp.asarray(MIXED),
                  force_kernel=True)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("active", [ALL, MIXED, np.zeros(SLOTS, bool)],
                         ids=["all", "mixed", "none"])
def test_the_work_list_is_the_live_pages_in_slot_order(active):
    table = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
    pair, pages, n = jax.jit(_live_pairs, static_argnums=3)(
        jnp.asarray(table), jnp.asarray(EDGES, jnp.int32),
        jnp.asarray(active), PS)
    want = [s * MAXP + k for s in range(SLOTS) if active[s]
            for k in range(EDGES[s] // PS + 1)]
    assert int(n[0]) == len(want)
    assert np.asarray(pair)[:len(want)].tolist() == want
    assert np.asarray(pages)[:len(want)].tolist() == want    # table = iota
    # the tail is never read, and still names entries of the table
    assert ((0 <= np.asarray(pair)) & (np.asarray(pair) < table.size)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, "int8"], ids=["f32", "int8"])
def test_shard_map_dispatch_shards_the_mask_with_the_slots(dtype):
    """On the CPU device mesh (model=2 x data=4) the kernel runs per
    shard: kv heads over ``model``, slots — q, table, positions AND the
    active mask — over ``data``; each shard lists its own slots' pages."""
    mesh = make_mesh(MeshConfig(data=4, model=2))
    q, pools, table = _inputs(np.random.default_rng(4), 8, 2, dtype)
    ref = _attend(q, pools, table, EDGES)
    got = _attend(q, pools, table, EDGES, jnp.asarray(MIXED), mesh=mesh,
                  force_kernel=True)
    whole = _attend(q, pools, table, EDGES, None, mesh=mesh,
                    force_kernel=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2 * np.abs(ref).max()
    assert np.abs(got - ref)[MIXED].max() <= tol
    assert not got[~MIXED].any()
    assert np.abs(whole - ref).max() <= tol


MODELS = {"gpt2": lambda: GPT2(gpt2_tiny()),
          "llama": lambda: Llama(llama_tiny()),
          "nemotron_h": lambda: NemotronH(nemotron_h_tiny()),
          "falcon_h1": lambda: FalconH1(falcon_h1_tiny())}


@pytest.mark.parametrize("model", sorted(MODELS))
def test_served_tokens_do_not_depend_on_the_path(model):
    """Two requests over three slots, so every decode step carries an
    idle slot and, once the shorter request ends, a finished one: their
    zero rows go on through the MLP / experts / sampler, and the tokens
    served are the reference path's.  The counter reads the share of
    the page table those steps needed."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 200, n).astype(np.int32) for n in (19, 5)]
    served = {}
    for mode in ("force", "reference"):
        engine = deepspeed_tpu.init_inference(
            MODELS[model](), dtype="float32", kv_cache_dtype="float32",
            mesh={"data": 1, "model": 1}, paged_kernel=mode)
        engine.init_params(seed=3)
        sched = ServingScheduler(engine, num_slots=3, num_pages=12,
                                 page_size=16, max_pages_per_slot=4,
                                 prefill_chunk=8)
        assert sched.health()["paged_attention"]["path"] == \
            ("kernel" if mode == "force" else "reference")
        reqs = [sched.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, (14, 5))]
        got = sched.run()
        served[mode] = [list(got[r.rid]) for r in reqs]
        share = sched.metrics.summary()["decode_live_page_share"]
        # 13 + 4 decode tokens: the long request's cursor crosses into
        # its third page at position 32, the short one stays in its first
        pages = sum((19 + j) // 16 + 1 for j in range(13)) + 4
        steps = sched.metrics.decode_steps
        assert share == round(pages / (steps * 3 * 4), 4)
    assert served["force"] == served["reference"]
