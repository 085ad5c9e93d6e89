"""Sharded multi-chip serving: the paged KV cache and every serving
primitive over the device mesh (deepspeed_tpu/serving/sharding.py).

The oracle: serving output on a forced multi-device CPU mesh (the
conftest's 8 virtual devices — the launcher-test mechanism) is
TOKEN-EXACT vs the 1-device engine, across mesh shapes
{model=1 x data=8, model=2 x data=4, model=4 x data=2}, including
prefix-cache hits, spec-decode verify rounds and forced eviction
on-mesh.  Sharding may only ever change WHERE bytes live: KV pools
shard kv-heads over ``model``, slot carries / token blocks / the page
table shard slots over ``data``, page ids stay global so the host-side
page bookkeeping (PagedKVManager / PrefixCache) is mesh-agnostic.

Every scheduler here shares the SAME (slots, pages, page_size,
max_pages, chunk) constants, so jit signatures differ only by horizon/K
bucket — the compile-count assertions bound the whole module (the
test_serving.py scheme), proving mesh churn adds no per-step
recompiles.
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
from deepspeed_tpu.models.llama import Llama, llama_tiny
from deepspeed_tpu.serving import ServingScheduler
from deepspeed_tpu.serving.sharding import (ServingShardingConfig,
                                            pool_bytes_per_device)

# slots divisible by every swept data-axis size {8, 4, 2}, so the slot
# family actually shards on every shape (an indivisible count degrades
# to replicated by design — covered separately)
CFG = dict(num_slots=8, num_pages=32, page_size=16, max_pages_per_slot=4,
           prefill_chunk=8)

MESH_SHAPES = [(1, 8), (2, 4), (4, 2)]      # (model, data)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual CPU mesh")


def _mesh_engine(model_ax, data_ax):
    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32",
        kv_cache_dtype="float32",
        tensor_parallel={"tp_size": model_ax},
        mesh={"data": data_ax, "model": model_ax})
    eng.init_params()
    return eng


@pytest.fixture(scope="module")
def engines():
    """One engine per mesh shape, built lazily and shared across the
    module (each shape owns a full compiled-signature set; rebuilding
    per test would dominate the suite's wall budget)."""
    cache = {}

    def get(model_ax, data_ax):
        if (model_ax, data_ax) not in cache:
            cache[(model_ax, data_ax)] = _mesh_engine(model_ax, data_ax)
        return cache[(model_ax, data_ax)]

    return get


@pytest.fixture(scope="module")
def ref(engines):
    """The 1-device reference engine (the token-exactness oracle)."""
    return engines(1, 1)


def _oracle(engine, prompts, max_new):
    return [
        [int(t) for t in
         engine.generate(p[None], max_new_tokens=m, do_sample=False)[
             0, len(p):]]
        for p, m in zip(prompts, max_new)]


@pytest.fixture(scope="module")
def workload(ref):
    """Mixed-length prompts (3 distinct lengths, more requests than
    comfortably fit) + their 1-device greedy oracle, computed once."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 11, 7, 5, 11, 7)]
    max_new = [8, 6, 10, 5, 7, 9]
    return prompts, max_new, _oracle(ref, prompts, max_new)


# ------------------------------------------------------ the mesh oracle


@pytest.mark.parametrize("model_ax,data_ax", MESH_SHAPES)
def test_mesh_serving_token_exact(engines, workload, model_ax, data_ax):
    """Serving on each mesh shape emits exactly the 1-device greedy
    stream; the KV pools are REALLY sharded (per-device bytes =
    total / model-axis size, the pool spec names the mesh axis) and the
    compile count stays at one fused-decode signature per horizon
    bucket."""
    prompts, max_new, want = workload
    eng = engines(model_ax, data_ax)
    # audit_every=1: page bookkeeping is mesh-agnostic by contract, so
    # the PR-11 refcount auditor must pass identically on-mesh
    sched = ServingScheduler(eng, decode_horizon_steps=8, audit_every=1,
                             **CFG)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    for r, w in zip(reqs, want):
        assert got[r.rid] == w, \
            f"mesh {model_ax}x{data_ax} diverged for rid={r.rid}"
    assert sched.kv.pool.pages_in_use == 0

    # the pools really shard: each device holds 1/model of every page
    total = sum(int(x.nbytes) for x in jax.tree.leaves(sched.pools))
    per_dev = pool_bytes_per_device(sched.pools)
    assert per_dev * model_ax == total
    axes = eng._serving_shardings().describe()
    assert axes["kv_heads"] == ("model" if model_ax > 1 else None)
    assert axes["slots"] == ("data" if data_ax > 1 else None)
    assert axes["pages"] is None, "page ids must stay global"
    if model_ax > 1:
        specs = {str(x.sharding.spec) for x in jax.tree.leaves(sched.pools)}
        assert all("model" in s for s in specs), specs

    # mesh churn adds no per-step recompiles: one fused-decode
    # signature per horizon bucket actually used, one prefill
    # signature per row bucket actually used
    assert 1 <= eng.serving_decode_multi_compile_count() <= \
        len(sched.horizon_buckets)
    assert 1 <= eng.serving_prefill_compile_count() <= \
        len(sched.prefill_row_buckets)

    # operators can see the topology: health() reports the shape and
    # the per-device KV-pool footprint
    h = sched.health()
    assert h["mesh"].get("model", 1) == model_ax
    assert h["mesh"].get("data", 1) == data_ax
    assert h["kv_pool_bytes_per_device"] == per_dev
    assert h["serving_axes"] == axes


@pytest.mark.parametrize("model_ax,data_ax", [
    pytest.param(1, 8, marks=pytest.mark.slow),
    (2, 4),
    pytest.param(4, 2, marks=pytest.mark.slow),
])
def test_mesh_prefix_cache_and_spec_decode_token_exact(
        engines, ref, model_ax, data_ax):
    """The full serving composition ON-MESH: radix prefix-cache
    donation + full-page hit + COW partial hit, and ngram spec-decode
    verify rounds with KV rollback — output token-exact vs the
    1-device engine, cache/verify machinery demonstrably engaged, and
    the verify compile count bounded by the spec-K bucket set.  The
    (2, 4) shape (both axes sharded) rides tier-1; the single-axis
    shapes ride the slow lane (PR-1 policy)."""
    rng = np.random.default_rng(7)
    donor = rng.integers(0, 256, 43).astype(np.int32)
    hit = donor.copy()                       # 2 full pages + COW tail
    spec_p = rng.integers(0, 256, 9).astype(np.int32)
    prompts, max_new = [donor, hit, spec_p], [6, 5, 30]
    want = _oracle(ref, prompts, max_new)

    eng = engines(model_ax, data_ax)
    sched = ServingScheduler(eng, decode_horizon_steps=8,
                             prefix_cache=True, spec_decode="ngram",
                             spec_k=4, **CFG)
    # wave 1: donor warms the cache; long greedy stream engages ngram
    r0 = sched.submit(donor, max_new_tokens=max_new[0])
    r2 = sched.submit(spec_p, max_new_tokens=max_new[2])
    got = sched.run()
    assert got[r0.rid] == want[0]
    assert got[r2.rid] == want[2], \
        f"spec-decode stream diverged on mesh {model_ax}x{data_ax}"
    assert sched.metrics.spec_dispatches > 0, "spec never engaged"
    assert sched.prefix_cache.cached_pages > 0, "donation must land"

    # wave 2: the identical prompt hits cached pages mapped READ-ONLY
    # into the slot table (+ a COW copy for the partial tail) — the
    # shared-page attach and the on-device page copy both run sharded
    r1 = sched.submit(hit, max_new_tokens=max_new[1])
    got = sched.run()
    assert got[r1.rid] == want[1], "prefix-hit stream diverged on mesh"
    assert r1.cached_prefix_tokens > 0, "prefix cache missed a clean hit"
    assert eng.serving_verify_compile_count() <= len(sched.spec_k_buckets)
    assert eng.serving_page_copy_compile_count() <= 1
    sched.prefix_cache.evict(10 ** 6)
    assert sched.kv.pool.pages_in_use == 0


def test_mesh_forced_eviction_token_exact(engines, ref):
    """Recompute preemption under pool pressure ON-MESH: hostage pages
    force eviction mid-stream; the evicted request's re-prefill and the
    survivors stay token-exact (page bookkeeping is host-side and
    mesh-agnostic, so the eviction path never consults the mesh)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 9, 5)]
    max_new = [40, 40, 40]
    want = _oracle(ref, prompts, max_new)

    eng = engines(2, 4)
    sched = ServingScheduler(eng, decode_horizon_steps=8, **CFG)
    hostage = sched.kv.pool.allocate(24)     # 8 pages left for 10 needed
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    for r, w in zip(reqs, want):
        assert got[r.rid] == w, "on-mesh eviction diverged"
    assert sched.metrics.preemptions >= 1, \
        "pressure probe never forced an eviction"
    sched.kv.pool.free(hostage)
    assert sched.kv.pool.pages_in_use == 0


# -------------------------------------------------- validation + edges


def test_model_axis_must_divide_num_heads():
    """Construction-time mesh validation: model=8 over gpt2-tiny's 4
    heads is intra-head tensor parallelism — the exact shape the legacy
    SPMD partitioner silently drifts on (~1e-2, the seed-era tp=8
    failure).  It must now fail LOUDLY, naming the axis and count."""
    with pytest.raises(ValueError, match=r"model.*8.*num_heads=4"):
        deepspeed_tpu.init_inference(
            model=GPT2(gpt2_tiny()), dtype="float32",
            tensor_parallel={"tp_size": 8}, mesh={"data": 1, "model": 8})


def test_model_axis_must_divide_num_kv_heads():
    """GQA: llama-tiny has 4 query heads but 2 KV heads — model=4
    passes weight sharding yet CANNOT shard the KV pools' head dim.
    The serving path must refuse with a ValueError naming the kv head
    count, not drift."""
    eng = deepspeed_tpu.init_inference(
        model=Llama(llama_tiny(num_layers=2)), dtype="float32",
        kv_cache_dtype="float32",
        tensor_parallel={"tp_size": 4}, mesh={"data": 2, "model": 4})
    eng.init_params()
    with pytest.raises(ValueError, match=r"model.*num_kv_heads=2"):
        eng.init_paged_cache(num_pages=8, page_size=16)


def test_uneven_slot_count_degrades_to_replicated(engines, ref):
    """A slot count the data axis cannot divide evenly (jax requires
    dim % shards == 0) degrades the SLOT family to replicated instead
    of crashing — a toy server on a big mesh keeps working, and the
    resolved axis map says so."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, 5).astype(np.int32) for _ in range(2)]
    want = _oracle(ref, prompts, [4, 4])

    eng = engines(1, 8)
    sched = ServingScheduler(eng, decode_horizon_steps=8, num_slots=3,
                             num_pages=16, page_size=16,
                             max_pages_per_slot=4, prefill_chunk=8)
    reqs = [sched.submit(p, max_new_tokens=4) for p in prompts]
    got = sched.run()
    assert [got[r.rid] for r in reqs] == want
    assert eng._serving_shardings().describe()["slots"] is None
    # the operator-facing snapshot must report the DEGRADED resolution
    # (mesh_info resolves against the scheduler's live num_slots), not
    # echo the rule table
    assert sched.health()["serving_axes"]["slots"] is None
    # restore the divisible resolution for any later test on this
    # shared engine (the engine re-resolves by live slot count)
    eng._serving_shardings(num_slots=CFG["num_slots"])


def test_sharding_config_rules_are_pure_config(engines):
    """The logical-axis rule table is data, not code: a custom rule set
    (e.g. a replicated-weights topology) resolves without touching the
    engine — the ICI x DCN path later is exactly this kind of config
    change."""
    eng = engines(2, 4)
    custom = ServingShardingConfig(rules=(("kv_heads", None),
                                          ("slots", "data"),
                                          ("pages", None),
                                          ("vocab", None)))
    shd = custom.resolve(eng.mesh, num_kv_heads=4, num_slots=8)
    assert shd.describe() == {"kv_heads": None, "slots": "data",
                              "pages": None, "vocab": None}
    # and the default rules validate kv-head divisibility as a hard
    # error naming axis + count
    with pytest.raises(ValueError, match=r"model.*num_kv_heads=3"):
        ServingShardingConfig().resolve(eng.mesh, num_kv_heads=3)


# ------------------------- shard_map'd Pallas paged kernel (ROADMAP 4)
#
# On any multi-device mesh the paged Pallas kernel used to be bypassed
# for the jnp gather reference (GSPMD cannot partition a pallas_call);
# it now runs PER-SHARD under jax.shard_map — kv pools sharded
# [pages, ps, KV_H/model, dim], q/page-table/positions over `data`,
# page ids global so per-shard BlockSpecs need no new indexing, and GQA
# pools run the per-kv-head BlockSpec kernel grouped (never expanded).
# These tests pin the whole dispatch with paged_kernel="force"
# (interpret mode — the CPU CI spelling of the TPU kernel): the
# shard_mapped kernel is the ACTIVE path (health says so), token-exact
# vs generate() / the jnp-reference engine under eviction and prefix
# sharing, with compile counts inside the existing bucket sets.

KCFG = dict(num_slots=8, num_pages=24, page_size=16, max_pages_per_slot=4,
            prefill_chunk=8)
# (2, 4) — both axes sharded, the strongest shape — rides tier-1; the
# single-axis 1x8 variants ride the slow lane (the PR-6 policy, and
# the suite is at ~815s of its 870s wall budget on this rig)
KERNEL_MESHES = [pytest.param(1, 8, marks=pytest.mark.slow), (2, 4)]


@pytest.fixture(scope="module")
def kernel_engines():
    """Forced-kernel engines per (mesh shape, model kind, kv dtype),
    built lazily (each owns its compiled interpret-kernel signatures)."""
    cache = {}

    def get(model_ax, data_ax, kind="gpt2", kv_dtype="float32"):
        key = (model_ax, data_ax, kind, kv_dtype)
        if key not in cache:
            module = GPT2(gpt2_tiny()) if kind == "gpt2" \
                else Llama(llama_tiny())
            eng = deepspeed_tpu.init_inference(
                model=module, dtype="float32", kv_cache_dtype=kv_dtype,
                tensor_parallel={"tp_size": model_ax},
                mesh={"data": data_ax, "model": model_ax},
                paged_kernel="force")
            eng.init_params()
            cache[key] = eng
        return cache[key]

    return get


@pytest.fixture(scope="module")
def llama_ref():
    """1-device llama (GQA) oracle engine."""
    eng = deepspeed_tpu.init_inference(
        model=Llama(llama_tiny()), dtype="float32",
        kv_cache_dtype="float32", tensor_parallel={"tp_size": 1},
        mesh={"data": 1, "model": 1})
    eng.init_params()
    return eng


def _kernel_workload(oracle_engine):
    """Donor (2 full pages + tail) + two long streams whose decode
    outgrows the squeezed pool, plus the 1-device greedy oracle."""
    rng = np.random.default_rng(11)
    donor = rng.integers(0, 256, 37).astype(np.int32)
    others = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 9)]
    prompts = [donor] + others
    max_new = [6, 26, 26]
    return donor, prompts, max_new, _oracle(oracle_engine, prompts,
                                            max_new)


def _run_kernel_oracle(eng, oracle_engine, kv_dtype="float32"):
    """The acceptance oracle for one forced-kernel mesh engine: health
    reports the shard_mapped kernel as the ACTIVE path, serving is
    token-exact vs the 1-device oracle scheduler-for-scheduler under
    hostage-page eviction AND a full-page prefix hit, and the compile
    counts stay inside the bucket sets."""
    donor, prompts, max_new, want = _kernel_workload(oracle_engine)
    # (no audit_every here: the hostage pages below are deliberately
    # unowned allocations the refcount auditor would rightly flag)
    sched = ServingScheduler(eng, decode_horizon_steps=4,
                             prefix_cache=True, **KCFG)
    pa = sched.health()["paged_attention"]
    assert pa["path"] == "kernel", pa
    assert pa["dispatch"] == "shard_map", pa

    hostage = sched.kv.pool.allocate(19)     # 5 pages left, 8 needed
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    for r, w in zip(reqs, want):
        assert got[r.rid] == w, f"kernel path diverged for rid={r.rid}"
    assert sched.metrics.preemptions >= 1, \
        "hostage pages never forced an eviction through the kernel path"
    sched.kv.pool.free(hostage)

    # wave 2: the donor's pages are cached — the identical prompt hits
    # full pages mapped read-only, and the kernel attends through the
    # shared chain
    r2 = sched.submit(donor.copy(), max_new_tokens=5)
    got = sched.run()
    assert got[r2.rid] == _oracle(oracle_engine, [donor], [5])[0], \
        "prefix-hit stream diverged on the kernel path"
    assert r2.cached_prefix_tokens > 0, "prefix cache missed a clean hit"

    assert 1 <= eng.serving_decode_multi_compile_count() <= \
        len(sched.horizon_buckets)
    assert 1 <= eng.serving_prefill_compile_count() <= \
        len(sched.prefill_row_buckets)
    sched.prefix_cache.evict(10 ** 6)
    assert sched.kv.pool.pages_in_use == 0
    return sched


@pytest.mark.parametrize("model_ax,data_ax", KERNEL_MESHES)
def test_shard_map_kernel_mha_token_exact(kernel_engines, ref,
                                          model_ax, data_ax):
    """MHA (gpt2): a sharded MHA model sees grouped heads per shard
    once model > 1 — the kernel must stay exact either way."""
    _run_kernel_oracle(kernel_engines(model_ax, data_ax, "gpt2"), ref)


@pytest.mark.parametrize("model_ax,data_ax", KERNEL_MESHES)
def test_shard_map_kernel_gqa_token_exact(kernel_engines, llama_ref,
                                          model_ax, data_ax):
    """GQA (llama, 4 q heads over 2 kv heads): the per-kv-head
    BlockSpec kernel runs grouped — on the model=2 shape each shard
    holds ONE kv head and its 2-query-head group."""
    _run_kernel_oracle(kernel_engines(model_ax, data_ax, "llama"),
                       llama_ref)


@pytest.fixture(scope="module")
def llama_int8_ref_tokens(llama_ref):
    """int8 oracle: the same workload served through a 1-DEVICE int8
    scheduler on the jnp reference path.  Quantization happens at
    paged_write with mesh-agnostic math, so the sharded kernel must
    reproduce these tokens exactly (fp32 generate() is NOT the oracle
    here — int8 legitimately diverges from it; test_kv_quant pins that
    distance)."""
    eng = deepspeed_tpu.init_inference(
        model=Llama(llama_tiny()), dtype="float32",
        kv_cache_dtype="int8", tensor_parallel={"tp_size": 1},
        mesh={"data": 1, "model": 1})
    eng.init_params()
    donor, prompts, max_new, _ = _kernel_workload(llama_ref)
    sched = ServingScheduler(eng, decode_horizon_steps=4,
                             prefix_cache=True, **KCFG)
    hostage = sched.kv.pool.allocate(19)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    toks = [got[r.rid] for r in reqs]
    sched.kv.pool.free(hostage)
    r2 = sched.submit(donor.copy(), max_new_tokens=5)
    got = sched.run()
    return toks, got[r2.rid]


@pytest.mark.slow   # ~14s/shape; mha+gqa above keep the shard_map
# dispatch in tier-1, and int8 parity rides test_kv_quant's mesh leg
@pytest.mark.parametrize("model_ax,data_ax", KERNEL_MESHES)
def test_shard_map_kernel_int8_token_exact(kernel_engines,
                                           llama_ref,
                                           llama_int8_ref_tokens,
                                           model_ax, data_ax):
    """int8 KV: the quantized kernel variant (per-row scale blocks
    riding the same prefetched page-table index map, dequant in VMEM)
    runs shard_mapped and token-exact vs the 1-device int8 jnp
    reference — under eviction and a prefix hit, scale pools moving
    with their pages."""
    want, want_hit = llama_int8_ref_tokens
    eng = kernel_engines(model_ax, data_ax, "llama", kv_dtype="int8")
    donor, prompts, max_new, _ = _kernel_workload(llama_ref)
    sched = ServingScheduler(eng, decode_horizon_steps=4,
                             prefix_cache=True, **KCFG)
    assert sched.health()["paged_attention"]["path"] == "kernel"
    assert sched.kv_dtype_name == "int8"
    hostage = sched.kv.pool.allocate(19)
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    for r, w in zip(reqs, want):
        assert got[r.rid] == w, \
            f"int8 kernel diverged from the int8 reference (rid={r.rid})"
    assert sched.metrics.preemptions >= 1
    sched.kv.pool.free(hostage)
    r2 = sched.submit(donor.copy(), max_new_tokens=5)
    got = sched.run()
    assert got[r2.rid] == want_hit
    assert r2.cached_prefix_tokens > 0
    assert 1 <= eng.serving_decode_multi_compile_count() <= \
        len(sched.horizon_buckets)
    sched.prefix_cache.evict(10 ** 6)
    assert sched.kv.pool.pages_in_use == 0


def test_hybrid_ici_dcn_mesh_token_exact(ref):
    """Hybrid ICI x DCN multi-slice mesh from PURE CONFIG: 2 emulated
    slices of 2x2 chips (mesh model=2,data=2 + mesh_dcn data=2 ->
    serving mesh model=2, data=4), shard_mapped kernel active, output
    token-exact vs the 1-device engine, and the hybrid split visible
    in mesh_info."""
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 11, 7, 9)]
    max_new = [8, 6, 10, 5]
    want = _oracle(ref, prompts, max_new)

    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32",
        kv_cache_dtype="float32", tensor_parallel={"tp_size": 2},
        mesh={"data": 2, "model": 2}, mesh_dcn={"data": 2},
        paged_kernel="force")
    eng.init_params()
    assert int(eng.mesh.shape["model"]) == 2
    assert int(eng.mesh.shape["data"]) == 4

    sched = ServingScheduler(eng, decode_horizon_steps=4, audit_every=1,
                             **KCFG)
    assert sched.mesh_info["mesh_hybrid"] == {
        "ici": {"model": 2, "data": 2}, "dcn": {"data": 2}}
    assert sched.mesh_info["mesh_shape"] == {"model": 2, "data": 4}
    h = sched.health()
    assert h["paged_attention"]["path"] == "kernel"
    assert h["serving_axes"]["kv_heads"] == "model"
    assert h["serving_axes"]["slots"] == "data"
    reqs = [sched.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    got = sched.run()
    for r, w in zip(reqs, want):
        assert got[r.rid] == w, "hybrid-mesh serving diverged"
    assert sched.kv.pool.pages_in_use == 0


def test_hybrid_dcn_validation():
    """Hybrid config validates loudly: a dcn factor the device count
    cannot cover, an unknown axis, and a -1 wildcard across slices are
    all ValueErrors naming the problem."""
    from deepspeed_tpu.parallel.topology import make_hybrid_mesh
    from deepspeed_tpu.runtime.config import MeshConfig
    with pytest.raises(ValueError, match="divisible"):
        make_hybrid_mesh(MeshConfig(data=1, model=1),
                         {"data": 3}, allow_subset=True)
    with pytest.raises(ValueError, match="unknown dcn"):
        make_hybrid_mesh(MeshConfig(data=1, model=1), {"dataa": 2},
                         allow_subset=True)
    with pytest.raises(ValueError, match="-1"):
        make_hybrid_mesh(MeshConfig(data=1, model=1), {"data": -1},
                         allow_subset=True)


# ------------------------------------------ dispatch guards + decision


def test_multichip_mesh_false_inside_shard_map(engines):
    """Regression: inside a shard_map body the mesh axes are bound and
    ``_multichip_mesh`` must report False — otherwise the per-shard
    kernel body would re-trigger the mesh bypass and every shard would
    run the gather reference."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu import comm as dist
    from deepspeed_tpu.ops.attention import decode as decode_ops
    from jax.sharding import PartitionSpec as P

    eng = engines(2, 4)
    seen = []

    def body(x):
        seen.append(decode_ops._multichip_mesh())
        return x

    with dist.mesh_scope(eng.mesh):
        assert decode_ops._multichip_mesh() is True
        jax.jit(jax.shard_map(body, mesh=eng.mesh, in_specs=P(),
                              out_specs=P(), check_vma=False))(
            jnp.zeros(4))
        assert seen == [False], \
            "shard_map body re-triggered the multi-chip bypass"
        assert decode_ops._multichip_mesh() is True


def test_paged_kernel_decision_is_data(engines):
    """The kernel-eligibility decision is a pure function of static
    config — the same rule the trace takes and health() reports."""
    from deepspeed_tpu.ops.attention.decode import paged_kernel_decision

    eng = engines(2, 4)
    # auto off-TPU: reference, naming the backend and the override
    d = paged_kernel_decision(num_heads=4, num_kv_heads=4, page_size=128,
                              mesh=eng.mesh, mode="auto", backend="cpu")
    assert d["path"] == "reference" and "cpu" in d["reason"]
    # auto on TPU with misaligned pages: reference, NAMING the size
    d = paged_kernel_decision(num_heads=4, num_kv_heads=4, page_size=16,
                              mesh=eng.mesh, mode="auto", backend="tpu")
    assert d["path"] == "reference" and "page_size=16" in d["reason"]
    # auto on TPU with aligned pages on a mesh: shard_mapped kernel
    d = paged_kernel_decision(num_heads=4, num_kv_heads=4, page_size=128,
                              mesh=eng.mesh, mode="auto", backend="tpu")
    assert d == {"path": "kernel", "dispatch": "shard_map",
                 "reason": d["reason"]}
    # force off-TPU: kernel (interpret), shard_mapped on the mesh
    d = paged_kernel_decision(num_heads=4, num_kv_heads=4, page_size=16,
                              mesh=eng.mesh, mode="force", backend="cpu")
    assert (d["path"], d["dispatch"]) == ("kernel", "shard_map")
    # force on one device: direct pallas_call
    d = paged_kernel_decision(num_heads=4, num_kv_heads=4, page_size=16,
                              mesh=None, mode="force", backend="cpu")
    assert (d["path"], d["dispatch"]) == ("kernel", "direct")
    with pytest.raises(ValueError, match="unknown paged-kernel mode"):
        paged_kernel_decision(num_heads=4, num_kv_heads=4, page_size=16,
                              mode="fast")


def test_page_size_gate_warns_at_pool_construction(monkeypatch):
    """The old silent `page_size % 128` fallback is now a
    constructor-time warning NAMING the offending page size (on the
    backend where the gate actually bites)."""
    import jax
    eng = deepspeed_tpu.init_inference(
        model=GPT2(gpt2_tiny()), dtype="float32",
        kv_cache_dtype="float32", tensor_parallel={"tp_size": 1},
        mesh={"data": 1, "model": 1})
    eng.init_params()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.warns(UserWarning, match="page_size=16"):
        eng.init_paged_cache(num_pages=4, page_size=16)
    # an aligned page size stays quiet (decision: kernel)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        eng.init_paged_cache(num_pages=2, page_size=128)


# ------------------------------------- tuned-config topology provenance


def _load_ds_serve():
    import importlib.machinery
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "bin", "ds_serve")
    loader = importlib.machinery.SourceFileLoader("ds_serve_cli", path)
    spec = importlib.util.spec_from_loader(loader.name, loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


def test_tuned_config_rejected_on_foreign_mesh(tmp_path):
    """Serving knobs are per-topology: a tuned config recorded on one
    mesh shape is REJECTED with a clear error when applied on another
    (and accepted when the shapes match; legacy files without the
    provenance field still load)."""
    import argparse
    import json as _json
    ds = _load_ds_serve()

    def args_for(mesh=None, tuned=None):
        return argparse.Namespace(
            mesh=mesh, tp=1, tuned_config=tuned, num_slots=8,
            num_pages=128, page_size=None, max_pages_per_slot=None,
            prefill_chunk=32, decode_horizon=8, no_overlap=False,
            prefix_cache=True, prefix_cache_pages=None, spec_k=8,
            spec_decode="off", kv_dtype="float32", weight_dtype=None)

    # tuned on model=2,data=4 but serving on the default 1x8 mesh
    foreign = tmp_path / "tuned_foreign.json"
    foreign.write_text(_json.dumps(
        {"knobs": {"decode_horizon_steps": 4},
         "mesh_shape": {"model": 2, "data": 4}}))
    with pytest.raises(SystemExit, match="per-topology|tuned on mesh"):
        ds.apply_tuned_config(args_for(tuned=str(foreign)))

    # same shape: applies cleanly
    matching = tmp_path / "tuned_match.json"
    matching.write_text(_json.dumps(
        {"knobs": {"decode_horizon_steps": 4},
         "mesh_shape": {"model": 2, "data": 4}}))
    a = args_for(mesh="model=2,data=4", tuned=str(matching))
    assert ds.apply_tuned_config(a) == str(matching)
    assert a.decode_horizon == 4

    # legacy tuned files carry no mesh provenance: still accepted
    legacy = tmp_path / "tuned_legacy.json"
    legacy.write_text(_json.dumps({"knobs": {"num_pages": 64}}))
    a = args_for(tuned=str(legacy))
    assert ds.apply_tuned_config(a) == str(legacy)
    assert a.num_pages == 64


# --------------------------------------------------- ds_serve exit code


def test_ds_serve_exits_nonzero_on_failed_rows(tmp_path, monkeypatch):
    """A ``failed`` row is an exception attributable to ONE request
    that the scheduler contained — ``ds_serve`` must not exit like a
    clean run.  ``finished`` rows exit 0.  An exception inside the
    SHARED prefill dispatch (on a chip: a kernel the compiler refused)
    belongs to no single request and leaves the loop loudly, like a
    failed decode dispatch."""
    import json as _json
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.resilience import faults
    ds = _load_ds_serve()
    inp, out = tmp_path / "in.jsonl", tmp_path / "out.jsonl"
    inp.write_text(_json.dumps({"prompt": [1, 2, 3],
                                "max_new_tokens": 2}) + "\n")
    argv = ["--model", "gpt2-tiny", "--mesh", "data=1", "--num-slots",
            "2", "--num-pages", "8", "--input", str(inp), "--output",
            str(out)]
    assert ds.main(argv) == 0
    assert _json.loads(out.read_text().splitlines()[0])["status"] == \
        "finished"

    inj = faults.FaultInjector(seed=0)
    inj.on("serve.request", nth=1, exc=RuntimeError("callback broke"))
    with faults.injected(inj):
        assert ds.main(argv) == 1
    row = _json.loads(out.read_text().splitlines()[0])
    assert row["status"] == "failed" and "callback broke" in row["error"]

    def refuse(self, *a, **k):
        raise RuntimeError("Mosaic refused the kernel")
    monkeypatch.setattr(InferenceEngine, "prefill_into_slots", refuse)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        ds.main(argv)
