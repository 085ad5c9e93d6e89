"""The ``mimo_v2_flash`` family through the harness on the CPU: a
test-local tiny configuration (data/mimo-v2-tiny.json) served by
``drive_serve`` against ``reference_mimo_v2.py`` under the eps-argmax
rule (open and closed loop), the same configuration against a reference with
the sink or the value scale dropped, the configuration's file against
the arithmetic it states, ``readers_mimo_v2``'s bytes and FLOPs against
hand counts and the program's own, the new kernel shapes compiled for
the v5e, and that the cell was added by files alone."""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_bench_paths as paths
import drive_serve
import readers
import readers_mimo_v2
import run as harness
import test_chip_bench_manifest as contract

SEED = 2 ** 31 + 42
CELL = "mimo-v2-flash.longctx-batch"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def serve(config, mix="tiny-open.json"):
    ctx = harness.Context(paths.ROOT, paths.BENCH, config,
                          load(paths.DATA, mix), SEED, 1.5,
                          devices=jax.devices(),
                          compiles=harness.CompileCount(),
                          config_file="data/mimo-v2-tiny.json")
    return ctx, harness.run_cell(ctx, "serve")


@pytest.mark.parametrize("mix", ["tiny-open.json", "tiny-closed.json"])
def test_the_family_serves_against_its_reference_under_the_eps_rule(mix):
    """The configuration declares no ``reference.routed``, as the
    cell's does not: every served token of the sample within eps of the
    reference's best (PERF.md section 6, PR 42 e, has the chip's
    readings on both sides of that limit)."""
    ctx, res = serve(load(paths.DATA, "mimo-v2-tiny.json"), mix)
    assert all(res["checks"].values()), (res["checks"], res["compared"])
    assert ctx.window_compiles == 0
    assert ctx.reference_routed() is None
    assert set(res["compared"]) >= {"reference_worst_margin"}
    assert "reference_over_eps_share" not in res["compared"]
    worst, eps = res["compared"]["reference_worst_margin"]
    assert worst <= eps == res["notes"]["reference_eps"]
    assert res["notes"]["paged_attention"]["path"] == "kernel"
    assert res["notes"]["paged_attention"]["heads"] == [8, 2]
    # the program's counters reach the readers
    c = res["counters"]
    assert c["moe_calls"] > 0
    assert 0 < c["moe_held_assignments"] < c["moe_assignments"]
    assert c["prefix_cache_refused"] == 1      # the CLI's default asked
    # pages for the two full layers, rings for the five window layers
    slots = load(paths.DATA, mix)["serve"]["num_slots"]
    assert c["kv_pool_bytes"] == 48 * 16 * 2 * 2 * (24 + 16) * 4
    assert c["kv_paged_bytes_per_token"] == 2 * 2 * 40 * 4
    assert c["kv_window_bytes_per_slot"] == 5 * 16 * 4 * 40 * 4
    assert c["state_pool_bytes"] == slots * 5 * 16 * 4 * 40 * 4 + 6 * 20
    assert c["decode_steps"] > 0 and c["prefill_kv_tokens"] > 0
    assert 0 < c["decode_window_tokens"] <= c["decode_kv_tokens"]
    assert c["prefill_kv_pairs"] >= c["prefill_tokens"]
    if mix == "tiny-closed.json":
        assert res["end_to_end"]["served_tokens_per_s"] > 0
    got = readers_mimo_v2.kv_bytes_per_live_token(
        {"counters": c, "config": load(paths.DATA, "mimo-v2-tiny.json")})
    context = c["decode_kv_tokens"] / c["decode_live_rows"]
    assert got == pytest.approx(640 + 51200 / context)


@pytest.mark.parametrize("arg,key,value", [
    ("swa_sink", "add_swa_attention_sink_bias", False),
    ("value_scale", "attention_value_scale", 1.0),
    ("window", "sliding_window", 4),
])
def test_a_reference_without_a_term_fails_the_served_tokens(arg, key, value):
    """The rule allows a margin of 8 bf16 ulps of the logit scale; the
    sink left out, v unscaled or another window move served positions
    past it.  The smaller terms (the partial
    rotary, the window's edge by one, the score bias) are held by the
    float32 comparison of logits in tests/unit/test_mimo_v2_serving.py."""
    config = load(paths.DATA, "mimo-v2-tiny.json")
    config["wrong_" + key] = value
    assert config["reference"]["args"][arg] == key
    config["reference"]["args"][arg] = "wrong_" + key
    _, res = serve(config)
    checks = dict(res["checks"])
    assert checks.pop("reference") is False
    assert all(checks.values()), checks
    worst, eps = res["compared"]["reference_worst_margin"]
    assert worst > eps


# ------------------------------------------- the configuration's file

CONFIG = load(paths.BENCH, "configs", "mimo-v2-flash-l7-ep16.json")
MIX = load(paths.BENCH, "traffic", "longctx-closed-s32.json")
PEAKS = load(paths.BENCH, "peaks.json")["devices"]["TPU v5e"]


def test_the_cell_is_the_issues():
    assert MIX["serve"] == {"num_slots": 32, "max_pages_per_slot": 130,
                            "max_queue": 128}
    assert CONFIG["serve"] == {"num_pages": 4160}
    assert (MIX["loop"], MIX["clients"], MIX["pool"],
            MIX["schedule_seed"]) == ("closed", 64, 192, 25)
    assert MIX["prompt_len"] == {"dist": "loguniform", "min": 2048,
                                 "max": 16384}
    assert MIX["output_len"] == {"dist": "uniform", "min": 64, "max": 256}
    thr = load(paths.BENCH, "traffic", "longprompt-closed.json")
    assert set(MIX) == set(thr)
    # the longest request fits a slot's pages to the token
    assert 16384 + 256 == 130 * 128
    assert CONFIG["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                                 "moe_layer_freq", "n_routed_experts",
                                 "vocab_size"]
    assert CONFIG["reduced_from"]["vocab_size"] == 8 * CONFIG["vocab_size"]
    assert set(CONFIG["assumed"]) >= {
        "rotary", "value_scale", "sink", "window", "score_bias",
        "sink_init", "down_projections", "prediction_heads", "unused"}
    manifest_holds(load(paths.ROOT, "BENCHMARK.json"), paths.ROOT)


# the cell's per-layer metrics as PR 42 and PR 44 entered them, less
# moe.experts_dense.time_share.win (PR 53: it read nothing), in the
# manifest's order.  A later metric of the cell may stand anywhere.
WIN_ORDER = [
    "device.idle_share.win", "engine.host_busy_share.win",
    "sched.slot_occupancy.win", "sched.prefill_step_share.win",
    "kernel.paged_decode.time_share.win", "kernel.paged_decode.roofline.win",
    "kernel.paged_prefill.time_share.win",
    "kernel.paged_prefill.roofline.win", "attn.window.time_share.win",
    "attn.window.roofline.win", "moe.experts.time_share.win",
    "moe.experts.roofline.win", "moe.held_load_max_over_mean.win",
    "cache.kv_bytes_per_live_token.win",
    "sched.prefill_rows_per_dispatch.win",
    "kernel.paged_decode.live_page_share.win", "sched.idle_in_boundary.win",
    "engine.idle_in_dispatch.win", "device.idle_outside_step.win",
    "cache.page_util_mean.win", "engine.host_share.win",
    "sched.decode_live_rows_per_step.win"]


def manifest_holds(manifest, root):
    """What a manifest has to say of THIS family's cell, whatever else
    it holds: never how many cells, configurations or metrics there are,
    nor which stands last (test_chip_bench_family.py runs this against a
    manifest that has grown by a cell, a configuration and metrics)."""
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("mimo-v2-flash-l7-ep16", "longctx-closed-s32", 1)
    assert any(c["name"] == cell["config"] for c in manifest["configs"])
    served = next(m for m in manifest["end_to_end"]
                  if m["name"] == "served_tokens_per_s")
    assert CELL in served["workloads"]
    # one entry a layer_metrics/*.win.json on disk, each the cell's, each
    # moving served_tokens_per_s
    new = contract.cell_metrics(manifest, root, ".win", CELL)
    assert {m["name"] for m in new} >= {b + ".win" for b in SIBLINGS} | \
        {"sched.decode_live_rows_per_step.win"}
    for m in new:
        assert m["moves"] == "served_tokens_per_s"
        if "roofline" in m["name"] or m["name"].startswith("cache.kv"):
            assert m["reader"].startswith("readers_mimo_v2:")
    # the entries keep their order among themselves
    assert [m["name"] for m in new if m["name"] in WIN_ORDER] == WIN_ORDER


def test_the_program_allocates_what_the_file_states():
    """3.43 B parameters; a page of the two full layers; a ring a slot
    of the five window layers — from the program's own shapes."""
    from deepspeed_tpu.models import mimo_v2
    module = drive_serve.build_module(CONFIG, dtype=jnp.bfloat16,
                                      param_dtype=jnp.bfloat16)
    cfg = module.cfg
    shapes = jax.eval_shape(
        lambda key: module.init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    n = sum(int(a.size) for a in jax.tree.leaves(shapes))
    attn_full = 4096 * 64 * 192 + 4096 * 4 * 192 + 4096 * 4 * 128 + \
        64 * 128 * 4096
    attn_swa = attn_full + 4096 * 4 * (192 + 128) + 64      # + the sinks
    experts = 16 * 3 * 4096 * 2048 + 4096 * 256 + 256
    assert n == (attn_full + 3 * 4096 * 16384) + 5 * (attn_swa + experts) \
        + (attn_full + experts) + 2 * 19072 * 4096 + 15 * 4096
    assert round(n / 1e9, 2) == 3.43
    assert (cfg.rotary_dim, cfg.k_pool_dim, cfg.routed_scaling_factor) == \
        (64, 256, 1.0)
    assert cfg.layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    pools = jax.eval_shape(lambda: mimo_v2.init_paged_kv_cache(
        cfg, 4160, 128, jnp.bfloat16, num_slots=32))
    kinds = ["ring" if "k_ring" in e else "pages" for e in pools["layers"]]
    assert kinds == ["pages", "ring", "ring", "ring", "ring", "pages", "ring"]
    assert pools["layers"][0]["k_pages"].shape == (4160, 128, 4, 256)
    assert pools["layers"][0]["v_pages"].shape == (4160, 128, 4, 128)
    assert pools["layers"][1]["k_ring"].shape == (32, 128, 8, 192)
    assert pools["layers"][1]["v_ring"].shape == (32, 128, 8, 128)
    # the published widths a token; what the pool pays with K at 256
    assert readers_mimo_v2.paged_bytes_per_token_layer(CONFIG) == 2560
    assert readers_mimo_v2.ring_bytes_per_token_layer(CONFIG) == 5120
    assert readers_mimo_v2.all_paged_bytes_per_token(CONFIG) == 30720
    assert mimo_v2.state_bytes_per_slot(cfg) == 5 * 128 * 5120 == 3_276_800
    assert mimo_v2.window_ring(cfg) == (128, 3_276_800)
    assert 786_432 == mimo_v2.kv_page_bytes(cfg, 128, jnp.bfloat16) == \
        2 * 128 * 4 * (256 + 128) * 2


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(paths.BENCH, "reference_mimo_v2.py")).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
    import reference_mimo_v2 as ref
    assert callable(ref.hidden) and callable(ref.logits)


# ------------------------------------------------ readers_mimo_v2

def trace_of(events):
    return readers.Trace({"/device:TPU:0": events}, [])


def context(trace, counters, config=CONFIG):
    return {"trace": trace, "counters": counters, "config": config,
            "traffic": MIX, "peaks": PEAKS}


COUNTERS = {"decode_steps": 200, "decode_live_rows": 200 * 20,
            "decode_kv_tokens": 200 * 20 * 9000,
            "decode_window_tokens": 200 * 20 * 128,
            "prefill_dispatches": 100, "prefill_kv_tokens": 100 * 30 * 8000,
            "prefill_kv_pairs": 100 * 30 * 32 * 8000,
            "moe_calls": 1800, "moe_held_assignments": 1800 * 500,
            "kv_paged_bytes_per_token": 6144,
            "kv_window_bytes_per_slot": 3_276_800}
CUSTOM = 'custom-call(%q), custom_call_target="tpu_custom_call"'


def nothing_to_read(reader, ops, **args):
    for ctx in (context(None, COUNTERS), context(trace_of(ops), {}),
                context(trace_of(ops[-1:]), COUNTERS),
                context(trace_of(ops), COUNTERS, {"hidden_size": 4096})):
        assert reader(ctx, **args) is None


def test_paged_decode_roofline_from_a_synthetic_trace():
    ops = [(f"%attn.{i} = bf16[32,4,16,128]{{3,2,1,0}} {CUSTOM}",
            i * 10 ** 7, i * 10 ** 7 + 2_000_000) for i in range(8)]
    ops.append((f"%paged_prefill.1 = bf16[32,4,512,128]{{3,2,1,0}} {CUSTOM}",
                0, 10 ** 8))
    args = {"heads": ["attn"], "all_of": ["tpu_custom_call"]}
    got = readers_mimo_v2.paged_decode_roofline(
        context(trace_of(ops), COUNTERS), **args)
    # 20 live slots of 9,000 tokens, 4 heads x (192 + 128) x 2 bytes
    need = 8 * 2560 * 20 * 9000
    assert got == pytest.approx(100 * need / 819e9 / (8 * 2e-3))
    assert 0 < got < 100
    nothing_to_read(readers_mimo_v2.paged_decode_roofline, ops, **args)


def test_paged_prefill_roofline_from_a_synthetic_trace():
    ops = [(f"%paged_prefill.{i} = bf16[32,4,512,128]{{3,2,1,0}} {CUSTOM}",
            i * 10 ** 7, i * 10 ** 7 + 6_000_000) for i in range(6)]
    ops.append((f"%attn.9 = bf16[32,4,16,128]{{3,2,1,0}} {CUSTOM}", 0, 10))
    args = {"heads": ["paged_prefill"], "all_of": ["tpu_custom_call"]}
    got = readers_mimo_v2.paged_prefill_roofline(
        context(trace_of(ops), COUNTERS), **args)
    nbytes, flops = readers_mimo_v2.prefill_needed(CONFIG, 30 * 8000,
                                                   30 * 32 * 8000)
    assert nbytes == 2560 * 240_000
    assert flops == 64 * 2 * 320 * 7_680_000
    least = max(nbytes / 819e9, flops / 197e12)
    assert least == flops / 197e12          # long contexts: compute bound
    assert got == pytest.approx(100 * 6 * least / (6 * 6e-3))
    assert 0 < got < 100
    nothing_to_read(readers_mimo_v2.paged_prefill_roofline, ops, **args)


def test_window_roofline_from_a_synthetic_trace():
    ops = [(f"%fusion.{i} = bf16[32,8,8,128]{{3,2,1,0}} fusion(%ring)",
            i * 10 ** 6, i * 10 ** 6 + 40_000) for i in range(10)]
    ops.append(("%copy.1 = f32[1] copy()", 0, 10 ** 8))
    args = {"substrs": ["bf16[32,8,8,128]"]}
    got = readers_mimo_v2.window_decode_roofline(
        context(trace_of(ops), COUNTERS), **args)
    need = 10 * 5120 * 20 * 128
    assert got == pytest.approx(100 * need / 819e9 / (10 * 4e-5))
    assert 0 < got < 100
    two = readers_mimo_v2.window_decode_roofline(
        context(trace_of(ops), COUNTERS), events_per_layer_step=2, **args)
    assert two == pytest.approx(got / 2)
    nothing_to_read(readers_mimo_v2.window_decode_roofline, ops, **args)


def test_expert_bytes_and_flops_a_call_count_three_matrices():
    nbytes, flops = readers_mimo_v2.experts_needed(CONFIG, 64.0)
    assert flops == 64 * 6 * 4096 * 2048
    touched = 16 * (1 - 2.718281828459045 ** -4.0)
    assert nbytes == pytest.approx(touched * 3 * 4096 * 2048 * 2)
    nbytes, _ = readers_mimo_v2.experts_needed(CONFIG, 24576.0)
    assert nbytes == pytest.approx(16 * 3 * 4096 * 2048 * 2)
    ops = [(f"%ragged-dot-none.{i} = bf16[8192,4096] custom-call()",
            i * 10 ** 7, i * 10 ** 7 + 3_000_000) for i in range(12)]
    ops.append(("%copy.1 = f32[1] copy()", 0, 10 ** 9))
    got = readers_mimo_v2.experts_roofline(
        context(trace_of(ops), COUNTERS), heads=["ragged-dot-none"])
    nbytes, flops = readers_mimo_v2.experts_needed(CONFIG, 500.0)
    least = max(nbytes / 819e9, flops / 197e12)
    assert got == pytest.approx(100 * 6 * least / (12 * 3e-3))
    assert 0 < got < 100
    nothing_to_read(readers_mimo_v2.experts_roofline, ops,
                    heads=["ragged-dot-none"])


def test_cache_bytes_a_live_token():
    got = readers_mimo_v2.kv_bytes_per_live_token(context(None, COUNTERS))
    assert got == pytest.approx(6144 + 3_276_800 / 9000)
    assert got < readers_mimo_v2.all_paged_bytes_per_token(CONFIG) / 4
    for counters in ({}, {"decode_kv_tokens": 5, "decode_live_rows": 1}):
        assert readers_mimo_v2.kv_bytes_per_live_token(
            context(None, counters)) is None


def test_live_decode_rows_a_step():
    got = readers_mimo_v2.decode_live_rows_per_step(context(None, COUNTERS))
    assert got == 20.0
    for counters in ({}, {"decode_live_rows": 5},
                     {"decode_live_rows": 5, "decode_steps": 0}):
        assert readers_mimo_v2.decode_live_rows_per_step(
            context(None, counters)) is None


# the accepted metrics of the layers this cell runs, on their own readers
SIBLINGS = ["sched.prefill_rows_per_dispatch",
            "kernel.paged_prefill.time_share", "sched.idle_in_boundary",
            "engine.idle_in_dispatch", "device.idle_outside_step",
            "cache.page_util_mean", "engine.host_share"]


@pytest.mark.parametrize("base", SIBLINGS)
def test_an_accepted_metric_reads_this_cell_through_its_own_reader(base):
    """``<base>.win`` is ``<base>.thr`` (the other ``served_tokens_per_s``
    cell's) but for its name and its cell: the same reader, the same
    arguments.  (``kernel.paged_prefill.time_share`` went the other way:
    PR 53 copied the ``.thr`` file from this cell's, in the place of
    ``kernel.paged_decode.live_page_share.thr``.)"""
    win = load(paths.BENCH, "layer_metrics", base + ".win.json")
    thr = load(paths.BENCH, "layer_metrics", base + ".thr.json")
    assert win.pop("name") == base + ".win"
    assert win.pop("workloads") == [CELL]
    assert thr.pop("name") == base + ".thr" and thr.pop("workloads")
    assert win == thr
    assert not win["reader"].startswith("readers_mimo_v2:")


def test_every_new_metric_is_the_cells_and_moves_served_tokens():
    manifest = load(paths.ROOT, "BENCHMARK.json")
    new = [m for m in manifest["per_layer"] if m["name"].endswith(".win")]
    assert new and len(new) == len({m["name"] for m in new})
    manifest_holds(manifest, paths.ROOT)


# ------------------------------------- the new shapes compile for the v5e

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


GEOMETRY = dict(slots=32, max_pages=130, pages=4160, page_size=128, heads=64,
                kv_heads=4, v_dim=128)


@pytest.mark.parametrize("k_dim", [256, 192], ids=["pool-256", "as-192"])
@pytest.mark.parametrize("kernel", ["decode", "prefill-32", "prefill-4"])
def test_the_paged_kernels_compile_for_the_v5e_at_the_cells_geometry(
        one_chip, kernel, k_dim):
    """The full layers' geometry — 64 query heads over 4 KV heads, values
    of 128 — with keys as the pool stores them (256) and as published
    (192: the kernels take it; it is the pool's layout that does not,
    PERF.md section 6 PR 42).  Nothing runs."""
    from deepspeed_tpu.ops.attention.decode import _paged_decode_pallas
    from deepspeed_tpu.ops.attention.paged_prefill import paged_prefill
    g, bf = GEOMETRY, jnp.bfloat16

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    k = spec((g["pages"], g["page_size"], g["kv_heads"], k_dim), bf)
    v = spec((g["pages"], g["page_size"], g["kv_heads"], g["v_dim"]), bf)
    if kernel == "decode":
        def fn(q, k, v, table, pos):
            return _paged_decode_pallas(q, k, v, table, pos,
                                        scale=192 ** -0.5, interpret=False)
        args = (spec((g["slots"], 1, g["heads"], k_dim), bf), k, v,
                spec((g["slots"], g["max_pages"])), spec((g["slots"],)))
    else:
        rows = int(kernel.split("-")[1])

        def fn(q, k, v, table, start, count):
            return paged_prefill(q, k, v, None, None, table, start, count,
                                 scale=192 ** -0.5, interpret=False)
        args = (spec((rows, 32, g["heads"], k_dim), bf), k, v,
                spec((rows, g["max_pages"])), spec((rows,)), spec((rows,)))
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') >= 1
    assert jax.eval_shape(fn, *args).shape[-1] == g["v_dim"]
