"""The ``nemotron_h`` family through the harness on the CPU: a
test-local tiny configuration (data/nemotron-tiny.json) served by
``drive_serve`` against ``reference_nemotron_h.py`` under the routed
rule, the same configuration against a reference of half the depth, and
``readers_hybrid``'s bytes and FLOPs against hand counts."""

import json
import os

import jax
import pytest

import chip_bench_paths as paths
import drive_serve
import readers
import readers_hybrid
import run as harness
import test_chip_bench_manifest as contract

SEED = 2 ** 31 + 35


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def serve(config, monkeypatch):
    # a 1.5 s window on the CPU cannot finish 1,024 positions
    monkeypatch.setattr(drive_serve, "ROUTED_MIN_POSITIONS", 40)
    ctx = harness.Context(paths.ROOT, paths.BENCH, config,
                          load(paths.DATA, "tiny-open.json"), SEED, 1.5,
                          devices=jax.devices(),
                          compiles=harness.CompileCount(),
                          config_file="data/nemotron-tiny.json")
    return ctx, harness.run_cell(ctx, "serve")


def test_the_hybrid_serves_against_its_reference_under_the_routed_rule(
        monkeypatch):
    ctx, res = serve(load(paths.DATA, "nemotron-tiny.json"), monkeypatch)
    assert all(res["checks"].values()), (res["checks"], res["compared"])
    assert ctx.window_compiles == 0
    # float32 on both sides: no near-tie flips an expert here
    share, limit = res["compared"]["reference_over_eps_share"]
    assert share == 0.0 and limit == pytest.approx(0.00041 * 3 * 4)
    worst, cap = res["compared"]["reference_worst_margin"]
    assert worst <= res["notes"]["reference_eps"] < cap
    assert res["notes"]["reference_routed"] == {
        "layers": 3, "experts": 16, "per_token": 3, "held": 4}
    # the program's new counters reach the readers
    c = res["counters"]
    assert c["moe_calls"] > 0 and c["state_pool_bytes"] > 0
    assert 0 < c["moe_held_assignments"] < c["moe_assignments"]
    assert c["state_resets"] >= res["attempted"]
    assert c["prefix_cache_refused"] == 1      # the CLI's default asked
    assert c["prefill_tokens_saved"] == 0


def test_a_reference_half_the_depth_fails_the_served_tokens(monkeypatch):
    config = load(paths.DATA, "nemotron-tiny.json")
    # the program still runs seven blocks, the reference its first three
    # (no attention block at all).  One block short passes: at a seeded
    # init the last routed block moves a logit by 6% of eps, and the
    # routed rule asks for a share over eps (PERF.md section 7)
    config["reference"]["args"]["pattern"] = "short_pattern"
    config["short_pattern"] = config["hybrid_override_pattern"][:3]
    _, res = serve(config, monkeypatch)
    checks = dict(res["checks"])
    assert checks.pop("reference") is False
    assert all(checks.values()), checks
    share, limit = res["compared"]["reference_over_eps_share"]
    assert share > 0.5 > limit


# ------------------------------------------------ readers_hybrid

CONFIG = load(paths.BENCH, "configs", "nemotron-3-nano-30b-a3b-l26-ep8.json")
MIX = load(paths.BENCH, "traffic", "chat-steady-s128.json")
PEAKS = load(paths.BENCH, "peaks.json")["devices"]["TPU v5e"]


CELL = "nemotron3-nano.chat"


def manifest_holds(manifest, root):
    """What a manifest has to say of THIS family's cell, whatever else
    it holds (test_chip_bench_family.py runs this against a manifest
    that has grown by a cell, a configuration and metrics)."""
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron-3-nano-30b-a3b-l26-ep8", "chat-steady-s128", 1)
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    assert config["reduced"] == CONFIG["reduced"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for name in ("ttft_p90_ms", "tpot_p90_ms", "req_tokens_per_s"):
        assert CELL in e2e[name]["workloads"]
    # one entry a layer_metrics/*.hyb.json on disk, each the cell's alone
    own = contract.cell_metrics(manifest, root, ".hyb", CELL)
    assert {m["name"] for m in own} >= {
        "ssm.state_update.roofline.hyb", "moe.experts.roofline.hyb",
        "moe.experts_dense.time_share.hyb", "moe.held_load_max_over_mean.hyb"}
    for m in own:
        assert m["moves"] in ("tpot_p90_ms", "ttft_p90_ms")
        if "roofline" in m["name"]:
            assert m["reader"].startswith("readers_hybrid:")
    # the chat cells' shared metrics read this cell too
    shared = [m for m in manifest["per_layer"] if m["name"].endswith(".chat")]
    assert shared and all(CELL in m["workloads"] for m in shared)


def test_the_manifest_holds_the_cell_and_its_metrics():
    manifest_holds(load(paths.ROOT, "BENCHMARK.json"), paths.ROOT)


def test_state_bytes_are_the_published_widths():
    # float32 [64 heads, 64, 128] + bf16 conv tail [3, 4096 + 2 * 8 * 128]
    assert readers_hybrid.state_bytes_per_slot_layer(CONFIG) == \
        64 * 64 * 128 * 4 + 3 * 6144 * 2 == 2_134_016
    # one decode step of one layer over 100 served slots: read + write
    assert readers_hybrid.state_update_needed_bytes(CONFIG, 100) == \
        2 * 100 * 2_134_016
    # and they are what the program allocates a slot a layer
    from deepspeed_tpu.models import nemotron_h
    cfg = drive_serve.build_module(CONFIG).cfg
    assert nemotron_h.state_bytes_per_slot(cfg) == 12 * 2_134_016


def test_expert_bytes_and_flops_a_call():
    nbytes, flops = readers_hybrid.experts_needed(CONFIG, 96.0)
    assert flops == 96 * 4 * 2688 * 1856
    touched = 16 * (1 - 2.718281828459045 ** -6.0)
    assert nbytes == pytest.approx(touched * 2 * 2688 * 1856 * 2)
    # many pairs touch every held expert: 16 x 2 matrices in bfloat16
    nbytes, _ = readers_hybrid.experts_needed(CONFIG, 24576.0)
    assert nbytes == pytest.approx(16 * 2 * 2688 * 1856 * 2)


def trace_of(events):
    return readers.Trace({"/device:TPU:0": events}, [])


def context(trace, counters):
    return {"trace": trace, "counters": counters, "config": CONFIG,
            "traffic": MIX, "peaks": PEAKS}


def test_state_update_roofline_from_a_synthetic_trace():
    # 24 state updates of 2 ms each: two decode steps of twelve layers
    ops = [(f"%fusion.{i} = f32[128,64,64,128] fusion(), metadata="
            f"{{op_name=\"x/mamba/ssm/add\"}}", i * 3_000_000,
            i * 3_000_000 + 2_000_000) for i in range(24)]
    ops.append(("%copy.1 = f32[1] copy()", 0, 10 ** 9))
    ctx = context(trace_of(ops), {"slot_occupancy": 0.75})
    got = readers_hybrid.state_update_roofline(ctx, substrs=["/ssm/"])
    need = 24 * 2 * 2_134_016 * 0.75 * 128
    assert got == pytest.approx(100 * need / 819e9 / (24 * 2e-3))
    assert 0 < got < 100
    # nothing to read: no trace, no counter, no event, another family
    assert readers_hybrid.state_update_roofline(
        context(None, {"slot_occupancy": 0.75}), substrs=["/ssm/"]) is None
    assert readers_hybrid.state_update_roofline(
        context(trace_of(ops), {}), substrs=["/ssm/"]) is None
    assert readers_hybrid.state_update_roofline(
        ctx, substrs=["no such name"]) is None
    other = dict(ctx, config={"hidden_size": 4096})
    assert readers_hybrid.state_update_roofline(
        other, substrs=["/ssm/"]) is None


def test_experts_roofline_from_a_synthetic_trace():
    ops = []
    for i in range(22):          # eleven layers' up and down matmuls
        ops.append((f"%ragged-dot-none.{i} = bf16[768,1856] custom-call()",
                    i * 10 ** 6, i * 10 ** 6 + 500_000))
    counters = {"moe_calls": 1000, "moe_held_assignments": 96_000}
    ctx = context(trace_of(ops), counters)
    got = readers_hybrid.experts_roofline(ctx, heads=["ragged-dot-none"])
    nbytes, flops = readers_hybrid.experts_needed(CONFIG, 96.0)
    least = max(nbytes / 819e9, flops / 197e12)
    assert least == nbytes / 819e9            # decode: memory bound
    assert got == pytest.approx(100 * 11 * least / (22 * 5e-4))
    assert 0 < got < 100
    assert readers_hybrid.experts_roofline(
        context(trace_of(ops), {}), heads=["ragged-dot-none"]) is None
    assert readers_hybrid.experts_roofline(
        context(None, counters), heads=["ragged-dot-none"]) is None
