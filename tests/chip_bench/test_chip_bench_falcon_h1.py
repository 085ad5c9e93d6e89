"""The ``falcon_h1`` family through the harness on the CPU: a test-local
tiny configuration (data/falcon-h1-tiny.json) served by ``drive_serve``
against ``reference_falcon_h1.py`` under the dense rule, the same
configuration against a reference one block short or with one
multiplier dropped, and ``readers_falcon_h1``'s bytes against hand
counts and the program's own."""

import json
import os

import jax
import pytest

import chip_bench_paths as paths
import drive_serve
import readers
import readers_falcon_h1
import run as harness
import test_chip_bench_manifest as contract

SEED = 2 ** 31 + 37


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def serve(config):
    ctx = harness.Context(paths.ROOT, paths.BENCH, config,
                          load(paths.DATA, "tiny-open.json"), SEED, 1.5,
                          devices=jax.devices(),
                          compiles=harness.CompileCount(),
                          config_file="data/falcon-h1-tiny.json")
    return ctx, harness.run_cell(ctx, "serve")


def test_the_parallel_hybrid_serves_against_its_reference_under_the_dense_rule():
    config = load(paths.DATA, "falcon-h1-tiny.json")
    assert "routed" not in config["reference"]
    ctx, res = serve(config)
    assert all(res["checks"].values()), (res["checks"], res["compared"])
    assert ctx.window_compiles == 0
    # float32 on both sides: the served tokens are the reference's best
    # to rounding, far inside the 8 bf16 ulps the dense rule allows
    worst, eps = res["compared"]["reference_worst_margin"]
    assert worst <= 1e-5 < eps
    assert res["notes"]["paged_attention"]["path"] == "kernel"
    assert res["notes"]["paged_attention"]["heads"] == [10, 2]
    # the program's counters reach the readers
    c = res["counters"]
    assert c["decode_steps"] > 0
    assert 0 < c["decode_live_rows"] <= 4 * c["decode_steps"]
    assert c["decode_kv_tokens"] >= c["decode_live_rows"] * 4
    # both pools of every layer: 4 slots' state, 48 pages of 16 tokens
    assert c["state_pool_bytes"] == 4 * 2 * (3 * 128 * 4 + 8 * 8 * 16 * 4)
    assert c["kv_pool_bytes"] == 48 * 2 * 2 * 16 * 2 * 16 * 4
    assert c["state_resets"] >= res["attempted"]
    assert c["prefix_cache_refused"] == 1      # the CLI's default asked


@pytest.mark.parametrize("key,value", [
    ("num_hidden_layers", 1),                  # one block short
    ("ssm_out_multiplier", 1.0),
    ("embedding_multiplier", 1.0),
])
def test_a_reference_short_a_block_or_a_multiplier_fails_the_served_tokens(
        key, value):
    """The dense rule allows 8 bf16 ulps of the logit scale (3%), which
    a lost block or one of the large multipliers passes by far.  The
    smaller ones (attention out, key, gate, the B / C / dt segments) move a logit by
    less than that at this size and are held by the float32 comparison
    of logits in tests/unit/test_falcon_h1_serving.py."""
    config = load(paths.DATA, "falcon-h1-tiny.json")
    # the program serves the configuration as it is; the reference reads
    # its changed twin
    config["wrong_" + key] = value
    arg = next(a for a, k in config["reference"]["args"].items() if k == key)
    config["reference"]["args"][arg] = "wrong_" + key
    _, res = serve(config)
    checks = dict(res["checks"])
    assert checks.pop("reference") is False
    assert all(checks.values()), checks
    worst, eps = res["compared"]["reference_worst_margin"]
    assert worst > eps


# ------------------------------------------------ readers_falcon_h1

CONFIG = load(paths.BENCH, "configs", "falcon-h1-34b-l6-v8.json")
MIX = load(paths.BENCH, "traffic", "chat-steady-s128-fh1.json")
PEAKS = load(paths.BENCH, "peaks.json")["devices"]["TPU v5e"]


def test_the_cell_is_the_issues():
    assert MIX["serve"] == {"num_slots": 128, "max_pages_per_slot": 12,
                            "max_queue": 2048}
    assert CONFIG["serve"] == {"num_pages": 1536}
    chat = load(paths.BENCH, "traffic", "chat-steady-s128.json")
    for key in ("schedule_seed", "loop", "arrivals", "prompt_len",
                "output_len", "sharing", "sampling", "drain_s"):
        assert MIX[key] == chat[key], key
    assert isinstance(MIX["rate_per_s"], float) and "knee" in MIX["rate_note"]


CELL = "falcon-h1.chat"


def manifest_holds(manifest, root):
    """What a manifest has to say of THIS family's cell, whatever else
    it holds (test_chip_bench_family.py runs this against a manifest
    that has grown by a cell, a configuration and metrics)."""
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("falcon-h1-34b-l6-v8", "chat-steady-s128-fh1", 1)
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    assert config["reduced"] == CONFIG["reduced"] == ["num_hidden_layers",
                                                      "vocab_size"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for name in ("ttft_p90_ms", "tpot_p90_ms"):
        assert CELL in e2e[name]["workloads"]
    # under the knee the rate is the schedule's and spread over half its
    # bound here (PERF.md section 2): the cell does not report it
    assert CELL not in e2e["req_tokens_per_s"]["workloads"]
    # one entry a layer_metrics/*.par.json on disk, each the cell's alone
    own = contract.cell_metrics(manifest, root, ".par", CELL)
    assert {m["name"] for m in own} >= {
        "ssm.state_update.roofline.par", "kernel.paged_decode.roofline.par",
        "ssm.prefill_state.time_share.par"}
    for m in own:
        assert m["moves"] in ("tpot_p90_ms", "ttft_p90_ms")
        if "roofline" in m["name"]:
            assert m["reader"].startswith("readers_falcon_h1:")
    shared = [m for m in manifest["per_layer"] if m["name"].endswith(".chat")]
    assert shared and all(CELL in m["workloads"] for m in shared)


def test_the_manifest_holds_the_cell_and_its_metrics():
    manifest_holds(load(paths.ROOT, "BENCHMARK.json"), paths.ROOT)


def test_state_and_kv_bytes_are_the_published_widths():
    # float32 [32 heads, 128, 256] + bf16 conv tail [3, 4096 + 2 * 2 * 256]
    assert readers_falcon_h1.state_bytes_per_slot_layer(CONFIG) == \
        32 * 128 * 256 * 4 + 3 * 5120 * 2 == 4_194_304 + 30_720
    # K and V of 4 heads of 128 in bf16
    assert readers_falcon_h1.kv_bytes_per_token_layer(CONFIG) == 2048
    # and they are what the program allocates: a slot over six layers, a
    # page of 128 tokens over six layers
    from deepspeed_tpu.models import falcon_h1
    from deepspeed_tpu.ops.quant.kv import kv_page_bytes
    import jax.numpy as jnp
    cfg = drive_serve.build_module(CONFIG).cfg
    assert falcon_h1.state_bytes_per_slot(cfg) == 6 * 4_225_024
    assert kv_page_bytes(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, 128,
                         jnp.bfloat16) == 6 * 128 * 2048 == 1_572_864
    assert (cfg.rope_base, cfg.mamba_d_ssm, cfg.ssm_state_size) == \
        (1e11, 4096, 256)


def trace_of(events):
    return readers.Trace({"/device:TPU:0": events}, [])


def context(trace, counters, config=CONFIG):
    return {"trace": trace, "counters": counters, "config": config,
            "traffic": MIX, "peaks": PEAKS}


COUNTERS = {"decode_steps": 200, "decode_live_rows": 200 * 50,
            "decode_kv_tokens": 200 * 50 * 300}


def test_state_update_roofline_from_a_synthetic_trace():
    # 12 state updates of 1.5 ms each: two decode steps of six layers
    ops = [(f"%fusion.{i} = (f32[128,32,128,256]{{3,2,1,0}}, f32[128,32,128]"
            f"{{2,1,0}}) fusion(%p.{i})", i * 2_000_000,
            i * 2_000_000 + 1_500_000) for i in range(12)]
    ops.append(("%fusion.99 = f32[128,32,128]{2,1,0} fusion("
                "f32[128,32,128,256]{3,2,1,0} %x)", 0, 10 ** 9))
    args = {"heads": ["fusion"], "all_of": ["= (f32[128,32,128,256]{"]}
    got = readers_falcon_h1.state_update_roofline(
        context(trace_of(ops), COUNTERS), **args)
    need = 12 * 2 * 4_225_024 * 50
    assert got == pytest.approx(100 * need / 819e9 / (12 * 1.5e-3))
    assert 0 < got < 100
    # nothing to read: no trace, the parent's counters, no event,
    # another family's configuration
    for ctx in (context(None, COUNTERS), context(trace_of(ops), {}),
                context(trace_of(ops), {"slot_occupancy": 0.5}),
                context(trace_of(ops[-1:]), COUNTERS),
                context(trace_of(ops), COUNTERS, {"hidden_size": 4096})):
        assert readers_falcon_h1.state_update_roofline(ctx, **args) is None


def test_paged_decode_roofline_from_a_synthetic_trace():
    ops = [(f"%attn.{i} = bf16[128,4,5,128]{{3,2,1,0}} custom-call(%q), "
            f"custom_call_target=\"tpu_custom_call\"", i * 10 ** 6,
            i * 10 ** 6 + 400_000) for i in range(12)]
    ops.append(("%paged_prefill.1 = bf16[16,4,160,128]{3,2,1,0} custom-call"
                "(%q), custom_call_target=\"tpu_custom_call\"", 0, 10 ** 8))
    args = {"heads": ["attn"], "all_of": ["tpu_custom_call"]}
    got = readers_falcon_h1.paged_decode_roofline(
        context(trace_of(ops), COUNTERS), **args)
    need = 12 * 2048 * 50 * 300
    assert got == pytest.approx(100 * need / 819e9 / (12 * 4e-4))
    assert 0 < got < 100
    for ctx in (context(None, COUNTERS), context(trace_of(ops), {}),
                context(trace_of(ops[-1:]), COUNTERS),
                context(trace_of(ops), COUNTERS, {"hidden_size": 4096})):
        assert readers_falcon_h1.paged_decode_roofline(ctx, **args) is None


def test_prefill_state_time_share_matches_results_not_operands():
    spec = load(paths.BENCH, "layer_metrics",
                "ssm.prefill_state.time_share.par.json")
    ops = [
        # prefill's state work at 64 rows: four results of state shape
        ("%fusion.784 = (f32[128,32,128,128]{3,2,1,0}, f32[128,32,128,128]"
         "{3,2,1,0}) fusion(f32[128,32,128,256]{3,2,1,0} %pools)", 0, 100),
        ("%pad_maximum_fusion.1 = f32[64,32,128,256]{3,2,1,0} fusion(%a)",
         100, 200),
        ("%convolution_add_fusion.2 = f32[64,2,16,128,256]{4,3,2,1,0} "
         "fusion(%b)", 200, 300),
        ("%dynamic-slice_dynamic-update-slice_fusion.3 = f32[64,32,128,128]"
         "{3,2,1,0} fusion(%c)", 300, 400),
        # decode's update (a tuple result) and an op that only READS a
        # state-shaped operand are not prefill's state work
        ("%fusion.852 = (f32[128,32,128,256]{3,2,1,0}, f32[128,32,128]"
         "{2,1,0}) fusion(f32[128,32,128,256]{3,2,1,0} %barrier)", 400, 600),
        ("%fusion.9 = bf16[64,32,4096]{2,1,0} fusion(f32[64,32,2,16,128]"
         "{4,3,2,1,0} %y)", 600, 1000),
    ]
    got = readers.name_time_share(context(trace_of(ops), {}), **spec["args"])
    assert got == pytest.approx(100 * 400 / 1000)
    assert readers.name_time_share(context(trace_of(ops[4:]), {}),
                                   **spec["args"]) is None
