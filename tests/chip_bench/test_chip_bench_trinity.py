"""The ``afmoe`` family (Trinity-Large-Preview) through the harness on
the CPU: a test-local tiny configuration (data/afmoe-tiny.json) served
by ``drive_serve`` against ``reference_afmoe.py`` with both paged
kernels in interpret mode over the page pool and the window rings, the
same configuration against a reference with a piece left out, the
configuration's file against the arithmetic it states (recounted from
the built tree), ``readers_afmoe``'s bytes and FLOPs against hand counts
and a recorded trace, and that the cell was added by files alone.  Its
kernels' ahead-of-time cases are aot/*.trinity-longctx.json."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_bench_paths as paths
import drive_serve
import readers
import readers_afmoe
import readers_scopes
import run as harness
import test_chip_bench_manifest as contract

sys.path.insert(0, os.path.join(paths.ROOT, "tests", "unit"))
from xplane_file import write_xspace  # noqa: E402

SEED = 2 ** 31 + 62
CELL = "trinity-large.longctx-batch"
NAME = "trinity-large-preview-l5-ep16"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def serve(config, mix="tiny-closed.json"):
    ctx = harness.Context(paths.ROOT, paths.BENCH, config,
                          load(paths.DATA, mix), SEED, 1.5,
                          devices=jax.devices(),
                          compiles=harness.CompileCount(),
                          config_file="data/afmoe-tiny.json")
    return ctx, harness.run_cell(ctx, "serve")


@pytest.fixture(scope="module")
def served():
    return serve(load(paths.DATA, "afmoe-tiny.json"))


def test_the_family_serves_against_its_reference(served):
    """The tiny file declares no ``reference.routed`` (a CPU window
    serves no 1,024 positions): every served token within eps of the
    float32 reference's best, through the kernels in interpret mode."""
    ctx, res = served
    assert all(res["checks"].values()), (res["checks"], res["compared"])
    assert ctx.window_compiles == 0 and ctx.reference_routed() is None
    worst, eps = res["compared"]["reference_worst_margin"]
    assert worst <= eps
    assert res["notes"]["paged_attention"]["path"] == "kernel"
    assert res["end_to_end"]["served_tokens_per_s"] > 0
    c = res["counters"]
    assert 0 < c["moe_held_assignments"] < c["moe_assignments"]
    assert c["prefix_cache_refused"] == 1      # the CLI's default asked
    # pages for the one full layer, rings of 96 rows for the four others
    slots = load(paths.DATA, "tiny-closed.json")["serve"]["num_slots"]
    assert c["kv_pool_bytes"] == 48 * 16 * 2 * 2 * 16 * 4
    assert c["kv_paged_bytes_per_token"] == 2 * 2 * 16 * 4
    assert c["kv_window_bytes_per_slot"] == 4 * 96 * 256
    assert c["state_pool_bytes"] == slots * 4 * 96 * 256 + 4 * 20
    assert 0 < c["decode_window_tokens"] <= c["decode_kv_tokens"]
    assert 0 < c["prefill_window_pairs"] <= c["prefill_kv_pairs"]
    assert c["prefill_tokens"] <= c["prefill_window_tokens"] <= \
        c["prefill_kv_tokens"]


def test_a_reference_with_another_window_fails_the_served_tokens(
        arg="window", key="sliding_window", value=4):
    """The rule allows a margin of 8 bf16 ulps of the logit scale;
    another window moves served positions past it.  Every other piece
    is held by the float32 comparison of logits in
    tests/unit/test_afmoe.py (one harness run here costs half a minute
    of interpret-mode kernels)."""
    config = load(paths.DATA, "afmoe-tiny.json")
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

CONFIG = load(paths.BENCH, "configs", NAME + ".json")
MIX = load(paths.BENCH, "traffic", "longctx-closed-s32.json")
PEAKS = load(paths.BENCH, "peaks.json")["devices"]["TPU v5e"]


def test_the_cell_is_the_issues():
    assert MIX["serve"] == {"num_slots": 32, "max_pages_per_slot": 130,
                            "max_queue": 128}
    assert CONFIG["serve"] == {"num_pages": 4160}
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_dense_layers", "num_experts",
                                 "vocab_size"]
    was = CONFIG["reduced_from"]
    assert (was["num_hidden_layers"], was["num_dense_layers"],
            was["num_experts"]) == (60, 6, 256)
    assert was["vocab_size"] == 8 * CONFIG["vocab_size"] == 200192
    # published layers 5-9: the last leading dense layer, then one whole
    # period of routed ones, 3 window : 1 full
    assert CONFIG["layer_types"] == was["layer_types"][5:10] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert CONFIG["num_dense_layers"] == 1
    assert CONFIG["num_routed_layers"] == \
        CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"] == 4
    assert set(CONFIG["assumed"]) >= {
        "source", "output_gate", "qk_norm", "rotary", "window",
        "sandwich_norms", "embedding_scale", "expert_bias", "router",
        "shared_expert", "weights", "unused"}
    for words in ("SIXTEEN chips share each layer", "PUBLISHED LAYERS 5-9",
                  "ONE shared batch of 32 slots", "5 of 60 layers"):
        assert words in CONFIG["deployment"]
    assert CONFIG["reference"]["routed"] == {
        "layers": "num_routed_layers", "experts": "num_router_experts",
        "per_token": "num_experts_per_tok", "held": "num_experts"}
    ctx = harness.Context(paths.ROOT, paths.BENCH, CONFIG, MIX, 1, 40,
                          config_file=NAME)
    routed = ctx.reference_routed()
    assert routed == {"layers": 4, "experts": 256, "per_token": 4,
                      "held": 16}
    assert drive_serve.routed_share_max(routed) == \
        pytest.approx(0.00041 * 64)
    manifest_holds(load(paths.ROOT, "BENCHMARK.json"), paths.ROOT)


TRI = ["device.idle_share.tri", "attn.window.time_share.tri",
       "attn.window.roofline.tri", "moe.experts.time_share.tri"]


def manifest_holds(manifest, root):
    """What a manifest has to say of THIS family's cell, whatever else
    it holds: never how many cells, configurations or metrics there are,
    nor which stands last."""
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "longctx-closed-s32", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert entry["file"].endswith(f"configs/{NAME}.json")
    served = next(m for m in manifest["end_to_end"]
                  if m["name"] == "served_tokens_per_s")
    assert CELL in served["workloads"]
    new = contract.cell_metrics(manifest, root, ".tri", CELL)
    assert {m["name"] for m in new} >= set(TRI)
    for m in new:
        assert m["moves"] == "served_tokens_per_s"
    by_name = {m["name"]: m for m in new}
    assert by_name["attn.window.roofline.tri"]["reader"] == \
        "readers_afmoe:window_roofline"
    assert by_name["attn.window.time_share.tri"]["args"] == {
        "component": "attn_core", "tokens": ["swa"]}
    assert by_name["moe.experts.time_share.tri"]["args"]["tokens"] == \
        readers_scopes.vocabulary()[0]["experts"]


def test_the_program_allocates_what_the_file_states():
    """2.510 B parameters, recounted from the built tree; a page of the
    one full layer; a ring a slot of the four window layers."""
    from deepspeed_tpu.models import afmoe
    module = drive_serve.build_module(CONFIG, dtype=jnp.bfloat16,
                                      param_dtype=jnp.bfloat16)
    cfg = module.cfg
    shapes = jax.eval_shape(
        lambda key: module.init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    n = sum(int(a.size) for a in jax.tree.leaves(shapes))
    hid = 3072
    attn = 3 * hid * 48 * 128 + 2 * hid * 8 * 128 + 2 * 128
    assert round(attn / 1e6, 2) == 62.91
    norms = 4 * hid
    dense = attn + norms + 3 * hid * 12288
    routed = attn + norms + hid * 256 + 256 + 3 * hid * 3072 + \
        16 * 3 * hid * 3072
    assert round(dense / 1e6, 1) == 176.2 and round(routed / 1e6, 1) == 545.0
    assert n == dense + 4 * routed + 2 * 25024 * hid + hid
    assert round(n / 1e9, 3) == 2.510
    assert cfg.layer_types == tuple(CONFIG["layer_types"])
    assert (cfg.ring_rows, cfg.route_scale, cfg.rms_eps) == \
        (4352, 2.448, 1e-5)
    pools = jax.eval_shape(lambda: afmoe.init_paged_kv_cache(
        cfg, 4160, 128, jnp.bfloat16, num_slots=32))
    kinds = ["ring" if "k_ring" in e else "pages" for e in pools["layers"]]
    assert kinds == ["ring", "ring", "pages", "ring", "ring"]
    assert pools["layers"][2]["k_pages"].shape == (4160, 128, 8, 128)
    assert pools["layers"][0]["k_ring"].shape == (32, 4352, 8, 128)
    assert readers_afmoe.kv_bytes_per_token_layer(CONFIG) == 4096
    # all five layers paged would cost 20,480 B a token
    assert len(CONFIG["layer_types"]) * 4096 == 20480
    assert afmoe.kv_page_bytes(cfg, 128, jnp.bfloat16) == 524_288
    per_slot = 4 * 4352 * 4096
    assert afmoe.window_ring(cfg) == (4096, per_slot)
    assert round(per_slot / 1e6, 1) == 71.3
    # 2.18 GB of pages and 2.28 GB of rings beside 5.02 GB of weights
    total = 4160 * 524_288 + 32 * per_slot + 2 * n
    assert round(total / 1e9, 2) == 9.48


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(paths.BENCH, "reference_afmoe.py")).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
    import reference_afmoe as ref
    assert callable(ref.hidden) and callable(ref.logits)


# ------------------------------------------------ readers_afmoe

COUNTERS = {"prefill_dispatches": 100, "decode_steps": 200,
            "prefill_window_tokens": 100 * 30 * 3000,
            "prefill_window_pairs": 100 * 30 * 32 * 3000,
            "decode_window_tokens": 200 * 20 * 4000}
CUSTOM = 'custom-call(%q), custom_call_target="tpu_custom_call"'
WINDOW_PATH = "jit(prefill)/AFMoE/layers_3/swa/jit(paged_prefill)/" \
    "paged_prefill:"
METADATA = {
    1: (f"%paged_prefill.3 = bf16[32,8,192,128] {CUSTOM}",
        {"tf_op": WINDOW_PATH}),
    2: (f"%paged_prefill.7 = bf16[32,8,192,128] {CUSTOM}",
        {"tf_op": WINDOW_PATH.replace("layers_3/swa", "layers_2/attn")}),
    3: (f"%swa.5 = bf16[32,8,6,128] {CUSTOM}",
        {"tf_op": "jit(decode_multi)/horizon/while/body/closed_call/AFMoE/"
                  "layers_3/swa/pallas_call:"}),
    4: ("%fusion.9 = bf16[32,32,6144] fusion(%a)",
        {"tf_op": "jit(prefill)/AFMoE/layers_3/swa/attn_proj/wg/"
                  "dot_general:"}),
}
# two window prefill calls of 4 ms, one full-layer call, three window
# decode calls of 1 ms, one projection under swa that is no kernel
OPS = [(1, 4_000_000_000), (1, 4_000_000_000), (2, 9_000_000_000),
       (3, 1_000_000_000), (3, 1_000_000_000), (3, 1_000_000_000),
       (4, 500_000_000)]


def context(monkeypatch, path, counters=COUNTERS, config=CONFIG):
    trace = readers.Trace({"/device:TPU:0": [("op", 0, 10 ** 9)]}, [])
    monkeypatch.setattr(readers_scopes, "trace_file", lambda ctx: path)
    return {"trace": trace, "counters": counters, "config": config,
            "traffic": MIX, "peaks": PEAKS}


def test_what_a_window_layers_call_needs():
    nbytes, flops = readers_afmoe.window_needed(CONFIG, 1000, 5000)
    assert nbytes == 1000 * 8 * 128 * 2 * 2
    assert flops == 5000 * 48 * 2 * (128 + 128)


def test_window_roofline_from_a_recorded_trace(tmp_path, monkeypatch):
    path = write_xspace(tmp_path / "one.xplane.pb", [
        ("/device:TPU:0", METADATA, {"XLA Ops": OPS})])
    calls = readers_afmoe.scoped_kernel_calls(path, "swa")
    assert calls == {"paged_prefill": (2, pytest.approx(8e-3)),
                     "swa": (3, pytest.approx(3e-3))}
    got = readers_afmoe.window_roofline(context(monkeypatch, path))
    pre = max(4096 * 30 * 3000 / 819e9,
              30 * 32 * 3000 * 48 * 512 / 197e12)
    dec = 4096 * 20 * 4000 / 819e9
    # a chunk of 32 queries is 32 x 48 x 512 FLOPs a key of 4,096 B: 192
    # a byte, under the chip's 240, so the read of the window binds
    assert pre == 4096 * 30 * 3000 / 819e9
    assert got == pytest.approx(100 * (2 * pre + 3 * dec) / 11e-3)
    assert 0 < got < 100
    # nothing to read: another family, no counters, no trace, no file,
    # a trace without such a call
    ctx = context(monkeypatch, path)
    assert readers_afmoe.window_roofline(
        dict(ctx, config={"hidden_size": 4096})) is None
    assert readers_afmoe.window_roofline(dict(ctx, counters={})) is None
    assert readers_afmoe.window_roofline(dict(ctx, trace=None)) is None
    bare = write_xspace(tmp_path / "bare.xplane.pb", [
        ("/device:TPU:0", {2: METADATA[2], 4: METADATA[4]},
         {"XLA Ops": [(2, 9_000_000), (4, 500_000)]})])
    assert readers_afmoe.window_roofline(context(monkeypatch, bare)) is None
    monkeypatch.setattr(readers_scopes, "trace_file", lambda ctx: None)
    assert readers_afmoe.window_roofline(ctx) is None


def test_every_new_metric_is_the_cells_and_moves_served_tokens():
    manifest = load(paths.ROOT, "BENCHMARK.json")
    new = [m for m in manifest["per_layer"] if m["name"].endswith(".tri")]
    assert new and len(new) == len({m["name"] for m in new})
    manifest_holds(manifest, paths.ROOT)
