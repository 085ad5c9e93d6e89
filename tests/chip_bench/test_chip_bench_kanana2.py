"""The ``deepseek_v3`` family (kanana-2-30b-a3b: multi-head latent
attention over a latent page pool, sigmoid-routed SwiGLU experts beside
a shared MLP) through the harness on the CPU: a test-local tiny
configuration (data/kanana2-tiny.json) served by ``drive_serve`` against
``reference_deepseek_v3.py`` under the eps-argmax rule (open and closed
loop), the same configuration against a reference with a term dropped,
the configuration's file against its published keys and the arithmetic
it states, ``readers_mla``'s bytes and FLOPs against hand counts on a
canned trace, the two ahead-of-time cases, and that the cell was added
by files alone."""

import json
import os
import subprocess

import jax
import jax.numpy as jnp
import pytest

import chip_bench_paths as paths
import drive_serve
import readers
import readers_mla
import run as harness
import test_chip_bench_aot as aot
import test_chip_bench_manifest as contract

SEED = 2 ** 31 + 54
CELL = "kanana2-30b.longdoc-batch"
NAME = "kanana-2-30b-a3b-l12-ep8"
PARENT = "890b84ea6e4d18783c76699765b002e9aeef1a5b"    # PR 53


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def serve(config, mix="tiny-open.json"):
    ctx = harness.Context(paths.ROOT, paths.BENCH, config,
                          load(paths.DATA, mix), SEED, 1.5,
                          devices=jax.devices(),
                          compiles=harness.CompileCount(),
                          config_file="data/kanana2-tiny.json")
    return ctx, harness.run_cell(ctx, "serve")


@pytest.mark.parametrize("mix", ["tiny-open.json", "tiny-closed.json"])
def test_the_family_serves_against_its_reference_under_the_eps_rule(mix):
    """Both paged kernels' shared read in interpret mode, the prefix
    cache on (the CLI's default: a latent page is a page), every served
    token of the sample within eps of the per-head reference's best."""
    tiny = load(paths.DATA, "kanana2-tiny.json")
    ctx, res = serve(tiny, mix)
    assert all(res["checks"].values()), (res["checks"], res["compared"])
    assert ctx.window_compiles == 0
    assert ctx.reference_routed() is None
    worst, eps = res["compared"]["reference_worst_margin"]
    assert worst <= eps == res["notes"]["reference_eps"]
    assert res["notes"]["paged_attention"]["path"] == "kernel"
    assert res["notes"]["paged_attention"]["multi_token"]["path"] == "kernel"
    assert res["notes"]["paged_attention"]["heads"] == [4, 1]
    # the program's counters reach the readers
    c = res["counters"]
    assert c["moe_calls"] > 0
    assert 0 < c["moe_held_assignments"] < c["moe_assignments"]
    assert c["prefix_cache_refused"] == 0 and c["state_pool_bytes"] == 0
    # one vector of 40 float32 a token a layer, three layers, 48 pages
    assert c["kv_latent_bytes_per_token"] == 3 * 40 * 4 == \
        c["kv_stored_bytes_per_token"]
    assert c["kv_pool_bytes"] == 48 * 16 * 3 * 40 * 4
    assert c["decode_steps"] > 0 and c["prefill_kv_tokens"] > 0
    assert c["prefill_kv_pairs"] >= c["prefill_tokens"]
    if mix == "tiny-closed.json":
        assert res["end_to_end"]["served_tokens_per_s"] > 0
    config = dict(tiny, num_hidden_layers=3)
    assert readers_mla.kv_bytes_per_live_token(
        {"counters": c, "config": config}) == 480
    # float32 here: the published arithmetic is in bf16
    assert readers_mla.published_bytes_per_token(config) == 3 * 40 * 2


@pytest.mark.parametrize("arg,key,value", [
    ("scaling", "routed_scaling_factor", 20.0),
    ("eps", "rms_norm_eps", 1.0),
    ("first_held", "first_held_expert", 8),
])
def test_a_reference_with_another_term_fails_the_served_tokens(arg, key,
                                                               value):
    """The rule allows a margin of 8 bf16 ulps of the logit scale;
    another routed scale, another eps in every norm or another share of
    the experts move served positions past it.  The smaller terms (at
    these widths the rotary hardly moves a score; the latent's norm
    weight, the score bias) are held by the float32 comparison of
    logits in tests/unit/test_deepseek_v3_serving.py."""
    config = load(paths.DATA, "kanana2-tiny.json")
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
MIX = load(paths.BENCH, "traffic", "longdoc-closed-s64.json")
PEAKS = load(paths.BENCH, "peaks.json")["devices"]["TPU v5e"]


def test_the_cell_is_the_issues():
    assert MIX["serve"] == {"num_slots": 64, "max_pages_per_slot": 68,
                            "max_queue": 256}
    assert CONFIG["serve"] == {"num_pages": 4352} and 4352 == 64 * 68
    assert (MIX["loop"], MIX["clients"], MIX["pool"],
            MIX["schedule_seed"]) == ("closed", 128, 384, 25)
    assert MIX["prompt_len"] == {"dist": "loguniform", "min": 1024,
                                 "max": 8192}
    assert MIX["output_len"] == {"dist": "uniform", "min": 64, "max": 256}
    assert (MIX["sharing"], MIX["sampling"]) == ("none", "greedy")
    assert set(MIX) == set(load(paths.BENCH, "traffic",
                                "longctx-closed-s32.json"))
    # the longest request fits a slot's pages
    assert 8192 + 256 <= 68 * 128
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert CONFIG["reduced_from"] == {"num_hidden_layers": 48,
                                      "n_routed_experts": 128,
                                      "vocab_size": 128256}
    assert CONFIG["reduced_from"]["vocab_size"] == 8 * CONFIG["vocab_size"]
    assert (CONFIG["num_router_experts"], CONFIG["first_held_expert"],
            CONFIG["num_routed_layers"]) == (128, 0, 11)
    assert set(CONFIG["assumed"]) >= {
        "source", "rotary", "latent", "query", "shared_experts",
        "score_bias", "init", "down_projections", "unused"}
    for word in ("EIGHT chips", "FIRST of four", "ONE shared batch"):
        assert word in CONFIG["deployment"]
    manifest_holds(load(paths.ROOT, "BENCHMARK.json"), paths.ROOT)


def test_the_file_carries_every_published_key_unchanged():
    published = load(os.path.dirname(paths.DATA), "published",
                     NAME + ".json")["keys"]
    assert published["kv_lora_rank"] == 512
    assert {k: CONFIG[k] for k in published} == published
    widths = dict(hidden_size=2048, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128,
                  num_attention_heads=32, intermediate_size=6144,
                  moe_intermediate_size=768, num_experts_per_tok=6,
                  n_shared_experts=2, routed_scaling_factor=2.448,
                  num_router_experts=128)
    assert {k: CONFIG[k] for k in widths} == widths
    assert not set(published) & set(CONFIG["reduced"])
    # the catalog row's keys are the published ones and the reduced ones
    assert set(CONFIG["reduced"]) <= set(CONFIG)


def manifest_holds(manifest, root):
    """What a manifest has to say of THIS family's cell, whatever else
    it holds: never how many cells, configurations or metrics there are,
    nor which stands last."""
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "longdoc-closed-s64", 1)
    config = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert config["source"] == CONFIG["source"] and \
        config["reduced"] == CONFIG["reduced"]
    served = next(m for m in manifest["end_to_end"]
                  if m["name"] == "served_tokens_per_s")
    assert CELL in served["workloads"]
    # one entry a layer_metrics/*.mla.json on disk, each the cell's,
    # each moving served_tokens_per_s
    new = contract.cell_metrics(manifest, root, ".mla", CELL)
    assert {m["name"] for m in new} >= {
        "kernel.paged_decode.roofline.mla",
        "kernel.paged_prefill.roofline.mla", "moe.experts.roofline.mla",
        "attn.mla_absorb.time_share.mla",
        "cache.kv_bytes_per_live_token.mla"}
    for m in new:
        assert m["moves"] == "served_tokens_per_s"
        if "roofline" in m["name"] or m["name"].startswith("cache.kv"):
            assert m["reader"].startswith("readers_mla:")
        else:
            assert not m["reader"].startswith("readers_mla:")


def test_the_program_allocates_what_the_file_states():
    """1.357 B parameters; a page of the twelve latent leaves — from the
    program's own shapes."""
    from deepspeed_tpu.models import deepseek_v3
    module = drive_serve.build_module(CONFIG, dtype=jnp.bfloat16,
                                      param_dtype=jnp.bfloat16)
    cfg = module.cfg
    shapes = jax.eval_shape(
        lambda key: module.init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    n = sum(int(a.size) for a in jax.tree.leaves(shapes))
    attn = 2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256 + \
        32 * 128 * 2048
    routed = 16 * 3 * 2048 * 768 + 3 * 2048 * 1536 + 2048 * 128 + 128
    assert n == 12 * (attn + 2 * 2048) + 3 * 2048 * 6144 + 11 * routed \
        + 2 * 16032 * 2048 + 2048
    assert round(n / 1e9, 3) == 1.357
    assert (cfg.latent_dim, cfg.num_kv_heads, cfg.num_routed_layers) == \
        (576, 1, 11)
    pools = jax.eval_shape(lambda: deepseek_v3.init_paged_kv_cache(
        cfg, 4352, 128, jnp.bfloat16))
    assert all(e["c_pages"].shape == (4352, 128, 640)
               for e in pools["layers"])
    assert [set(e) for e in pools["layers"]] == \
        [{"c_pages"}] + [{"c_pages", "routing", "walked"}] * 11
    # the published widths a token; what the pool pays with 576 at 640
    assert readers_mla.latent_bytes_per_token_layer(CONFIG) == 1152
    assert readers_mla.published_bytes_per_token(CONFIG) == 13824
    assert readers_mla.per_head_bytes_per_token(CONFIG) == 245760
    assert deepseek_v3.latent_bytes_per_token(cfg) == (13824, 15360)
    assert deepseek_v3.kv_page_bytes(cfg, 128, jnp.bfloat16) == \
        12 * 128 * 640 * 2 == 1_966_080
    assert 4352 * 1_966_080 == sum(
        a.size * 2 for e in pools["layers"] for n, a in e.items()
        if n == "c_pages")


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(paths.BENCH, "reference_deepseek_v3.py")).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
    import reference_deepseek_v3 as ref
    assert callable(ref.hidden) and callable(ref.logits)
    # the calling convention: every keyword the file maps is one the
    # functions take
    ctx = harness.Context(paths.ROOT, paths.BENCH, CONFIG, MIX, SEED, 1.0)
    assert ctx.reference("hidden") is ref.hidden
    assert ctx.reference("logits") is ref.logits
    args = ctx.reference_args()
    assert args == dict(layers=12, first_dense=1, eps=1e-6, heads=32,
                        rank=512, nope=128, rope=64, v_dim=128,
                        theta=1000000, interleave=True, per_token=6,
                        scaling=2.448, first_held=0)


# ------------------------------------------------------ readers_mla

def trace_of(events):
    return readers.Trace({"/device:TPU:0": events}, [])


def context(trace, counters, config=CONFIG):
    return {"trace": trace, "counters": counters, "config": config,
            "traffic": MIX, "peaks": PEAKS}


COUNTERS = {"decode_steps": 200, "decode_live_rows": 200 * 17,
            "decode_kv_tokens": 200 * 17 * 3600,
            "prefill_dispatches": 100, "prefill_kv_tokens": 100 * 47 * 2300,
            "prefill_kv_pairs": 100 * 47 * 32 * 2300,
            "moe_calls": 1800, "moe_held_assignments": 1800 * 190,
            "kv_latent_bytes_per_token": 13824,
            "kv_stored_bytes_per_token": 15360}
CUSTOM = 'custom-call(%q), custom_call_target="tpu_custom_call"'


def nothing_to_read(reader, ops, **args):
    for ctx in (context(None, COUNTERS), context(trace_of(ops), {}),
                context(trace_of(ops[-1:]), COUNTERS),
                context(trace_of(ops), COUNTERS, {"hidden_size": 2048})):
        assert reader(ctx, **args) is None


def test_paged_decode_roofline_from_a_canned_trace():
    ops = [(f"%attn.{i} = bf16[64,1,32,512]{{3,2,1,0}} {CUSTOM}",
            i * 10 ** 7, i * 10 ** 7 + 400_000) for i in range(8)]
    ops.append((f"%paged_prefill.1 = bf16[64,1,1024,512]{{3,2,1,0}} "
                f"{CUSTOM}", 0, 10 ** 8))
    args = {"heads": ["attn"], "all_of": ["tpu_custom_call"]}
    got = readers_mla.paged_decode_roofline(
        context(trace_of(ops), COUNTERS), **args)
    # 17 live slots of 3,600 tokens, one vector of 576 x 2 bytes each
    nbytes, flops = readers_mla.decode_needed(CONFIG, 17 * 3600)
    assert nbytes == 1152 * 61200 and flops == 32 * 2 * 1088 * 61200
    assert flops / nbytes == pytest.approx(60.4, abs=0.1)   # bytes bound
    assert got == pytest.approx(100 * 8 * nbytes / 819e9 / (8 * 4e-4))
    assert 0 < got <= 100
    nothing_to_read(readers_mla.paged_decode_roofline, ops, **args)


def test_paged_prefill_roofline_from_a_canned_trace():
    ops = [(f"%paged_prefill.{i} = bf16[64,1,1024,512]{{3,2,1,0}} {CUSTOM}",
            i * 10 ** 7, i * 10 ** 7 + 6_000_000) for i in range(6)]
    ops.append((f"%attn.9 = bf16[64,1,32,512]{{3,2,1,0}} {CUSTOM}", 0, 10))
    args = {"heads": ["paged_prefill"], "all_of": ["tpu_custom_call"]}
    got = readers_mla.paged_prefill_roofline(
        context(trace_of(ops), COUNTERS), **args)
    nbytes, flops = readers_mla.prefill_needed(CONFIG, 47 * 2300,
                                               47 * 32 * 2300)
    assert nbytes == 1152 * 108_100
    assert flops == 32 * 2 * (576 + 512) * 3_459_200
    least = max(nbytes / 819e9, flops / 197e12)
    assert least == flops / 197e12          # a chunk of 32: compute bound
    assert got == pytest.approx(100 * 6 * least / (6 * 6e-3))
    assert 0 < got <= 100
    nothing_to_read(readers_mla.paged_prefill_roofline, ops, **args)


def test_expert_bytes_and_flops_a_call_count_three_matrices_at_768():
    nbytes, flops = readers_mla.experts_needed(CONFIG, 64.0)
    assert flops == 64 * 6 * 2048 * 768
    touched = 16 * (1 - 2.718281828459045 ** -4.0)
    assert nbytes == pytest.approx(touched * 3 * 2048 * 768 * 2)
    ops = [(f"%ragged-dot-none.{i} = bf16[2048,1536] custom-call()",
            i * 10 ** 7, i * 10 ** 7 + 300_000) for i in range(12)]
    ops.append(("%copy.1 = f32[1] copy()", 0, 10 ** 9))
    got = readers_mla.experts_roofline(
        context(trace_of(ops), COUNTERS), heads=["ragged-dot-none"])
    nbytes, flops = readers_mla.experts_needed(CONFIG, 190.0)
    least = max(nbytes / 819e9, flops / 197e12)
    assert got == pytest.approx(100 * 6 * least / (12 * 3e-4))
    assert 0 < got <= 100
    nothing_to_read(readers_mla.experts_roofline, ops,
                    heads=["ragged-dot-none"])


def test_cache_bytes_a_live_token_are_the_pools_own():
    got = readers_mla.kv_bytes_per_live_token(context(None, COUNTERS))
    assert got == 15360
    assert readers_mla.published_bytes_per_token(CONFIG) == 13824 < got
    assert got < readers_mla.per_head_bytes_per_token(CONFIG) / 15
    # a program without the counter (the parent), another family
    assert readers_mla.kv_bytes_per_live_token(context(None, {})) is None
    assert readers_mla.kv_bytes_per_live_token(
        context(None, COUNTERS, {"hidden_size": 2048})) is None


# the accepted metrics of the layers this cell runs, on their own readers
SIBLINGS = ["device.idle_share", "engine.host_busy_share",
            "sched.slot_occupancy", "sched.prefill_step_share",
            "sched.prefill_rows_per_dispatch", "sched.idle_in_boundary",
            "engine.idle_in_dispatch", "cache.page_util_mean",
            "kernel.paged_decode.time_share",
            "kernel.paged_prefill.time_share", "moe.experts.time_share",
            "moe.held_load_max_over_mean",
            "sched.decode_live_rows_per_step"]


@pytest.mark.parametrize("base", SIBLINGS)
def test_an_accepted_metric_reads_this_cell_through_its_own_reader(base):
    """``<base>.mla`` is ``<base>.win`` (the other closed loop with
    experts) but for its name, its cell and its note: the same reader,
    the same arguments."""
    mla = load(paths.BENCH, "layer_metrics", base + ".mla.json")
    win = load(paths.BENCH, "layer_metrics", base + ".win.json")
    assert mla.pop("name") == base + ".mla"
    assert mla.pop("workloads") == [CELL]
    assert win.pop("name") == base + ".win" and win.pop("workloads")
    mla.pop("args_note", None), win.pop("args_note", None)
    assert mla == win


# ------------------------------------- the new shapes compile for the v5e

@pytest.mark.parametrize("case", ["paged_decode.kanana2-longdoc",
                                  "paged_prefill.kanana2-longdoc"])
def test_the_cells_ahead_of_time_cases_are_the_cells_shapes(case):
    """64 slots / 64 rows x chunk 32, 68 pages a row, 32 heads on ONE
    latent head of 576 stored at 640, the value its leading 512, 4,352
    pages.  test_chip_bench_aot.py compiles every case under aot/ for
    the v5e (one process may load libtpu); here the case is held to the
    cell and its builder to a trace at the case's shapes."""
    assert case in aot.CASES
    c = aot.load_case(os.path.join(aot.AOT, case + ".json"))
    assert c["kernel"].startswith("aot_kernels_mla:")
    assert c["kernel"].split(":")[1] not in aot.KERNELS
    assert (c.get("slots", c.get("rows")), c["max_pages"], c["pages"],
            c["page_size"]) == (MIX["serve"]["num_slots"],
                                MIX["serve"]["max_pages_per_slot"],
                                CONFIG["serve"]["num_pages"], 128)
    assert (c["heads"], c["value_dim"], c["scale_dim"]) == (
        CONFIG["num_attention_heads"], CONFIG["kv_lora_rank"],
        CONFIG["qk_nope_head_dim"] + CONFIG["qk_rope_head_dim"])
    from deepspeed_tpu.ops.quant.kv import latent_stored_dim
    assert c["stored_dim"] == latent_stored_dim(
        CONFIG["kv_lora_rank"] + CONFIG["qk_rope_head_dim"]) == 640
    fn, args, least = aot.kernel_builder(c["kernel"])(
        c, jax.ShapeDtypeStruct)
    assert least == 1
    assert jax.eval_shape(fn, *args).shape[-1] == 512


def _git(*args):
    return subprocess.run(["git", *args], cwd=paths.ROOT, timeout=60,
                          capture_output=True, text=True, check=True).stdout


def test_no_file_the_benchmark_had_changed():
    """Against the commit PR 54 started from, under the manifest's
    ``paths``: files added, none modified or deleted (``BENCHMARK.json``
    gained entries and lost none: test_chip_bench_family.py proves that
    of a copy).  Only while this PR is the tree on top of that commit:
    a later PR's tree is held by its own checks."""
    try:
        heads = _git("rev-parse", "HEAD", "HEAD^").split()
        if PARENT not in heads:
            pytest.skip("not the tree of PR 54")
        out = _git("diff", "--name-status", PARENT, "--",
                   "benchmarks/chip", "tests/chip_bench")
    except (OSError, subprocess.SubprocessError):
        pytest.skip("no git history to compare with")
    changed = [line.split("\t") for line in out.splitlines() if line]
    assert [c for c in changed if c[0] != "A"] == []
