"""The serving check can hold a routed model.  A test-local routed
family (data/reference_routed.py, data/routed-tiny.json: the calibration
toy's layers at tiny widths, a 128-score top-6 router holding 32
experts) is dropped into a copy of the harness, as a later PR would
bring it.  Its bf16 twin stands where a program would: faultless, it
passes the routed rule and FAILS the dense one (a swapped expert moves
a logit by more than rounding does), and each fault of the calibration
fails the routed rule.  A configuration with no ``routed`` key is held
to the dense rule as the parent held it, number for number; a ``routed``
block that names what its file lacks ends the run before set-up; and
the last line of a run names the check that failed and the numbers it
compared."""

import json
import os
import shutil
import types

import jax
import numpy as np
import pytest

import chip_bench_paths as paths
import drive_serve
import loadgen
import run as harness

MANIFEST = harness.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))
SEED = 2 ** 31 + 5
REQUESTS, PROMPT, NEW = 29, 16, 36      # 1,044 served positions
FAULTS = ("fp8", "kminus1", "no_routed", "swap_all", "other")


def load(name):
    with open(os.path.join(paths.DATA, name)) as f:
        return json.load(f)


def parent_check_outputs(engine, ref_hidden, ref_logits, ref_args, rows, cap,
                         seed):
    """``drive_serve.check_outputs`` as the parent commit (PR 29) had
    it, copied word for word: what "the dense rule left as it is" is
    held against."""
    import jax.numpy as jnp
    EPS_ULPS, CHECK_SAMPLE = 8, 4
    done = [r for r in rows if r["state"] == "finished" and r["n_out"] > 0]
    if not done:
        return False, {"reference": "no finished request to check"}
    rng = np.random.default_rng(loadgen.seed_words(seed))
    pick = [done[i] for i in rng.choice(len(done), min(CHECK_SAMPLE,
                                                       len(done)), False)]
    new = max(r["max_new"] for r in pick)
    ids = np.zeros((len(pick), cap), np.int32)
    pos = np.zeros((len(pick), new), np.int32)
    valid = np.zeros((len(pick), new), bool)
    for j, r in enumerate(pick):
        p, t = r["prompt"], r["req"].out_tokens
        ids[j, :len(p)] = p
        ids[j, len(p):len(p) + len(t)] = t
        # logits at position i score token i + 1
        pos[j, :len(t)] = len(p) - 1 + np.arange(len(t))
        valid[j, :len(t)] = True
    with jax.default_matmul_precision("highest"):
        hidden = ref_hidden(engine.params, jnp.asarray(ids), **ref_args)
        rows_h = jnp.take_along_axis(hidden, jnp.asarray(pos)[..., None], 1)
        lg = ref_logits(engine.params, rows_h)
    served = jnp.take_along_axis(jnp.asarray(ids), jnp.asarray(pos) + 1, 1)
    got = jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
    margin = np.asarray(jnp.max(lg, -1) - got)[valid]
    scale = float(np.asarray(jnp.max(jnp.abs(lg), -1))[valid].max())
    exact = int((np.asarray(jnp.argmax(lg, -1) == served))[valid].sum())
    eps = EPS_ULPS * 2.0 ** -8 * scale
    worst = float(margin.max())
    notes = {"reference_worst_margin": worst, "reference_eps": eps,
             "reference_logit_scale": scale,
             "reference_exact_argmax": [exact, int(valid.sum())],
             "reference_requests": len(pick)}
    return bool(np.all(np.isfinite(margin)) and worst <= eps), notes


def test_the_dense_rule_is_the_parents():
    assert drive_serve.EPS_ULPS == 8 and drive_serve.CHECK_SAMPLE == 4


@pytest.fixture(scope="module")
def family(tmp_path_factory):
    """A copy of the harness plus ONE new file, as a model_config PR
    would bring the family."""
    bench = str(tmp_path_factory.mktemp("routed") / "chip")
    shutil.copytree(paths.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(paths.DATA, "reference_routed.py"), bench)
    return bench


def finished(prompts, tokens):
    return [{"state": "finished", "n_out": len(t), "max_new": len(t),
             "prompt": p, "req": types.SimpleNamespace(out_tokens=list(t))}
            for p, t in zip(prompts, tokens)]


@pytest.fixture(scope="module")
def twin(family):
    """The routed family's reference through the harness's own lookup,
    its seeded weights, and what its bf16 twin serves: faultless and
    with each fault switched on."""
    config = load("routed-tiny.json")
    ctx = harness.Context(paths.ROOT, family, config, {}, SEED, 1.0,
                          config_file="data/routed-tiny.json")
    ref = harness.load_module(family, "reference_routed")
    params = ref.init_params(5, config)
    prompts = np.random.default_rng(5).integers(
        0, config["vocab_size"], (REQUESTS, PROMPT)).astype(np.int32)
    tokens = {f: ref.served(params, prompts, NEW, config, f)
              for f in (None,) + ref.FAULTS}
    tokens["other"] = np.roll(tokens[None], 1, 0)

    def check_args(fault):
        """``check_outputs``'s arguments for what the twin served."""
        return (types.SimpleNamespace(params=params),
                ctx.reference("hidden"), ctx.reference("logits"),
                ctx.reference_args(), finished(prompts, tokens[fault]),
                PROMPT + NEW, SEED)
    return ctx, check_args


def test_the_routed_block_is_read_from_the_configurations_own_keys(twin):
    ctx, _ = twin
    assert ctx.reference_routed() == {"layers": 6, "experts": 128,
                                      "per_token": 6, "held": 32}
    assert ctx.reference("hidden").__module__ == "reference_routed"


def test_the_bf16_twin_passes_the_routed_rule_and_fails_the_dense_one(twin):
    ctx, check_args = twin
    ok, notes = drive_serve.check_outputs(*check_args(None),
                                          ctx.reference_routed())
    assert ok, notes
    assert notes["reference_positions"] >= drive_serve.ROUTED_MIN_POSITIONS
    # some served tokens miss by more than rounding: a swapped expert
    assert notes["reference_worst_margin"] > notes["reference_eps"]
    assert 0 < notes["reference_over_eps_share"] <= \
        notes["reference_share_limit"]
    dense_ok, dense = drive_serve.check_outputs(*check_args(None))
    assert dense_ok is False
    assert dense["reference_worst_margin"] > dense["reference_eps"]
    assert "reference_share_limit" not in dense


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_the_calibration_fails_the_routed_rule(twin, fault):
    ctx, check_args = twin
    ok, notes = drive_serve.check_outputs(*check_args(fault),
                                          ctx.reference_routed())
    assert ok is False, notes
    assert notes["reference_over_eps_share"] > notes["reference_share_limit"]
    compared = drive_serve.reference_compared(notes)
    share, limit = compared["reference_over_eps_share"]
    assert share > limit


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_without_the_key_the_parents_rule_decides_number_for_number(twin,
                                                                    fault):
    """The dense rule on the twin's rows (it fails them all, the
    faultless twin by a swapped expert): same sample, same margin, same
    eps, same verdict as the parent's function."""
    _, check_args = twin
    got = drive_serve.check_outputs(*check_args(fault))
    assert got == parent_check_outputs(*check_args(fault))
    assert got[0] is False


@pytest.fixture(scope="module")
def served_dense():
    """llama-tiny through the serve driver: a configuration with no
    ``routed`` key, and the rows its window finished."""
    kept = {}
    real = drive_serve.check_outputs

    def keep(*args):
        kept["args"] = args
        return real(*args)
    ctx = harness.Context(paths.ROOT, paths.BENCH, load("llama-tiny.json"),
                          load("tiny-open.json"), SEED, 1.5,
                          devices=jax.devices(),
                          compiles=harness.CompileCount())
    drive_serve.check_outputs = keep
    try:
        res = harness.run_cell(ctx, "serve")
    finally:
        drive_serve.check_outputs = real
    return ctx, res, kept["args"]


def test_a_dense_configuration_reads_what_it_read_at_the_parent(served_dense):
    ctx, res, args = served_dense
    assert args[7] is None                      # no routed block
    want_ok, want = parent_check_outputs(*args[:7])
    assert want_ok is True and res["checks"]["reference"] is True
    for key in ("reference_worst_margin", "reference_eps",
                "reference_logit_scale", "reference_exact_argmax",
                "reference_requests"):
        assert res["notes"][key] == want[key], key
    assert res["compared"]["reference_worst_margin"] == [
        want["reference_worst_margin"], want["reference_eps"]]
    assert "reference_over_eps_share" not in res["compared"]


def routed_llama(**keys):
    """llama-tiny with a ``routed`` block: the block's wiring through the
    serve driver (a dense program under the routed rule reads share 0)."""
    config = load("llama-tiny.json")
    config["reference"]["routed"] = {
        "layers": "num_hidden_layers", "experts": "num_attention_heads",
        "per_token": "num_key_value_heads"}
    config["reference"]["routed"].update(keys)
    return config


@pytest.mark.parametrize("min_positions,verdict", [(40, True),
                                                   (10 ** 6, False)])
def test_the_serve_driver_holds_a_declared_router_to_the_routed_rule(
        monkeypatch, min_positions, verdict):
    """Too few served positions to judge a share is not correct: the
    window has to finish enough of them."""
    monkeypatch.setattr(drive_serve, "ROUTED_MIN_POSITIONS", min_positions)
    ctx = harness.Context(paths.ROOT, paths.BENCH, routed_llama(),
                          load("tiny-open.json"), SEED, 1.5,
                          devices=jax.devices(),
                          compiles=harness.CompileCount())
    res = harness.run_cell(ctx, "serve")
    checks = dict(res["checks"])
    assert checks.pop("reference") is verdict
    assert all(checks.values()), checks
    assert res["compared"]["reference_over_eps_share"][0] == 0.0
    got, least = res["compared"]["reference_positions_at_least"]
    assert least == min_positions and (got >= least) is verdict
    assert got >= 40 and res["notes"]["reference_requests"] >= 4
    worst, cap = res["compared"]["reference_worst_margin"]
    assert worst <= res["notes"]["reference_eps"] < cap


@pytest.mark.parametrize("block,keys,names", [
    ({"layers": "num_routed_layers"}, {}, ("layers", "num_routed_layers")),
    ({"experts": "n_routed_experts"}, {}, ("experts", "n_routed_experts")),
    ({"per_token": None}, {}, ("per_token",)),
    ({"layers": "rope_theta"}, {}, ("layers", "rope_theta")),
    ({"experts": "tie_word_embeddings"}, {},
     ("experts", "tie_word_embeddings")),
    ({"held": "held_experts"}, {}, ("held", "held_experts")),
    ({"held": "held_experts"}, {"held_experts": 5}, ("4 experts", "5")),
    ({"per_token": "vocab_size"}, {}, ("4 experts", "256 a token")),
    ({"top_k": "num_key_value_heads"}, {}, ("top_k",)),
])
def test_a_routed_block_its_file_cannot_answer_ends_the_run_before_set_up(
        block, keys, names):
    ctx = harness.Context(paths.ROOT, paths.BENCH,
                          dict(routed_llama(**block), **keys),
                          load("tiny-open.json"), 3, 1.0,
                          devices=jax.devices(),
                          config_file="configs/some-model.json")
    with pytest.raises(SystemExit) as err:
        harness.run_cell(ctx, "serve")
    message = str(err.value)
    assert "configs/some-model.json" in message
    assert "reference.routed" in message
    for name in names:
        assert name in message
    assert ctx.notes == {} and ctx.setup_s is None    # nothing was built


@pytest.mark.parametrize("layers,held,want", [
    (6, 32, 0.00041 * 6 * 32),            # the envelope: layers x held
    (11, 16, 0.00041 * 11 * 16),
    (12, None, 0.30),                     # all 128 held: its cap
])
def test_the_share_limit_comes_from_the_routed_block(layers, held, want):
    config = {"l": layers, "e": 128, "k": 6, "h": held,
              "reference": {"routed": {"layers": "l", "experts": "e",
                                       "per_token": "k"}}}
    if held is not None:
        config["reference"]["routed"]["held"] = "h"
    routed = harness.Context(paths.ROOT, paths.BENCH, config, {}, 0,
                             1.0).reference_routed()
    assert routed["held"] == (held or 128)
    assert drive_serve.routed_share_max(routed) == pytest.approx(want)


def fake_rows(lengths):
    return finished([np.zeros(4, np.int32)] * len(lengths),
                    [np.zeros(n, np.int32) for n in lengths])


@pytest.fixture
def canned(monkeypatch):
    """``teacher_force`` replaced by canned margins (scale 1, so eps is
    8 / 256): the rule alone, and the sample it takes."""
    state = {"margins": None, "blocks": []}

    def fake(engine, ref_hidden, ref_logits, ref_args, pick, cap):
        n = sum(r["n_out"] for r in pick)
        state["blocks"].append([r["n_out"] for r in pick])
        m = np.zeros(n) if state["margins"] is None else \
            state["margins"][:n]
        if state["margins"] is not None:
            state["margins"] = state["margins"][n:]
        return m, 1.0, int((m == 0).sum())
    monkeypatch.setattr(drive_serve, "teacher_force", fake)
    return state


ROUTED = {"layers": 6, "experts": 128, "per_token": 6, "held": 32}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_the_sample_grows_until_the_rule_has_its_positions(canned, seed):
    lengths = [30 + 7 * (i % 9) for i in range(60)] + [400]
    ok, notes = drive_serve.check_outputs(
        None, None, None, {}, fake_rows(lengths), 512, seed, ROUTED)
    assert ok
    blocks = canned["blocks"]
    assert blocks[0][0] == 400                       # the longest is in
    assert all(len(b) <= drive_serve.CHECK_SAMPLE for b in blocks)
    took = [n for b in blocks for n in b]
    least = drive_serve.ROUTED_MIN_POSITIONS
    assert sum(took) >= least > sum(took[:-1])       # and no more
    assert notes["reference_positions"] == sum(took)
    assert notes["reference_requests"] == len(took) > 4
    # another seed, another sample
    canned["blocks"] = []
    drive_serve.check_outputs(None, None, None, {}, fake_rows(lengths), 512,
                              seed + 1, ROUTED)
    assert canned["blocks"] != blocks


def margins_with(n, over, worst):
    """n margins in units of eps: ``over`` of them at 1.5, one of those
    at ``worst``."""
    m = np.zeros(n)
    m[:over] = 1.5
    if over:
        m[0] = worst
    return m * (8 / 256)


def cases():
    n = drive_serve.ROUTED_MIN_POSITIONS
    share, cap = (drive_serve.routed_share_max(ROUTED),
                  drive_serve.ROUTED_WORST_MAX)
    at = int(share * n)
    return [("share at the limit", margins_with(n, at, 1.5), True),
            ("share over the limit", margins_with(n, at + 1, 1.5), False),
            ("worst at the cap", margins_with(n, 3, cap), True),
            ("worst over the cap", margins_with(n, 3, cap * 1.01), False),
            ("one margin not finite", margins_with(n, 1, float("nan")),
             False),
            ("one margin infinite", margins_with(n, 1, float("inf")),
             False),
            ("no margin over eps", margins_with(n, 0, 0), True)]


@pytest.mark.parametrize("name,margins,verdict", cases(),
                         ids=[c[0] for c in cases()])
def test_the_routed_rule_at_its_limits(canned, name, margins, verdict):
    canned["margins"] = margins
    n = drive_serve.ROUTED_MIN_POSITIONS
    ok, notes = drive_serve.check_outputs(
        None, None, None, {}, fake_rows([n // 4] * 4), 512, 1, ROUTED)
    assert ok is verdict, notes
    compared = drive_serve.reference_compared(notes)
    assert set(compared) == {"reference_over_eps_share",
                             "reference_worst_margin",
                             "reference_positions_at_least"}
    assert notes["reference_exact_argmax"][1] == n


def one_layer_short(config, layers_key):
    """The reference is told of one layer fewer than the program runs."""
    args = dict(config["reference"]["args"], layers="reference_layers")
    return dict(config, reference_layers=config[layers_key] - 1,
                reference=dict(config["reference"], args=args))


LINE_KEYS = ["correct", "attempted", "failed", "device", "metrics", "notes"]


@pytest.mark.parametrize("config,layers_key,mix,kind,check,number", [
    ("llama-tiny.json", "num_hidden_layers", "tiny-open.json", "serve",
     "reference", "reference_worst_margin"),
    ("gpt2-tiny.json", "n_layer", "tiny-train.json", "train",
     "step0_matches_reference", "loss_step0_vs_reference"),
])
def test_the_last_line_names_the_check_that_failed_and_its_numbers(
        config, layers_key, mix, kind, check, number):
    cell = {"serve": "mistral7b.chat", "train": "gpt2-medium.train"}[kind]
    ctx = harness.Context(paths.ROOT, paths.BENCH,
                          one_layer_short(load(config), layers_key),
                          load(mix), SEED, 1.0, devices=jax.devices(),
                          compiles=harness.CompileCount())
    res = harness.run_cell(ctx, kind)
    device = {"platform": "cpu", "kind": "cpu", "count": 8,
              "memory_peak_bytes": 1}
    e2e = dict(res["end_to_end"], setup_s=ctx.setup_s)
    line = json.loads(json.dumps(harness.result_line(
        res, ctx, device, harness.end_to_end_metrics(MANIFEST, cell, e2e))))
    assert line["correct"] is False
    assert list(line) == LINE_KEYS
    assert list(line["notes"]) == ["setup_phases", "checks", "compared"]
    checks = dict(line["notes"]["checks"])
    assert checks.pop(check) == 0
    assert checks and set(checks.values()) == {1}
    assert set(checks) | {check} == set(res["checks"])
    assert checks["no_compile_in_window"] == 1
    value, limit = line["notes"]["compared"][number]
    assert value > limit > 0
    assert line["notes"]["compared"]["window_compiles"] == [0, 0]
    for pair in line["notes"]["compared"].values():
        assert len(pair) == 2
    # the metrics are the cell's end-to-end metrics, as before
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if harness.reports(m, cell)}
    assert set(line["metrics"]) == want and "setup_s" in want


def test_the_last_line_of_a_passing_run_gains_the_two_notes_alone(
        served_dense):
    ctx, res, _ = served_dense
    layer = {"sched.slot_occupancy.lat": {"value": 42.0, "unit": "%"}}
    line = harness.result_line(res, ctx, {"platform": "cpu"}, layer,
                               {"device_ops": [], "idle_gaps": []})
    assert line["correct"] is True
    assert list(line) == LINE_KEYS[:5] + ["breakdown", "notes"]
    assert line["metrics"] is layer
    assert set(line["notes"]["checks"].values()) == {1}
    assert line["notes"]["setup_phases"] == ctx.notes["setup_phases"]
    assert {"reference_worst_margin", "failed_requests", "lateness_median_s",
            "window_compiles"} == set(line["notes"]["compared"])


@pytest.mark.parametrize("value,want", [
    (float("nan"), "nan"), (float("inf"), "inf"), (float("-inf"), "-inf"),
    (1.5, 1.5), (3, 3), (None, None)])
def test_a_number_that_is_not_finite_keeps_the_line_json(value, want):
    assert harness.plain(value) == want
    json.loads(json.dumps({"compared": [harness.plain(value), 1.0]},
                          allow_nan=False))
