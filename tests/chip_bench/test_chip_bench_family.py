"""A model family the harness has never seen enters by files alone: a
test-local OPT-style configuration (data/opt-tiny.json: the program's
GPT2 module with ReLU and positions stored at index + 2) with its plain
float32 reference in a NEW module (data/reference_opt.py), dropped into
a copy of the harness and named ``"reference_opt:<function>"``.  Both
drivers run it on the CPU with every check true; the same configuration
with the reference one layer short is not correct.  Beside it: a bare
name still resolves in reference.py, a name that resolves nowhere ends
the run before any set-up, and every number of the scheduler's own
summary reaches the counter reader."""

import json
import os
import shutil

import jax
import pytest

import chip_bench_paths as paths
import run as harness

MANIFEST = harness.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))


def load(name):
    with open(os.path.join(paths.DATA, name)) as f:
        return json.load(f)


def snapshot(bench):
    out = {}
    for d, _, files in os.walk(bench):
        for f in files:
            if not f.endswith(".pyc"):
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.join(d, f)] = fh.read()
    return out


@pytest.fixture(scope="module")
def family(tmp_path_factory):
    """A copy of the harness plus ONE new file, and proof afterwards
    that no file of the copy was edited."""
    bench = str(tmp_path_factory.mktemp("family") / "chip")
    shutil.copytree(paths.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = snapshot(bench)
    shutil.copy(os.path.join(paths.DATA, "reference_opt.py"), bench)
    yield bench
    after = snapshot(bench)
    assert set(after) - set(before) == {
        os.path.join(bench, "reference_opt.py")}
    for p, content in before.items():
        assert after[p] == content, f"{p} was edited"


def context(bench, config, mix, seconds):
    return harness.Context(paths.ROOT, bench, config, load(mix),
                           2 ** 31 + 29, seconds, devices=jax.devices(),
                           compiles=harness.CompileCount(),
                           config_file="data/opt-tiny.json")


def one_layer_short(config):
    """The reference's layer pattern loses its last entry: the program
    still runs every layer, the reference one fewer."""
    return dict(config, layer_types=config["layer_types"][:-1])


@pytest.fixture(scope="module")
def served(family):
    ctx = context(family, load("opt-tiny.json"), "tiny-open.json", 1.0)
    return ctx, harness.run_cell(ctx, "serve")


def test_unknown_family_serves_against_its_own_reference_module(served):
    ctx, res = served
    assert all(res["checks"].values()), res["checks"]
    assert ctx.window_compiles == 0
    margin, eps = res["compared"]["reference_worst_margin"]
    assert margin <= eps
    # the set-up's phases, in order, all before the window
    phases = ctx.notes["setup_phases"]
    assert 0 < phases["engine_built"] < phases["warm_up_done"] <= ctx.setup_s


def test_unknown_family_trains_against_its_own_reference_module(family):
    ctx = context(family, load("opt-tiny.json"), "tiny-train.json", 0.5)
    res = harness.run_cell(ctx, "train")
    assert all(res["checks"].values()), res["checks"]
    diff, limit = res["compared"]["loss_step0_vs_reference"]
    assert diff < 1e-4 < limit          # float32 on both sides here
    assert res["counters"]["compiled.step_loop"] == 1
    assert set(ctx.notes["setup_phases"]) == {"engine_built", "warm_up_done"}


def test_a_reference_one_layer_short_fails_the_served_tokens(family):
    ctx = context(family, one_layer_short(load("opt-tiny.json")),
                  "tiny-open.json", 1.0)
    checks = harness.run_cell(ctx, "serve")["checks"]
    assert checks.pop("reference") is False
    assert all(checks.values()), checks


def test_a_reference_one_layer_short_fails_the_step0_loss(family):
    ctx = context(family, one_layer_short(load("opt-tiny.json")),
                  "tiny-train.json", 0.5)
    checks = harness.run_cell(ctx, "train")["checks"]
    assert checks.pop("step0_matches_reference") is False
    assert all(checks.values()), checks


def test_reference_args_may_name_a_string_or_a_list(family):
    ctx = context(family, load("opt-tiny.json"), "tiny-train.json", 0.5)
    args = ctx.reference_args()
    assert args["activation"] == "relu"
    assert args["layer_types"] == ["full", "full"] and args["heads"] == 4


@pytest.mark.parametrize("config,key,name", [
    ("gpt2-tiny.json", "loss", "gpt2_loss"),
    ("gpt2-tiny.json", "logits", "gpt2_logits"),
    ("llama-tiny.json", "hidden", "llama_hidden"),
    ("llama-tiny.json", "logits", "llama_logits"),
])
def test_a_bare_name_still_resolves_in_reference_py(config, key, name):
    ctx = harness.Context(paths.ROOT, paths.BENCH, load(config), {}, 0, 1.0)
    fn = ctx.reference(key)
    assert fn.__name__ == name
    assert fn.__module__ == "reference"
    assert fn.__code__.co_filename == os.path.join(paths.BENCH,
                                                   "reference.py")


@pytest.mark.parametrize("config,mix,kind,key,spec", [
    ("gpt2-tiny.json", "tiny-train.json", "train", "loss", "gpt2_lose"),
    ("gpt2-tiny.json", "tiny-train.json", "train", "loss",
     "reference_nowhere:loss"),
    ("llama-tiny.json", "tiny-open.json", "serve", "logits",
     "reference:no_such_logits"),
    ("llama-tiny.json", "tiny-open.json", "serve", "hidden", None),
])
def test_an_unknown_reference_ends_the_run_before_set_up(config, mix, kind,
                                                         key, spec):
    cfg = load(config)
    cfg["reference"] = dict(cfg["reference"], **{key: spec})
    ctx = harness.Context(paths.ROOT, paths.BENCH, cfg, load(mix), 3, 1.0,
                          devices=jax.devices(),
                          config_file="configs/some-model.json")
    with pytest.raises(SystemExit) as err:
        harness.run_cell(ctx, kind)
    message = str(err.value)
    assert "configs/some-model.json" in message
    assert f"reference.{key}" in message
    assert spec is None or spec in message
    assert ctx.notes == {} and ctx.setup_s is None    # nothing was built


def test_every_number_of_the_schedulers_summary_reaches_the_readers(served):
    from deepspeed_tpu.serving.metrics import ServingMetrics
    _, res = served
    numeric = {k for k, v in ServingMetrics().summary().items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    assert len(numeric) > 50
    assert numeric <= set(res["counters"])
    # the driver's own keys win over the program's of the same name
    assert 0 < res["counters"]["slot_occupancy"] <= 1
    assert res["counters"]["prefill_dispatches"] > 0
    assert res["counters"]["prefill_rows"] >= \
        res["counters"]["prefill_dispatches"]


@pytest.mark.parametrize("metric,key,scale", [
    ("sched.prefill_pad_share.lat", "prefill_pad_share", 100.0),
    ("sched.prefill_rows_per_dispatch.thr", "prefill_rows_per_dispatch", 1.0),
])
def test_the_new_scheduler_metrics_read_the_programs_counters(served, metric,
                                                              key, scale):
    _, res = served
    entry = harness.find(MANIFEST["per_layer"], metric, "metric")
    assert entry["source"] == "program_counter"
    got = harness.layer_metrics(paths.BENCH, {"per_layer": [entry]},
                                entry["workloads"][0], {
        "trace": None, "counters": res["counters"], "static": {},
        "end_to_end": res["end_to_end"], "chips": 1, "peaks": {},
        "config": {}, "traffic": {}})
    assert got[metric]["value"] == pytest.approx(
        res["counters"][key] * scale)
    assert got[metric]["value"] >= 0 and metric in got
