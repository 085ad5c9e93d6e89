"""A model family the harness has never seen enters by files alone: a
test-local OPT-style configuration (data/opt-tiny.json: the program's
GPT2 module with ReLU and positions stored at index + 2) with its plain
float32 reference in a NEW module (data/reference_opt.py), dropped into
a copy of the harness and named ``"reference_opt:<function>"``.  Both
drivers run it on the CPU with every check true; the same configuration
with the reference one layer short is not correct.  Beside it: a bare
name still resolves in reference.py, a name that resolves nowhere ends
the run before any set-up, and every number of the scheduler's own
summary reaches the counter reader.

The copy is a whole tree (BENCHMARK.json, benchmarks/chip,
tests/chip_bench), and it GROWS as a ``model_config`` PR would grow it:
an eighth cell on an existing closed-loop mix, a seventh configuration
with its published widths, two per-layer metric files, an ahead-of-time
case whose kernel is ``"module:function"`` of a new file beside the
tests, and the manifest's entries for them.  No file that was there is
edited, the manifest only gains entries, and every family's
manifest-level assertions (each module's ``manifest_holds``) pass on
the grown manifest: no test pins how many cells, configurations or
metrics there are, or which stands last."""

import copy
import importlib
import json
import os
import shutil

import jax
import pytest

import chip_bench_paths as paths
import readers
import run as harness

MANIFEST = harness.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_REL, TESTS_REL = MANIFEST["paths"]
# the modules of this directory that hold manifest-level assertions as a
# function of a manifest and its tree's root (read as text: nothing is
# imported while the tests are collected)
FAMILIES = sorted(
    f[:-3] for f in os.listdir(HERE)
    if f.startswith("test_chip_bench_") and f.endswith(".py")
    and "\ndef manifest_holds(manifest, root)" in open(
        os.path.join(HERE, f)).read())
NEW_CELL, NEW_CONFIG = "opt-tiny.longprompt-batch", "opt-tiny"
NEW_METRICS = {"sched.slot_occupancy.opt": "sched.slot_occupancy.thr",
               "kernel.paged_prefill.time_share.opt":
               "kernel.paged_prefill.time_share.thr"}


def load(name):
    with open(os.path.join(paths.DATA, name)) as f:
        return json.load(f)


def snapshot(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if not f.endswith(".pyc"):
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.join(d, f)] = fh.read()
    return out


def dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)
    return os.path.join(*parts)


def grow(root):
    """What a ``model_config`` PR adds, as files and manifest entries
    alone.  Returns the paths it added."""
    bench = os.path.join(root, BENCH_REL)
    tests = os.path.join(root, TESTS_REL)
    added = [
        shutil.copy(os.path.join(paths.DATA, "reference_opt.py"), bench),
        shutil.copy(os.path.join(paths.DATA, "opt-tiny.json"),
                    os.path.join(bench, "configs", NEW_CONFIG + ".json")),
        # the kind of kernel the family brings, compiled ahead of time
        shutil.copy(os.path.join(paths.DATA, "aot_kernels_opt.py"), tests),
        shutil.copy(os.path.join(paths.DATA, "paged_prefill.opt-tiny.json"),
                    os.path.join(tests, "aot")),
    ]
    config = load("opt-tiny.json")
    added.append(dump({"keys": {k: config[k] for k in (
        "hidden_size", "ffn_dim", "num_attention_heads", "vocab_size")}},
        tests, "published", NEW_CONFIG + ".json"))
    manifest = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": NEW_CONFIG, "source": config["source"],
        "file": f"{BENCH_REL}/configs/{NEW_CONFIG}.json",
        "reduced": config["reduced"], "why": "a family by files alone"})
    manifest["workloads"].append({
        "name": NEW_CELL, "config": NEW_CONFIG,
        "traffic": "longprompt-closed", "chips": 1,
        "why": "an eighth cell on a closed-loop mix that is there"})
    harness.find(manifest["end_to_end"], "served_tokens_per_s",
                 "metric")["workloads"].append(NEW_CELL)
    # two metrics of the cell: an accepted metric's file but for its
    # name and its cell, as the MiMo cell's .win files are
    for name, sibling in NEW_METRICS.items():
        spec = dict(harness.load_json(os.path.join(
            bench, "layer_metrics", sibling + ".json")),
            name=name, workloads=[NEW_CELL])
        added.append(dump(spec, bench, "layer_metrics", name + ".json"))
        manifest["per_layer"].append({k: spec[k] for k in harness.find(
            manifest["per_layer"], sibling, "metric")})
    dump(manifest, root, "BENCHMARK.json")
    return set(added)


def only_gained_entries(before, after):
    """``after`` is ``before`` with entries appended: nothing that was
    there is gone, changed or moved, but a ``workloads`` list may have
    grown at its end."""
    assert {k: v for k, v in after.items() if isinstance(v, (int, str))} \
        == {k: v for k, v in before.items() if isinstance(v, (int, str))}
    assert after["command"] == before["command"]
    assert after["paths"] == before["paths"]
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(after[section]) >= len(before[section])
        for was, now in zip(before[section], after[section]):
            grew = dict(now)
            if "workloads" in was:
                n = len(was["workloads"])
                assert now["workloads"][:n] == was["workloads"]
                grew["workloads"] = was["workloads"]
            assert grew == was, was["name"]


@pytest.fixture(scope="module")
def family(tmp_path_factory):
    """The harness's directory in a copy of the benchmark's tree that
    has grown by a family, a cell and their files, and proof afterwards
    that no file of the copy was edited."""
    root = str(tmp_path_factory.mktemp("family"))
    for rel in (BENCH_REL, TESTS_REL):
        shutil.copytree(os.path.join(paths.ROOT, rel),
                        os.path.join(root, rel),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(paths.ROOT, "BENCHMARK.json"), root)
    before = snapshot(root)
    added = grow(root)
    yield os.path.join(root, BENCH_REL)
    after = snapshot(root)
    assert set(after) - set(before) == added
    manifest = os.path.join(root, "BENCHMARK.json")
    for p, content in before.items():
        if p != manifest:
            assert after[p] == content, f"{p} was edited"
    only_gained_entries(json.loads(before[manifest]),
                        json.loads(after[manifest]))


def root_of(bench):
    return bench[:-len(BENCH_REL)].rstrip(os.sep)


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


# --------------------------------------- the copy grew, and nothing broke

def test_the_copy_grew_by_a_cell_a_configuration_and_two_metrics(family):
    grown = harness.load_json(os.path.join(root_of(family), "BENCHMARK.json"))
    assert len(grown["workloads"]) == len(MANIFEST["workloads"]) + 1
    assert len(grown["configs"]) == len(MANIFEST["configs"]) + 1
    assert len(grown["per_layer"]) == len(MANIFEST["per_layer"]) + 2
    only_gained_entries(MANIFEST, grown)
    with pytest.raises(AssertionError):       # a changed bound is no gain
        worse = copy.deepcopy(grown)
        worse["end_to_end"][0]["bound"] = 0.09
        only_gained_entries(MANIFEST, worse)
    with pytest.raises(AssertionError):       # nor is a cell taken away
        fewer = copy.deepcopy(grown)
        fewer["end_to_end"][0]["workloads"].pop(0)
        only_gained_entries(MANIFEST, fewer)


@pytest.mark.parametrize("module", FAMILIES)
def test_every_familys_manifest_assertions_hold_on_the_grown_manifest(
        family, module):
    """Each module's ``manifest_holds(manifest, root)`` is what its own
    tests hold the real BENCHMARK.json to."""
    root = root_of(family)
    grown = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    importlib.import_module(module).manifest_holds(grown, root)


def test_the_families_found_are_the_ones_with_a_cell():
    assert set(FAMILIES) >= {
        "test_chip_bench_manifest", "test_chip_bench_mimo_v2",
        "test_chip_bench_nemotron", "test_chip_bench_falcon_h1",
        "test_chip_bench_four_chips"}


def test_the_eighth_cell_loads_and_its_metrics_read(family):
    """The harness's own loader finds the cell's files by the names the
    grown manifest gives, and its two metrics, each by the reader its
    own file names, read a synthetic trace and counters — and no other
    cell's metric does."""
    root = root_of(family)
    loaded = harness.load_cell(root, family, NEW_CELL)
    assert loaded["config"]["name"] == NEW_CONFIG
    assert loaded["config_file"] == f"{BENCH_REL}/configs/{NEW_CONFIG}.json"
    assert loaded["traffic"] == harness.load_json(os.path.join(
        paths.BENCH, "traffic", "longprompt-closed.json"))
    kernel = ('%paged_prefill.1 = bf16[16,8,128,128]{3,2,1,0} custom-call('
              '%q), custom_call_target="tpu_custom_call"')
    trace = readers.Trace({"/device:TPU:0": [
        (kernel, 0, 250), ("%fusion.1 = bf16[16] fusion(%x)", 250, 1000)]},
        [])
    ctx = harness.reader_context(
        loaded, {"counters": {"slot_occupancy": 0.5}, "static": {}},
        {"setup_s": 1.0}, trace, {"flops_per_s": 1.0})
    got = harness.layer_metrics(family, loaded["manifest"], NEW_CELL, ctx)
    assert got == {
        "sched.slot_occupancy.opt": {"value": 50.0, "unit": "%"},
        "kernel.paged_prefill.time_share.opt": {"value": 25.0, "unit": "%"}}
    e2e = harness.end_to_end_metrics(
        loaded["manifest"], NEW_CELL,
        {"served_tokens_per_s": 10.0, "setup_s": 1.0, "ttft_p90_ms": 5.0})
    assert set(e2e) == {"served_tokens_per_s", "setup_s"}


def test_the_eighth_cells_configuration_serves_a_closed_loop(family):
    ctx = context(family, load("opt-tiny.json"), "tiny-closed.json", 1.0)
    res = harness.run_cell(ctx, "serve")
    assert all(res["checks"].values()), (res["checks"], res["compared"])
    assert ctx.window_compiles == 0
    assert res["end_to_end"]["served_tokens_per_s"] > 0
    assert res["notes"]["late_runs_mean_over_step"] == 0


def test_the_added_aot_case_resolves_from_the_file_beside_the_copys_tests(
        family):
    """test_chip_bench_aot.py compiles such a case for the v5e; here,
    without the topology, the copy's case resolves in the copy's file
    and its function traces at the case's shapes."""
    import test_chip_bench_aot as aot
    tests = os.path.join(root_of(family), TESTS_REL)
    case = aot.load_case(os.path.join(tests, "aot",
                                      "paged_prefill.opt-tiny.json"))
    builder = aot.kernel_builder(case["kernel"], beside=tests)
    assert builder.__code__.co_filename == os.path.join(
        tests, "aot_kernels_opt.py")
    with pytest.raises(KeyError):
        aot.kernel_builder(case["kernel"].split(":")[1])
    fn, args, min_calls = builder(case, jax.ShapeDtypeStruct)
    assert min_calls == 1 and args[0].shape == (4, 32, 4, 128)
