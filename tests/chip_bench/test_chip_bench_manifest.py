"""Every file under configs/, traffic/ and layer_metrics/ against
BENCHMARK.json, and BENCHMARK.json against the contract's limits."""

import json
import os
import re

import pytest

import chip_bench_paths as paths
import run as harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden_size|intermediate_size|latent|state_size|"
                    r"_dim$|_rank$|n_embd|n_inner|expansion|experts_per_tok)")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = load(paths.ROOT, "BENCHMARK.json")
SECTIONS = [
    ("configs", {"name", "source", "file", "reduced", "why"}, set()),
    ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
    ("end_to_end", {"name", "unit", "better", "bound", "source"},
     {"workloads"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
     {"workloads"}),
]

# Every check below is a function of a manifest and of the root of the
# tree that holds it, so that test_chip_bench_family.py can hold a GROWN
# copy (one more cell, configuration, metric) to the same contract.


def bench_of(manifest, root):
    return os.path.join(root, manifest["paths"][0])


def cells_of(manifest, metric):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


def metrics_of(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def check_contract_keys(manifest, root):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(manifest)) < 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["command"][1].startswith(manifest["paths"][0] + "/")
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(root, p))
    # the whole check fits: (2 + 14 n) runs of run_seconds + 60, 180 s a
    # cell to compile, 1200 s spare, in 43200 s — at the full 24 cells
    s = manifest["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 180 + 1200 <= 43200
    # the count's one guard: no family's test pins how many there are
    for section, most in (("workloads", 24), ("configs", 24),
                          ("end_to_end", 16), ("per_layer", 128)):
        assert 1 <= len(manifest[section]) <= most, section


def check_entries(manifest, section, keys, optional):
    names = [e["name"] for e in manifest[section]]
    assert len(names) == len(set(names))
    for e in manifest[section]:
        assert keys <= set(e) <= keys | optional, e["name"]
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], (e["name"], k)


def check_names_do_not_collide(manifest, root):
    names = [m["name"] for m in metrics_of(manifest)]
    assert len(names) == len(set(names))


def check_metric_fields(manifest, metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(cells_of(manifest, metric)) <= \
        {w["name"] for w in manifest["workloads"]}
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def check_setup_s(manifest, root):
    setup = next(m for m in manifest["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup
    assert setup["bound"] <= 0.1


def check_cell(manifest, root, cell):
    configs = {c["name"]: c for c in manifest["configs"]}
    assert cell["config"] in configs
    assert NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    bench = bench_of(manifest, root)
    mix = load(bench, "traffic", cell["traffic"] + ".json")
    assert os.path.isfile(os.path.join(bench, "drive_" + mix["kind"] + ".py"))
    others = [m for m in manifest["end_to_end"] if m["name"] != "setup_s"
              and cell["name"] in cells_of(manifest, m)]
    layers = [m for m in manifest["per_layer"]
              if cell["name"] in cells_of(manifest, m)]
    assert others and layers
    cfg = load(root, configs[cell["config"]]["file"])
    assert mix["kind"] in cfg, "the configuration has no sizes for this kind"


def check_pairs_and_four_chip_cells(manifest, root):
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(pairs) // 4)
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def check_configuration_file(manifest, root, config):
    assert config["file"].startswith(manifest["paths"][0] + "/configs/")
    cfg = load(root, config["file"])
    for key in ("source", "reduced", "assumed", "program", "reference"):
        assert key in cfg, key
    assert cfg["source"] == config["source"]
    assert cfg["reduced"] == config["reduced"]
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key), key
        assert key in cfg and key in cfg.get("reduced_from", {})
    # every field the program's config class is given is a published key
    for src in cfg["program"]["fields"].values():
        assert src in cfg, src
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))


def check_published_widths(manifest, root, config):
    """published/<config>.json (added with the configuration, beside
    these tests) lists the public config's keys that must be carried
    unchanged."""
    cfg = load(root, config["file"])
    pub = load(root, manifest["paths"][1], "published",
               config["name"] + ".json")["keys"]
    assert not set(pub) & set(config["reduced"])
    assert any(WIDTHS.search(k) for k in pub)
    for key, value in pub.items():
        assert cfg[key] == value, key


def check_layer_metric_file(manifest, root, metric):
    bench = bench_of(manifest, root)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    spec = load(bench, "layer_metrics", metric["name"] + ".json")
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    assert cells_of(manifest, spec) == cells_of(manifest, metric)
    # moves names an end-to-end metric that each of its cells reports
    assert metric["moves"] in e2e
    assert set(cells_of(manifest, metric)) <= \
        set(cells_of(manifest, e2e[metric["moves"]]))
    # the reader resolves as the harness resolves it
    assert ":" in spec["reader"]
    assert callable(harness.load_function(bench, spec["reader"]))


def check_no_stray_data_files(manifest, root):
    bench = bench_of(manifest, root)
    have = {f[:-5] for f in os.listdir(os.path.join(bench, "layer_metrics"))}
    assert have == {m["name"] for m in manifest["per_layer"]}
    mixes = {f[:-5] for f in os.listdir(os.path.join(bench, "traffic"))}
    assert {w["traffic"] for w in manifest["workloads"]} <= mixes


def check_layer_spelling(manifest, root):
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


def check_run_py_names_nothing(manifest, root):
    src = open(os.path.join(bench_of(manifest, root), "run.py")).read()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in manifest[k]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += ["paged_decode", "flash", "mistral", "gpt2", "llama"]
    for n in names:
        if n == "setup_s":      # the one metric every run reports
            continue
        assert n not in src, n


def cell_metrics(manifest, root, suffix, cell):
    """The ``per_layer`` entries of one cell's own suffix (``.win``,
    ``.hyb``, ``.par``): one entry a ``layer_metrics/*<suffix>.json`` on
    disk, each listing ``cell`` alone and agreeing with its file; their
    files are returned, in the manifest's order.  A family's
    ``manifest_holds`` starts from these, so that it counts entries
    against files and never against a number."""
    metrics = os.path.join(bench_of(manifest, root), "layer_metrics")
    own = [m for m in manifest["per_layer"] if m["name"].endswith(suffix)]
    assert sorted(m["name"] for m in own) == sorted(
        f[:-len(".json")] for f in os.listdir(metrics)
        if f.endswith(suffix + ".json"))
    specs = [load(metrics, m["name"] + ".json") for m in own]
    for m, spec in zip(own, specs):
        assert m["workloads"] == [cell]
        assert {k: spec[k] for k in m} == m
    return specs


WHOLE = [check_contract_keys, check_names_do_not_collide, check_setup_s,
         check_pairs_and_four_chip_cells, check_no_stray_data_files,
         check_layer_spelling, check_run_py_names_nothing]


def manifest_holds(manifest, root):
    """Every check of this file, over every entry of ``manifest``."""
    for check in WHOLE:
        check(manifest, root)
    for section, keys, optional in SECTIONS:
        check_entries(manifest, section, keys, optional)
    for metric in metrics_of(manifest):
        check_metric_fields(manifest, metric)
    for cell in manifest["workloads"]:
        check_cell(manifest, root, cell)
    for config in manifest["configs"]:
        check_configuration_file(manifest, root, config)
        check_published_widths(manifest, root, config)
    for metric in manifest["per_layer"]:
        check_layer_metric_file(manifest, root, metric)


def test_manifest_has_exactly_the_contract_keys():
    check_contract_keys(MANIFEST, paths.ROOT)


@pytest.mark.parametrize("section,keys,optional", SECTIONS)
def test_entries_have_just_the_contract_keys(section, keys, optional):
    check_entries(MANIFEST, section, keys, optional)


def test_metric_names_do_not_collide_across_sections():
    check_names_do_not_collide(MANIFEST, paths.ROOT)


@pytest.mark.parametrize("metric", metrics_of(MANIFEST),
                         ids=lambda m: m["name"])
def test_metric_fields(metric):
    check_metric_fields(MANIFEST, metric)


def test_setup_s_is_reported_everywhere():
    check_setup_s(MANIFEST, paths.ROOT)


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell(cell):
    check_cell(MANIFEST, paths.ROOT, cell)


def test_cells_are_unique_pairs_and_at_most_one_takes_four_chips():
    check_pairs_and_four_chip_cells(MANIFEST, paths.ROOT)


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_file(config):
    check_configuration_file(MANIFEST, paths.ROOT, config)


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_widths_are_the_published_ones(config):
    check_published_widths(MANIFEST, paths.ROOT, config)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_file_agrees_with_the_manifest(metric):
    check_layer_metric_file(MANIFEST, paths.ROOT, metric)


def test_no_stray_data_files():
    check_no_stray_data_files(MANIFEST, paths.ROOT)


def test_layers_with_one_name_are_spelled_alike():
    check_layer_spelling(MANIFEST, paths.ROOT)


def test_run_py_names_no_cell_config_mix_kernel_or_metric():
    check_run_py_names_nothing(MANIFEST, paths.ROOT)
