"""Every file under configs/, traffic/ and layer_metrics/ against
BENCHMARK.json, and BENCHMARK.json against the contract's limits."""

import json
import os
import re

import pytest

import chip_bench_paths as paths

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden_size|intermediate_size|latent|state_size|"
                    r"_dim$|_rank$|n_embd|n_inner|expansion|experts_per_tok)")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = load(paths.ROOT, "BENCHMARK.json")
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(paths.ROOT, p))
    n = len(MANIFEST["workloads"])
    # the whole check fits: (2 + 14 n) runs of run_seconds + 60, 180 s a
    # cell to compile, 1200 s spare, in 43200 s — at the full 24 cells
    s = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= n <= 24


@pytest.mark.parametrize("section,keys,optional", [
    ("configs", {"name", "source", "file", "reduced", "why"}, set()),
    ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
    ("end_to_end", {"name", "unit", "better", "bound", "source"},
     {"workloads"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
     {"workloads"}),
])
def test_entries_have_just_the_contract_keys(section, keys, optional):
    names = [e["name"] for e in MANIFEST[section]]
    assert len(names) == len(set(names))
    for e in MANIFEST[section]:
        assert keys <= set(e) <= keys | optional, e["name"]
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], (e["name"], k)


def test_metric_names_do_not_collide_across_sections():
    names = [m["name"] for m in MANIFEST["end_to_end"] +
             MANIFEST["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"] +
                         MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(cells_of(metric)) <= set(CELLS)
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_setup_s_is_reported_everywhere():
    assert "workloads" not in E2E["setup_s"]
    assert E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell(cell):
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert cell["config"] in configs
    assert NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    mix = load(paths.BENCH, "traffic", cell["traffic"] + ".json")
    assert os.path.isfile(os.path.join(paths.BENCH,
                                       "drive_" + mix["kind"] + ".py"))
    others = [m for m in MANIFEST["end_to_end"]
              if m["name"] != "setup_s" and cell["name"] in cells_of(m)]
    layers = [m for m in MANIFEST["per_layer"]
              if cell["name"] in cells_of(m)]
    assert others and layers
    cfg = load(paths.ROOT, configs[cell["config"]]["file"])
    assert mix["kind"] in cfg, "the configuration has no sizes for this kind"


def test_cells_are_unique_pairs_and_at_most_one_takes_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(pairs) // 4)
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_file(config):
    assert config["file"].startswith(MANIFEST["paths"][0] + "/configs/")
    cfg = load(paths.ROOT, config["file"])
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
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_widths_are_the_published_ones(config):
    """published/<config>.json (added with the configuration) lists the
    public config's keys that must be carried unchanged."""
    cfg = load(paths.ROOT, config["file"])
    pub = load(os.path.dirname(os.path.abspath(__file__)), "published",
               config["name"] + ".json")["keys"]
    assert not set(pub) & set(config["reduced"])
    assert any(WIDTHS.search(k) for k in pub)
    for key, value in pub.items():
        assert cfg[key] == value, key


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_file_agrees_with_the_manifest(metric):
    spec = load(paths.BENCH, "layer_metrics", metric["name"] + ".json")
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    assert spec.get("workloads", CELLS) == cells_of(metric)
    # moves names an end-to-end metric that each of its cells reports
    assert metric["moves"] in E2E
    assert set(cells_of(metric)) <= set(cells_of(E2E[metric["moves"]]))
    mod, fn = spec["reader"].split(":")
    assert os.path.isfile(os.path.join(paths.BENCH, mod + ".py"))
    import importlib
    assert callable(getattr(importlib.import_module(mod), fn))


def test_no_stray_data_files():
    have = {f[:-5] for f in os.listdir(os.path.join(paths.BENCH,
                                                    "layer_metrics"))}
    assert have == {m["name"] for m in MANIFEST["per_layer"]}
    mixes = {f[:-5] for f in os.listdir(os.path.join(paths.BENCH, "traffic"))}
    assert {w["traffic"] for w in MANIFEST["workloads"]} <= mixes


def test_layers_with_one_name_are_spelled_alike():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


def test_run_py_names_no_cell_config_mix_kernel_or_metric():
    src = open(os.path.join(paths.BENCH, "run.py")).read()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in MANIFEST[k]]
    names += [w["traffic"] for w in MANIFEST["workloads"]]
    names += ["paged_decode", "flash", "mistral", "gpt2", "llama"]
    for n in names:
        if n == "setup_s":      # the one metric every run reports
            continue
        assert n not in src, n
