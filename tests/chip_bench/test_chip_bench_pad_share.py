"""The two hybrid chat cells report the batched prefill's padding
(``sched.prefill_pad_share.par`` in ``falcon-h1.chat``, ``.hyb`` in
``nemotron3-nano.chat``): data files alone, read by ``readers:counter``
off ``ServingScheduler.summary()``'s ``prefill_pad_share``, which the
parent of the PR that added them reports too.  The dict beside it,
``prefill_dispatches_by_bucket``, is no number and reaches no reader.

``data/pad_share.recorded_lines.json`` holds what traced runs on the
chip printed for the two metrics (the result line's ``metrics`` entry
and the wrapper's own count of dispatches by bucket), parent and
change."""

import json
import os

import pytest

import chip_bench_paths as paths
import drive_serve
import run as harness
from deepspeed_tpu.serving.metrics import ServingMetrics

MANIFEST = harness.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))
CELLS = {"sched.prefill_pad_share.par": "falcon-h1.chat",
         "sched.prefill_pad_share.hyb": "nemotron3-nano.chat"}
with open(os.path.join(paths.DATA, "pad_share.recorded_lines.json")) as f:
    RECORDED = json.load(f)


def read(metric, counters):
    entry = harness.find(MANIFEST["per_layer"], metric, "metric")
    return harness.layer_metrics(paths.BENCH, {"per_layer": [entry]},
                                 CELLS[metric], {
        "trace": None, "counters": counters, "static": {},
        "end_to_end": {}, "chips": 1, "peaks": {}, "config": {},
        "traffic": {}})


@pytest.mark.parametrize("metric", sorted(CELLS))
def test_the_file_loads_and_names_a_cell_the_benchmark_has(metric):
    spec = harness.load_json(os.path.join(paths.BENCH, "layer_metrics",
                                          metric + ".json"))
    lat = harness.load_json(os.path.join(
        paths.BENCH, "layer_metrics", "sched.prefill_pad_share.lat.json"))
    # a copy of the Mistral cell's file but for the name and the cell
    assert dict(spec, name=lat["name"], workloads=lat["workloads"]) == lat
    assert spec["workloads"] == [CELLS[metric]]
    cell = harness.find(MANIFEST["workloads"], CELLS[metric], "workload")
    ttft = harness.find(MANIFEST["end_to_end"], spec["moves"], "metric")
    assert cell["name"] in ttft["workloads"]
    entry = harness.find(MANIFEST["per_layer"], metric, "metric")
    assert entry["workloads"] == spec["workloads"]


@pytest.mark.parametrize("metric", sorted(CELLS))
def test_it_reads_the_programs_counter_and_nothing_of_the_dict(metric):
    m = ServingMetrics(None)
    for step, (rows, padded) in enumerate([(20, 32), (17, 32), (5, 16)], 1):
        m.record_prefill_dispatch(step, rows=rows, padded_rows=padded,
                                  tokens=8 * rows)
    summary = m.summary()
    assert summary["prefill_dispatches_by_bucket"] == {"16": 1, "32": 2}
    counters = drive_serve.numeric_items(summary)
    assert "prefill_dispatches_by_bucket" not in counters
    got = read(metric, counters)
    assert got == {metric: {"value": pytest.approx(100 * (1 - 42 / 80),
                                                   abs=0.01), "unit": "%"}}
    # a program without the counter: nothing is reported, nothing raises
    assert read(metric, {}) == {}
    # the metric is its own cell's alone
    entry = harness.find(MANIFEST["per_layer"], metric, "metric")
    assert harness.layer_metrics(
        paths.BENCH, {"per_layer": [entry]}, "mistral7b.chat",
        {"counters": counters}) == {}


@pytest.mark.parametrize("metric", sorted(CELLS))
def test_the_recorded_lines_of_both_sides_hold_it(metric):
    """Both sides of a pair read the counter; the finer set can only
    pad less on the same schedule."""
    sides = RECORDED[CELLS[metric]]
    for side in ("parent", "change"):
        line = sides[side]["metrics"][metric]
        assert line["unit"] == "%" and 0.0 <= line["value"] < 100.0
        assert sum(sides[side]["buckets"].values()) > 0
    assert sides["change"]["metrics"][metric]["value"] <= \
        sides["parent"]["metrics"][metric]["value"]
    assert "32" not in sides["parent"]["buckets"]
