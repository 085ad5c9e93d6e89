"""run.py's whole control flow on the CPU, for one serve and one train
cell given as test-local files (data/): the tests build the Context and
call the drivers, so the command itself keeps its device check and has
no option that bypasses it.  Then the proof that later cells are data:
a throw-away configuration, mix and per-layer metric added to a copy of
the harness run without editing a file that was there."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_bench_paths as paths
import run as harness


def load(name):
    with open(os.path.join(paths.DATA, name)) as f:
        return json.load(f)


def context(config, mix, seconds, bench_dir=paths.BENCH, seed=2 ** 31 + 7):
    return harness.Context(paths.ROOT, bench_dir, config, mix, seed,
                           seconds, devices=jax.devices(),
                           compiles=harness.CompileCount())


@pytest.fixture(scope="module")
def serve_open():
    ctx = context(load("llama-tiny.json"), load("tiny-open.json"), 2.0)
    return ctx, harness.run_cell(ctx, "serve")


@pytest.fixture(scope="module")
def train():
    ctx = context(load("gpt2-tiny.json"), load("tiny-train.json"), 1.0)
    return ctx, harness.run_cell(ctx, "train")


def test_serve_flow_open_loop(serve_open):
    ctx, res = serve_open
    assert all(res["checks"].values()), res["checks"]
    assert ctx.window_compiles == 0 and ctx.setup_s > 0
    import loadgen
    assert res["attempted"] == len(loadgen.make_requests(
        load("tiny-open.json"), 2.0)) > 8 and res["failed"] == 0
    assert {"step_max_s", "step_max_cpu_s", "step_max_gc_s",
            "step_max_beat_gap_s", "steps_over_1s"} <= set(res["notes"])
    # the on-time rule reads the median; the mean and the maximum stay
    # in the notes, with what the old rule would have said
    notes = res["notes"]
    assert {"lateness_mean_s", "lateness_median_s", "lateness_max_s",
            "late_runs_mean_over_step"} <= set(notes)
    assert res["checks"]["generator_on_time"] is True
    assert res["compared"]["lateness_median_s"] == [
        notes["lateness_median_s"], notes["step_mean_s"]]
    assert "lateness_mean_s" not in res["compared"]
    assert 0 <= notes["lateness_median_s"] <= notes["lateness_max_s"]
    e2e = res["end_to_end"]
    assert e2e["ttft_p90_ms"] > 0 and e2e["tpot_p90_ms"] > 0
    assert e2e["req_tokens_per_s"] > 0
    assert e2e["served_tokens_per_s"] is None     # no cut in an open loop
    assert res["notes"]["paged_attention"]["path"] == "kernel"
    assert res["notes"]["reference_worst_margin"] <= \
        res["notes"]["reference_eps"]
    assert 0 < res["counters"]["slot_occupancy"] <= 1


def test_serve_flow_closed_loop_cuts_at_the_window():
    ctx = context(load("llama-tiny.json"), load("tiny-closed.json"), 1.0)
    res = harness.run_cell(ctx, "serve")
    assert all(res["checks"].values()), res["checks"]
    assert ctx.window_compiles == 0
    # only requests that finished are left: the ones in flight when the
    # window ended were cancelled and are not failures
    assert res["failed"] == 0 and res["attempted"] >= 6
    # a closed loop's generator is on time by construction, as before
    assert res["checks"]["generator_on_time"] is True
    assert res["notes"]["lateness_median_s"] < res["notes"]["step_mean_s"]
    assert res["notes"]["late_runs_mean_over_step"] == 0
    assert res["counters"]["slot_occupancy"] == pytest.approx(1.0, abs=0.05)
    assert "ttft_p90_ms" in res["end_to_end"]
    # whole requests finished in the window, and everything served in it
    e2e = res["end_to_end"]
    assert e2e["served_tokens_per_s"] >= e2e["req_tokens_per_s"] > 0


def test_train_flow(train):
    ctx, res = train
    assert all(res["checks"].values()), res["checks"]
    assert ctx.window_compiles == 0
    assert res["notes"]["compiled"] == {"step_loop": 1}
    assert res["end_to_end"]["train_tokens_per_s"] > 0
    assert res["attempted"] == res["counters"]["steps"] > 0
    assert abs(res["notes"]["loss_step0"] - res["notes"]["loss_reference"]) \
        < 1e-4     # float32 on both sides here


def test_layer_metrics_are_found_by_name_and_skipped_when_empty(train):
    ctx, res = train
    manifest = {"per_layer": [
        {"name": "train.mfu", "unit": "%"},
        {"name": "flash_roofline.train", "unit": "%"}]}
    peaks = harness.load_json(os.path.join(paths.BENCH, "peaks.json"))
    got = harness.layer_metrics(paths.BENCH, manifest, "any", {
        "trace": None, "counters": res["counters"], "static": res["static"],
        "end_to_end": res["end_to_end"], "chips": 1,
        "peaks": peaks["devices"]["TPU v5 lite"]})
    # the utilisation needs no trace; the roofline share has none to read
    assert set(got) == {"train.mfu"} and got["train.mfu"]["unit"] == "%"


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 12345])
def test_command_exits_nonzero_without_a_tpu(seed):
    manifest = harness.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))
    cell = manifest["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    out = subprocess.run(
        [sys.executable] + manifest["command"][1:] +
        ["--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", "0"],
        cwd=paths.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_a_later_cell_is_data_only(tmp_path):
    """Copy the harness, ADD a configuration, a mix, a per-layer metric
    and a reader module, and run the new cell from the copy."""
    bench = str(tmp_path / "chip")
    shutil.copytree(paths.BENCH, bench)
    before = {}
    for d, _, files in os.walk(bench):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    shutil.copy(os.path.join(paths.DATA, "gpt2-tiny.json"),
                os.path.join(bench, "configs", "throwaway.json"))
    mix = dict(load("tiny-train.json"), steps_per_dispatch=2)
    with open(os.path.join(bench, "traffic", "throwaway-mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "readers_throwaway.py"), "w") as f:
        f.write("def steps(ctx, per):\n"
                "    return ctx['counters']['steps'] / per\n")
    for name, reader, args in (
            ("throwaway.steps", "readers_throwaway:steps", {"per": 2}),
            ("throwaway.mfu", "readers:train_mfu", {})):
        with open(os.path.join(bench, "layer_metrics", name + ".json"),
                  "w") as f:
            json.dump({"name": name, "reader": reader, "args": args}, f)
    manifest = {"per_layer": [{"name": "throwaway.steps", "unit": "1"},
                              {"name": "throwaway.mfu", "unit": "%"}]}

    config = harness.load_json(os.path.join(bench, "configs",
                                            "throwaway.json"))
    traffic = harness.load_json(os.path.join(bench, "traffic",
                                             "throwaway-mix.json"))
    ctx = context(config, traffic, 0.5, bench_dir=bench)
    res = harness.run_cell(ctx, traffic["kind"])
    assert all(res["checks"].values()), res["checks"]
    got = harness.layer_metrics(bench, manifest, "throwaway", {
        "trace": None, "counters": res["counters"], "static": res["static"],
        "end_to_end": res["end_to_end"], "chips": 1,
        "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e11}})
    assert got["throwaway.steps"]["value"] == res["counters"]["steps"] / 2
    assert got["throwaway.mfu"]["value"] > 0
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"
