"""The four-chip cell's geometry on the CPU: the committed ZeRO-3
configuration at tiny size over four of the virtual devices, the trace
readers on a synthetic four-device trace, what the readers are handed
for a cell, and a run whose timed path is broken underneath."""

import json
import os

import jax
import pytest

import chip_bench_paths as paths
import readers
import run as harness

MANIFEST = harness.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))
MOSAIC = 'custom_call_target="tpu_custom_call"'
DEVICES = [f"/device:TPU:{i}" for i in range(4)]


def load(name):
    with open(os.path.join(paths.DATA, name)) as f:
        return json.load(f)


def train_context(config, seconds=0.5, devices=None):
    return harness.Context(paths.ROOT, paths.BENCH, config,
                           load("tiny-train.json"), 2 ** 31 + 4, seconds,
                           devices=devices or jax.devices(),
                           compiles=harness.CompileCount())


CELL = "gpt2-xl.zero3-4chip"


def manifest_holds(manifest, root):
    """What a manifest has to say of the four-chip cell, whatever else
    it holds (test_chip_bench_family.py runs this against a manifest
    that has grown by a cell, a configuration and metrics)."""
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("gpt2-xl-zero3", "pretrain-1k-d1", 4)
    assert "fits no one" in cell["why"]       # why it takes four
    assert harness.find(manifest["configs"], cell["config"],
                        "config")["reduced"] == []
    rate = harness.find(manifest["end_to_end"], "train_tokens_per_s",
                        "metric")
    assert CELL in rate["workloads"]
    exposed = harness.find(manifest["per_layer"], "comm.exposed_share.train",
                           "metric")
    assert exposed["workloads"] == [CELL] and \
        exposed["moves"] == "train_tokens_per_s"
    # every cell's files reach the readers as the harness loads them
    for w in manifest["workloads"]:
        loaded = harness.load_cell(root, os.path.join(
            root, manifest["paths"][0]), w["name"])
        assert loaded["config"]["name"] == w["config"]
        assert loaded["traffic"]["kind"] in loaded["config"]


def test_the_manifest_holds_the_four_chip_cell():
    manifest_holds(MANIFEST, paths.ROOT)


def test_zero3_over_four_devices_through_the_train_driver():
    """data/gpt2-tiny-zero3.json is the committed gpt2-xl-zero3 file's
    train block (mesh data=4, ZeRO-3, remat) at tiny widths."""
    tiny = load("gpt2-tiny-zero3.json")
    real = harness.load_json(os.path.join(paths.BENCH, "configs",
                                          "gpt2-xl-zero3.json"))
    for key in ("mesh", "zero_stage"):
        assert tiny["train"][key] == real["train"][key]
    assert tiny["program"]["extra"]["remat"] is \
        real["program"]["extra"]["remat"] is True
    ctx = train_context(tiny, devices=jax.devices()[:4])
    res = harness.run_cell(ctx, "train")
    assert all(res["checks"].values()), res["checks"]
    assert res["checks"]["params_on_all_chips"] is True
    assert ctx.window_compiles == 0
    # tokens of every chip's micro-batch count in the rate
    per_step = 4 * tiny["train"]["micro_batch_per_chip"] * 32
    assert res["end_to_end"]["train_tokens_per_s"] * \
        res["notes"]["window_s"] == pytest.approx(
            res["counters"]["steps"] * per_step)
    assert res["static"]["flash"]["batch_per_chip"] == \
        tiny["train"]["micro_batch_per_chip"]


def test_a_step_that_leaves_out_half_the_batch_is_not_correct(monkeypatch):
    """The rest of a run with the timed path broken underneath: the
    engine's train_loop is handed every batch with its second half of
    rows replaced by the first, so the step's loss is the loss of half
    the batch, and ``step0_matches_reference`` comes out false against
    the reference on the whole one.  (A step that returns its state
    unchanged is NOT caught by ``loss_falls``, which then compares
    batch noise with batch noise: PERF.md section 7.)"""
    import numpy as np
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    sound = DeepSpeedEngine.train_loop

    def half(self, batches, sync=False):
        cut = [{k: np.concatenate([v[:len(v) // 2]] * 2) for k, v in
                b.items()} for b in batches]
        return sound(self, cut, sync=sync)
    monkeypatch.setattr(DeepSpeedEngine, "train_loop", half)
    res = harness.run_cell(train_context(load("gpt2-tiny.json")), "train")
    checks = dict(res["checks"])
    assert checks.pop("step0_matches_reference") is False
    assert all(checks.values()), checks
    diff, limit = res["compared"]["loss_step0_vs_reference"]
    assert diff > 3 * limit


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_readers_are_handed_the_cells_own_files(cell):
    loaded = harness.load_cell(paths.ROOT, paths.BENCH, cell)
    entry = harness.find(MANIFEST["workloads"], cell, "workload")
    config = harness.find(MANIFEST["configs"], entry["config"], "config")
    assert loaded["config_file"] == config["file"]
    ctx = harness.reader_context(
        loaded, {"counters": {"steps": 1}, "static": {}}, {"setup_s": 1.0},
        None, {"flops_per_s": 1.0})
    assert ctx["config"] == harness.load_json(
        os.path.join(paths.ROOT, config["file"]))
    assert ctx["traffic"] == harness.load_json(os.path.join(
        paths.BENCH, "traffic", entry["traffic"] + ".json"))
    assert ctx["config"]["name"] == entry["config"]
    assert ctx["chips"] == entry["chips"]
    assert set(ctx) == {"trace", "counters", "static", "end_to_end",
                        "peaks", "chips", "config", "traffic"}


def four_device_trace(kernel_ns=(400, 400, 400, 400)):
    """One step on four chips.  Each device: a compute fusion [0, 1000],
    an all-gather that starts under it and runs 300 ns past its end, the
    flash kernel, a reduce-scatter after everything (fully exposed), and
    a fusion that only CONSUMES the gathered weights."""
    ops = {}
    for d, (name, k) in enumerate(zip(DEVICES, kernel_ns)):
        ops[name] = [
            ("%fusion.1 = bf16[4,1024,1600]{2,1,0} fusion(%p), kind=kOutput",
             0, 1000),
            ("%all-gather.7 = bf16[1600,6400]{1,0} all-gather(bf16[400,6400] "
             "%w), dimensions={0}", 800, 1300),
            (f"%shard_map.3 = bf16[100,1024,64]{{2,1,0}} custom-call(bf16[8] "
             f"%q), {MOSAIC}", 1300, 1300 + k),
            ("%fusion.9 = bf16[4,1024,6400]{2,1,0} fusion(bf16[1600,6400] "
             "%all-gather.7), kind=kOutput", 1800, 2000),
            ("%reduce-scatter.2 = f32[400,6400]{1,0} reduce-scatter("
             "f32[1600,6400] %g), dimensions={0}", 2000, 2200 + 100 * d),
            # the scan over steps: its event spans everything inside it
            ("%while.4 = (s32[], f32[400,6400]) while(%t), body=%step", 0,
             2200 + 100 * d),
        ]
    return readers.Trace(ops, [("bench.train_loop", 0, 2600)])


def reader_ctx(trace, **kw):
    base = {"trace": trace, "counters": {}, "static": {}, "end_to_end": {},
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "chips": 4, "config": {}, "traffic": {}}
    base.update(kw)
    return base


def committed_args(metric):
    return harness.load_json(os.path.join(
        paths.BENCH, "layer_metrics", metric + ".json"))["args"]


def test_exposed_collectives_on_four_devices():
    """all-gather: 300 ns past the compute it overlaps; reduce-scatter:
    200, 300, 400, 500 ns with nothing beside it.  The fusion that takes
    %all-gather.7 as an operand is compute, not a collective."""
    got = readers.exposed_collective_share(
        reader_ctx(four_device_trace()),
        **committed_args("comm.exposed_share.train"))
    exposed = [300 + 200 + 100 * d for d in range(4)]
    assert got == pytest.approx(100 * (sum(exposed) / 4) / 2600)
    assert 0 < got < 100


def test_flash_roofline_on_four_devices_divides_like_by_like():
    """FLOPs of ONE chip's micro-batch over the kernel time of the
    average chip: four device planes must not count the FLOPs once and
    the time four times over, nor the other way round."""
    static = {"flash": {"batch_per_chip": 4, "heads": 25, "seq": 1024,
                        "head_dim": 64, "layers": 48}}
    args = committed_args("flash_roofline.train")
    flops = readers.flash_causal_flops(4, 25, 1024, 64) * 48
    one = readers.flash_roofline(reader_ctx(
        readers.Trace({DEVICES[0]: four_device_trace().device_ops[
            DEVICES[0]]}, []), static=static,
        counters={"traced_steps": 1}), **args)
    four = readers.flash_roofline(reader_ctx(
        four_device_trace(), static=static, counters={"traced_steps": 1}),
        **args)
    assert one == four == pytest.approx(100 * flops / 197e12 / 400e-9)
    # a chip whose kernels take longer pulls the average down, no more
    slow = readers.flash_roofline(reader_ctx(
        four_device_trace((400, 400, 400, 800)), static=static,
        counters={"traced_steps": 1}), **args)
    assert slow == pytest.approx(100 * flops / 197e12 / 500e-9)


def test_idle_share_and_mfu_on_four_devices():
    trace = four_device_trace()
    busy = [2200 + 100 * d for d in range(4)]   # the loop's event spans all
    assert readers.idle_share(reader_ctx(trace)) == pytest.approx(
        100 * (1 - sum(busy) / 4 / 2600))
    static = {"train": {"n_params": 1000, "layers": 2, "hidden": 8,
                        "seq": 16}}
    mfu = readers.train_mfu(reader_ctx(
        None, static=static, end_to_end={"train_tokens_per_s": 4e6}))
    assert mfu == pytest.approx(100 * (6000 + 1536) * 4e6 / (4 * 197e12))


@pytest.mark.parametrize("warm,first_sync_in_set_up", [(4, True),
                                                        (1, False)])
def test_one_step_a_dispatch_warms_past_the_timers_first_sync(
        monkeypatch, warm, first_sync_in_set_up):
    """The committed pretrain-1k-d1 mix at tiny length: with one step to
    a dispatch the engine's throughput timer reaches its start step (2)
    after the second dispatch, and the scalar add it syncs with would
    compile inside the window (it did on the chip, PR 29) unless
    ``warm_dispatches`` carries the warm-up past it."""
    d1 = harness.load_json(os.path.join(paths.BENCH, "traffic",
                                        "pretrain-1k-d1.json"))
    k4 = harness.load_json(os.path.join(paths.BENCH, "traffic",
                                        "pretrain-1k.json"))
    assert d1["steps_per_dispatch"] == 1 and d1["warm_dispatches"] == 4
    assert {k: v for k, v in d1.items() if k not in (
        "steps_per_dispatch", "warm_dispatches", "note")} == {
        k: v for k, v in k4.items() if k not in ("steps_per_dispatch",
                                                 "note")}
    mix = dict(load("tiny-train.json"), steps_per_dispatch=1,
               warm_dispatches=warm)
    # 3 s, not the 0.5 s of the tests above: with one step to a dispatch
    # half a second holds two steps where the machine is busy, and
    # loss_falls (the last tenth of the window's losses under the first)
    # needs more than two to mean something
    ctx = harness.Context(paths.ROOT, paths.BENCH, load("gpt2-tiny.json"),
                          mix, 5, 3.0, devices=jax.devices(),
                          compiles=harness.CompileCount())
    from deepspeed_tpu.utils import timer
    in_set_up = []
    sound = timer._sync

    def sync():
        in_set_up.append(ctx.setup_s is None)
        sound()
    monkeypatch.setattr(timer, "_sync", sync)
    res = harness.run_cell(ctx, "train")
    assert in_set_up and in_set_up[0] is first_sync_in_set_up
    assert all(v for k, v in res["checks"].items()), res["checks"]
    assert ctx.window_compiles == 0 or not first_sync_in_set_up
