"""The trace reduction on a small synthetic event list (committed as
data/synthetic_trace.json): busy union, idle share, gaps and their
labels, time by name, collectives not hidden behind compute."""

import json
import os

import pytest

import chip_bench_paths as paths
import readers


@pytest.fixture()
def trace():
    with open(os.path.join(paths.DATA, "synthetic_trace.json")) as f:
        d = json.load(f)

    def tup(evs):
        return [tuple(e) for e in evs]
    return readers.Trace(
        {k: tup(v) for k, v in d["device_ops"].items()},
        tup(d["host_spans"]),
        {ln: {k: tup(v) for k, v in devs.items()}
         for ln, devs in d["device_lines"].items()})


def ctx(trace, **kw):
    base = {"trace": trace, "counters": {}, "static": {}, "end_to_end": {},
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "chips": 1}
    base.update(kw)
    return base


@pytest.mark.parametrize("ivs,want", [
    ([(0, 5), (3, 8), (10, 12)], [(0, 8), (10, 12)]),
    ([(5, 5), (1, 2)], [(1, 2)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
    ([], []),
])
def test_merge(ivs, want):
    assert readers.merge(ivs) == want


@pytest.mark.parametrize("a,b,want", [
    ([(0, 8), (10, 12)], [(4, 11)], [(0, 4), (11, 12)]),
    ([(0, 10)], [(2, 3), (5, 6)], [(0, 2), (3, 5), (6, 10)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 4), (6, 8)], [(0, 10)], []),
])
def test_subtract(a, b, want):
    assert readers.subtract(a, b) == want


def test_window_is_the_hull_of_ops_and_spans(trace):
    assert (trace.lo, trace.hi) == (0, 11000)
    assert trace.window_s == pytest.approx(11e-6)


def test_busy_union_and_idle_share(trace):
    # dev0: [1000, 6000] + [9000, 10500] = 6500; dev1: 4000 + 1000 = 5000
    assert readers.total(trace.busy("/device:TPU:0")) == 6500
    assert readers.total(trace.busy("/device:TPU:1")) == 5000
    assert trace.busy_s() == pytest.approx(5750e-9)
    assert readers.idle_share(ctx(trace)) == pytest.approx(
        100 * (1 - 5750 / 11000))


def test_gaps_are_labelled_by_the_covering_span(trace):
    gaps = trace.top_gaps()
    # dev0 idle: [0,1000] 1000, [6000,9000] 3000, [10500,11000] 500
    assert [round(s * 1e9) for _, s in gaps] == [3000, 1000, 500]
    # the 3000 ns gap: 500 under sched_step, 2300 under wait_arrival
    assert [n for n, _ in gaps] == ["bench.wait_arrival", "bench.sched_step",
                                    "bench.sched_step"]


def test_top_ops_average_over_devices(trace):
    top = dict(trace.top_ops())
    # grouped by short name: fusion.1 (2000 + 4000) and fusion.4 (1000)
    assert top["fusion"] == pytest.approx((3000 + 4000) / 2 * 1e-9)
    assert top["all-gather"] == pytest.approx((2500 + 1000) / 2 * 1e-9)


@pytest.mark.parametrize("name,want", [
    ("%fusion.12 = bf16[32,4096]{1,0:T(8,128)(2,1)} fusion(bf16[8] %x), "
     "kind=kLoop", "fusion bf16[32,4096]"),
    ("%attn.5 = (bf16[128,1024,64]{2,1,0}, f32[1]{0}) custom-call(bf16[1] "
     "%y), custom_call_target=\"tpu_custom_call\"",
     "attn bf16[128,1024,64] [mosaic]"),
    ("%while.5 = (s32[]{:T(128)}, f32[1024]{0}) while(%t), body=%b", None),
    ("%call.1 = f32[2]{0} call(%a), to_apply=%f", None),
    ("fusion.1", "fusion"),
])
def test_short_name(name, want):
    assert readers.short_name(name) == want


def test_time_by_name_substring(trace):
    c = ctx(trace)
    assert readers.name_time_share(c, ["paged_decode"]) == pytest.approx(
        100 * (1500 / 2) / 11000)
    assert readers.name_time_share(c, ["no_such_kernel"]) is None
    # whole programs on the modules line, as a share of busy time
    assert readers.name_time_share(
        c, ["prefill"], line="XLA Modules", of="busy") == pytest.approx(
        100 * ((5000 + 6000) / 2) / 5750)


MOSAIC = 'custom_call_target="tpu_custom_call"'


def two_kernel_trace():
    """A program with the attention kernel, a SECOND Mosaic kernel that
    takes its output as an operand, and a plain copy of that output."""
    ops = [(f"%attn.3 = bf16[32,8,4,128]{{3,2,1,0}} custom-call(bf16[8] "
            f"%q), {MOSAIC}", 0, 1000),
           (f"%qdense.7 = bf16[32,4096]{{1,0}} custom-call(bf16[32,8,4,128] "
            f"%attn.3, s8[4096,4096] %w), {MOSAIC}", 1000, 1500),
           ("%copy.2 = bf16[32,8,4,128]{3,2,1,0} copy(%attn.3)", 1500, 1700),
           ("%fusion.1 = bf16[32]{0} fusion(%copy.2), kind=kLoop", 1700,
            2000)]
    return readers.Trace({"/device:TPU:0": ops}, [])


@pytest.mark.parametrize("args,ns", [
    ({"heads": ["attn"], "all_of": ["tpu_custom_call"]}, 1000),
    ({"heads": ["qdense"], "all_of": ["tpu_custom_call"]}, 500),
    ({"substrs": ["tpu_custom_call"]}, 1500),        # any Mosaic kernel
    ({"substrs": ["attn"]}, 1700),                   # operands match too
    ({"heads": ["attn"], "all_of": ["no_such_target"]}, None),
])
def test_a_kernel_is_told_from_a_second_custom_call(args, ns):
    c = ctx(two_kernel_trace())
    want = None if ns is None else pytest.approx(100 * ns / 2000)
    assert readers.name_time_share(c, **args) == want


def test_flash_roofline_leaves_out_a_second_custom_call():
    st = {"flash": {"batch_per_chip": 2, "heads": 3, "seq": 4, "head_dim": 8,
                    "layers": 5}}
    c = ctx(two_kernel_trace(), static=st, counters={"traced_steps": 1})
    flops = 12 * 8 * 10 * 6 * 5
    assert readers.flash_roofline(
        c, heads=["attn"], all_of=["tpu_custom_call"]) == pytest.approx(
        100 * flops / 197e12 / 1000e-9)


@pytest.mark.parametrize("metric", [
    "kernel.paged_decode.time_share.lat", "kernel.paged_prefill.time_share.thr",
    "flash_roofline.train"])
def test_committed_kernel_metrics_match_the_kernel_by_its_own_name(metric):
    with open(os.path.join(paths.BENCH, "layer_metrics",
                           metric + ".json")) as f:
        args = json.load(f)["args"]
    assert args["heads"] and "tpu_custom_call" in args["all_of"]
    assert not args.get("substrs")


def test_exposed_collective_time(trace):
    # dev0: all-gather [3500,6000] minus compute [1000,4000] -> 2000;
    # all-reduce [10000,10500] minus compute [9000,10000] -> 500
    # dev1: all-gather [6000,7000], compute ends at 5000 -> 1000
    got = readers.exposed_collective_share(
        ctx(trace), ["all-gather", "all-reduce", "reduce-scatter"])
    assert got == pytest.approx(100 * ((2500 + 1000) / 2) / 11000)
    assert readers.exposed_collective_share(ctx(trace), ["nope"]) is None


def test_readers_return_nothing_without_a_trace():
    c = ctx(None)
    assert readers.idle_share(c) is None
    assert readers.name_time_share(c, ["x"]) is None
    assert readers.flash_roofline(c, ["flash"]) is None
    assert readers.exposed_collective_share(c, ["all"]) is None
    assert readers.counter(c, "missing") is None
    assert readers.train_mfu(c) is None


def test_counter_reader():
    c = ctx(None, counters={"device_wait_frac": 0.25})
    assert readers.counter(c, "device_wait_frac", scale=100.0,
                           one_minus=True) == pytest.approx(75.0)


def test_flops_from_static_shapes(trace):
    # causal attention: b*h*s*(s+1)/2 pairs, 4d FLOPs forward, 8d backward
    assert readers.flash_causal_flops(1, 1, 4, 8, backward=False) == \
        4 * 8 * 10
    assert readers.flash_causal_flops(2, 3, 4, 8) == 12 * 8 * 10 * 6
    assert readers.train_flops_per_token(1000, 2, 8, 16) == 6000 + 6 * 2 * 8 * 16
    st = {"flash": {"batch_per_chip": 2, "heads": 3, "seq": 4, "head_dim": 8,
                    "layers": 5}}
    c = ctx(trace, static=st, counters={"traced_steps": 2})
    flops = 12 * 8 * 10 * 6 * 5 * 2
    # "fusion" events stand in for the kernel: 2000+1000 on dev0, 4000 on
    # dev1 -> 3500 ns a device
    assert readers.flash_roofline(c, ["fusion"]) == pytest.approx(
        100 * flops / 197e12 / 3500e-9)
    st = {"train": {"n_params": 1000, "layers": 2, "hidden": 8, "seq": 16}}
    c = ctx(None, static=st, end_to_end={"train_tokens_per_s": 1e6})
    assert readers.train_mfu(c) == pytest.approx(
        100 * (6000 + 1536) * 1e6 / 197e12)
