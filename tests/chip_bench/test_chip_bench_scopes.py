"""``readers_scopes``: device time by model component, on a recorded
trace (``tests/unit/xplane_file.py`` writes a v5e's layout by hand), and
the benchmark's copy of the vocabulary against the program's."""

import glob
import json
import os
import sys

import pytest

import chip_bench_paths as paths
import readers
import readers_scopes
import run as harness

sys.path.insert(0, os.path.join(paths.ROOT, "tests", "unit"))
from xplane_file import write_xspace  # noqa: E402

from deepspeed_tpu import tracing  # noqa: E402

MANIFEST = harness.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))
SCOPE_FILES = sorted(glob.glob(os.path.join(
    paths.BENCH, "layer_metrics", "scope.*.json")))
HORIZON = "jit(decode_multi)/horizon/while/body/closed_call/Llama/"
# two programs that both hold %fusion.1, under different paths; a loop
# whose event spans its body's; a copy the compiler made (no path); a
# collective under a module's path; an operation under no token
METADATA = {
    1: ("%fusion.1 = (f32[32], bf16[32,4096]) fusion(%a, %b)", {
        "tf_op": HORIZON + "layers_3/mlp/w_down/dot_general:",
        "program_id": 11, "hlo_category": "convolution fusion"}),
    2: ("%fusion.1 = bf16[4,32,4096] fusion(%a, %b)", {
        "tf_op": "jit(prefill)/Llama/layers_3/attn/wq/dot_general:",
        "program_id": 22}),
    3: ("%while.7 = (s32[], bf16[32,4096]) while(%t)", {
        "tf_op": "jit(decode_multi)/horizon/while", "program_id": 11}),
    4: ("%copy-done.2 = bf16[4096,4096] copy-done(%copy-start.2)", {
        "program_id": 11}),
    5: ("%all-gather-start.1 = bf16[64] all-gather-start(%p)", {
        "tf_op": "jit(step)/jvp(GPT2)/h_0/mlp/fc_in/zero_gather/"
                 "sharding_constraint", "program_id": 33}),
    6: ("%add.9 = s32[] add(%i, %one)", {
        "tf_op": "jit(f)/add", "program_id": 33}),
    7: ("%attn.12 = bf16[32,8,4,128] custom-call(%q), "
        "custom_call_target=\"tpu_custom_call\"", {
            "tf_op": HORIZON + "layers_3/attn/pallas_call:",
            "program_id": 11}),
}
OPS = [(3, 9_000_000), (1, 4_000_000), (7, 1_500_000), (4, 500_000),
       (2, 2_000_000), (5, 300_000), (6, 100_000)]
SECONDS = {"mlp": 4e-6, "attn_core": 1.5e-6, "unattributed": 0.5e-6,
           "attn_proj": 2e-6, "comm": 0.3e-6, "other": 0.1e-6}


@pytest.fixture
def recorded(tmp_path):
    return write_xspace(tmp_path / "one.xplane.pb", [
        ("/host:CPU", {}, {}),
        ("/device:TPU:0", METADATA,
         {"XLA Modules": [(3, 9_000_000)], "XLA Ops": OPS})])


def test_components_partition_the_operations(recorded):
    secs = readers_scopes.component_seconds(recorded)
    assert secs == pytest.approx(SECONDS)
    # the two %fusion.1 are two programs' (mlp and attn_proj above), the
    # loop's 9 us are not counted beside its body's, and the components
    # add up to the events that are no wrapper: to the picosecond
    ops = readers_scopes.device_events(recorded)["/device:TPU:0"]
    assert sum(ops.values()) == \
        sum(ps for mid, ps in OPS if mid != 3) == 8_400_000
    assert sum(secs.values()) == pytest.approx(8.4e-6, rel=1e-12)


def test_a_trace_without_paths_reads_none_not_zero(tmp_path):
    bare = {mid: (name, {k: v for k, v in stats.items() if k != "tf_op"})
            for mid, (name, stats) in METADATA.items()}
    path = write_xspace(tmp_path / "bare.xplane.pb", [
        ("/device:TPU:0", bare, {"XLA Ops": OPS})])
    assert readers_scopes.component_seconds(path) is None
    assert readers_scopes.describe(path)[0][0] == "unattributed"


def context(monkeypatch, path, window_ns=10_000):
    trace = readers.Trace({"/device:TPU:0": [("op", 0, 8_400)]},
                          [("bench.sched_step", 0, window_ns)])
    monkeypatch.setattr(readers_scopes, "trace_file", lambda ctx: path)
    return {"trace": trace}


@pytest.mark.parametrize("component, share", [
    ("mlp", 40.0), ("attn_proj", 20.0), ("attn_core", 15.0), ("comm", 3.0),
    # other + unattributed; a component the trace does not hold is 0
    ("unattributed", 6.0), ("ssm", 0.0)])
def test_time_share_of_the_window(recorded, monkeypatch, component, share):
    ctx = context(monkeypatch, recorded)
    assert readers_scopes.time_share(
        ctx, component, tracing.COMPONENTS[component]) == \
        pytest.approx(share)


def test_time_share_is_the_mean_over_chips_and_reads_its_files_tokens(
        tmp_path, monkeypatch):
    path = write_xspace(tmp_path / "two.xplane.pb", [
        ("/device:TPU:0", METADATA, {"XLA Ops": OPS}),
        ("/device:TPU:1", METADATA, {"XLA Ops": [(1, 2_000_000)]})])
    ctx = context(monkeypatch, path)
    assert readers_scopes.time_share(ctx, "mlp", ["mlp"]) == \
        pytest.approx(30.0)
    # the file's tokens are what makes a path the component's
    assert readers_scopes.time_share(ctx, "attn_proj", ["wq"]) == \
        pytest.approx(10.0)
    assert readers_scopes.time_share(ctx, "attn_proj", []) == 0.0
    monkeypatch.setattr(readers_scopes, "trace_file", lambda ctx: None)
    assert readers_scopes.time_share(ctx, "mlp", ["mlp"]) is None


def test_the_trace_file_is_this_runs_or_none(tmp_path, monkeypatch):
    monkeypatch.setattr(readers_scopes.readers_spans, "TRACE_ROOT",
                        str(tmp_path))
    assert readers_scopes.trace_file({"trace": None}) is None
    trace = readers.Trace({}, [("bench.sched_step", 0, 10)])
    assert readers_scopes.trace_file({"trace": trace}) is None


# ------------------------------------------- one vocabulary, two copies

def test_the_benchmarks_vocabulary_is_the_programs():
    components, collectives = readers_scopes.vocabulary()
    assert list(components) == list(tracing.COMPONENTS)
    assert {c: tuple(t) for c, t in components.items()} == \
        tracing.COMPONENTS
    assert collectives == tracing.COLLECTIVE_OPCODES


@pytest.mark.parametrize("path", SCOPE_FILES, ids=os.path.basename)
def test_a_scope_metric_file_is_pinned_to_the_vocabulary(path):
    spec = harness.load_json(path)
    scope, component, reading, kind = spec["name"].split(".")
    assert (scope, reading) == ("scope", "time_share")
    assert spec["reader"] == "readers_scopes:time_share"
    assert spec["args"] == {
        "component": component,
        "tokens": list(tracing.COMPONENTS[component])}
    assert (spec["unit"], spec["better"], spec["source"]) == \
        ("%", "lower", "device_trace")
    kinds = {"open": "tpot_p90_ms", "closed": "served_tokens_per_s",
             "train": "train_tokens_per_s"}
    assert spec["moves"] == kinds[kind]
    # every cell of the kind's end-to-end metric, for the family's check
    # of itself; a subset of them for a component
    e2e = harness.find(MANIFEST["end_to_end"], spec["moves"], "metric")
    cells = e2e.get("workloads") or \
        [w["name"] for w in MANIFEST["workloads"]]
    if kind == "open":
        cells = [c for c in cells if c.endswith(".chat")]
    assert set(spec["workloads"]) <= set(cells)
    if component == "unattributed":
        assert spec["workloads"] == [
            w["name"] for w in MANIFEST["workloads"] if w["name"] in cells]


@pytest.mark.parametrize("op_name, opcode", [
    (HORIZON + "layers_3/attn/pallas_call", "attn"),
    (HORIZON + "layers_3/attn/cache/scatter", "fusion"),
    ("jit(prefill)/Llama/layers_3/attn/rope/mul", "fusion"),
    ("jit(step)/transpose(jvp(GPT2))/h_3/mlp/fc_in/dot_general", "fusion"),
    ("jit(step)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add",
     "scatter"),
    ("jit(step)/train_loop/while/body/closed_call/optimizer/mul", "fusion"),
    ("jit(step)/jvp(GPT2)/h_0/ln_1/reduce_sum", "reduce"),
    ("jit(prefill)/NemotronH/layers_1/moe/router/top_k", "sort"),
    ("jit(prefill)/NemotronH/layers_1/moe/experts/ragged_dot", "fusion"),
    ("jit(prefill)/NemotronH/layers_0/mamba/in_proj/dot_general", "fusion"),
    ("jit(prefill)/NemotronH/norm_f/rsqrt", "fusion"),
    ("jit(step)/jvp(GPT2)/h_0/mlp/fc_in/dot_general", "all-reduce"),
    ("jit(f)/jit(_where)/select_n", "select"),
    ("", "copy-done"),
    ("jit(decode_multi)/horizon/while/body/closed_call", "slice-done"),
    ("jit(decode_multi)/horizon/while/body/add", "add"),
    ("jit(f)/cond/branch_1", "copy"),
    ("params['layers_22']['moe']['w_up']:", "copy"),
    ("pools['layers'][0]['k_pages']", "copy"),
    ("ragged-dot-none:", "ragged-dot-none"),
    ("jit(step)/train_loop/while/body/closed_call/optimizer/mul;while/body/"
     "closed_call", "multiply"),
])
def test_the_benchmarks_rule_is_the_programs(op_name, opcode):
    mine = readers_scopes.classifier(*readers_scopes.vocabulary())
    assert mine(op_name, opcode) == tracing.component(op_name, opcode)


def test_describe_names_the_groups_inside_a_component(recorded):
    rows = readers_scopes.describe(recorded, groups=2)
    assert [r[0] for r in rows][:2] == ["mlp", "attn_proj"]
    assert rows[0][2] == [["fusion f32[32]", pytest.approx(4e-6)]]
    assert json.dumps(rows)
