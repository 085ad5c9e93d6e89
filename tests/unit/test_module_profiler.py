"""Per-module trace profiler (VERDICT r4 task 7: the reference
print_model_profile equivalent). The xplane reader is tested against
hand-encoded protobuf bytes (``xplane_file.py``: CPU backends emit no
op-level trace), the aggregation against synthetic records."""

import jax
import pytest

from deepspeed_tpu.profiling.module_profiler import (
    _aggregate, _module_path, aggregate_by_component, aggregate_by_module,
    format_profile, top_traffic_consumers)
from deepspeed_tpu.profiling.xplane import device_plane, read_xspace
from xplane_file import write_xspace


def _make_xspace(tmp_path):
    """One plane '/device:TPU:0' with an 'XLA Ops' line: two events of
    one op attributed to GPT2/h_0/attn with 2 GFLOP + 1 GB each."""
    metadata = {7: ("%fusion.1 = f32[8] fusion(...)", {
        "tf_op": "jit(step)/jvp(GPT2)/h_0/attn/dot_general:",
        "flops": 2_000_000_000, "raw_bytes_accessed": 1_000_000_000})}
    return write_xspace(tmp_path / "t.xplane.pb", [
        ("/device:TPU:0", metadata,
         {"XLA Ops": [(7, 500_000_000)] * 2})])     # 0.5 ms each


def test_xplane_reader_roundtrip(tmp_path):
    path = _make_xspace(tmp_path)
    planes = read_xspace(path)
    plane = device_plane(planes)
    assert plane is not None and plane.name == "/device:TPU:0"
    assert plane.event_names[7].startswith("%fusion.1")
    stats = plane.event_stats[7]
    assert stats["tf_op"].endswith("attn/dot_general:")
    assert stats["flops"] == 2_000_000_000
    line = [l for l in plane.lines if l.name == "XLA Ops"][0]
    assert len(line.events) == 2
    assert line.events[0].duration_ps == 500_000_000


def test_module_path_normalization():
    assert _module_path("jit(f)/jvp(GPT2)/h_0/attn/qkv/dot_general:") \
        == "GPT2/h_0/attn/qkv [fwd]"
    assert _module_path(
        "jit(f)/transpose(jvp(GPT2))/h_3/mlp/fc_in/dot_general:") \
        == "GPT2/h_3/mlp/fc_in [bwd]"
    assert _module_path("") == "(unattributed)"
    assert _module_path("jit(f)/add:") == "(top)"
    # a loop's own parts and the scope round it name no module
    assert _module_path(
        "jit(decode_multi)/horizon/while/body/closed_call/Llama/layers_3/"
        "mlp/w_down/dot_general:") == "Llama/layers_3/mlp/w_down"
    assert _module_path("jit(decode_multi)/horizon/while:") == "(top)"
    assert _module_path(
        "jit(step_loop)/train_loop/while/body/closed_call/"
        "transpose(jvp(GPT2))/h_0/attn/qkv/dot_general:") == \
        "GPT2/h_0/attn/qkv [bwd]"


def _recs():
    return [
        {"op": "fusion.1", "module": "GPT2/h_0/attn [fwd]",
         "leaf_op": "dot_general", "category": "fusion",
         "duration_ps": 4_000_000_000, "flops": 8e9, "bytes": 2e9,
         "occurrences": 2, "steps": 2},
        {"op": "fusion.2", "module": "GPT2/h_0/mlp [fwd]",
         "leaf_op": "dot_general", "category": "fusion",
         "duration_ps": 2_000_000_000, "flops": 4e9, "bytes": 8e9,
         "occurrences": 2, "steps": 2},
    ]


def test_aggregation_and_traffic():
    rows = aggregate_by_module(_recs(), depth=2)
    assert rows[0]["module"] == "GPT2/h_0"   # both collapse at depth 2
    assert rows[0]["ms"] == pytest.approx(3.0)      # (4+2) ns.. ps->ms /2
    top = top_traffic_consumers(_recs(), k=1)
    assert top[0]["module"] == "GPT2/h_0/mlp [fwd]"  # most bytes wins
    assert top[0]["gb"] == pytest.approx(4.0)
    table = format_profile(_recs(), depth=3)
    assert "top HBM traffic consumers" in table
    assert "GPT2/h_0/mlp" in table


def test_records_carry_component_and_pass(tmp_path):
    """A recorded trace through the whole pipeline: the wrapper's event
    is left out, every record has its component (``tracing.component``)
    and its pass, and the component table heads the printed profile."""
    metadata = {
        1: ("%fusion.1 = f32[8] fusion(...)", {
            "tf_op": "jit(step)/transpose(jvp(GPT2))/h_0/mlp/fc_in/"
                     "dot_general:", "flops": 10}),
        2: ("%while.3 = (s32[]) while(...)", {
            "tf_op": "jit(step)/train_loop/while"}),
        3: ("%copy-done.2 = f32[8] copy-done(...)", {"flops": 0}),
        4: ("%all-reduce.1 = f32[8] all-reduce(...)", {
            "tf_op": "jit(step)/jvp(GPT2)/h_0/mlp/fc_in/dot_general:"})}
    path = write_xspace(tmp_path / "t.xplane.pb", [(
        "/device:TPU:0", metadata,
        {"XLA Ops": [(2, 9_000), (1, 4_000), (3, 1_000), (4, 3_000)]})])
    records = _aggregate(device_plane(read_xspace(path)), 1)
    assert {(r["op"], r["component"], r["pass"]) for r in records} == {
        ("fusion.1", "mlp", "bwd"), ("copy-done.2", "unattributed", ""),
        ("all-reduce.1", "comm", "fwd")}
    rows = aggregate_by_component(records)
    assert [(r["component"], r["pass"]) for r in rows] == [
        ("mlp", "bwd"), ("comm", "fwd"), ("unattributed", "")]
    assert sum(r["share"] for r in rows) == pytest.approx(1.0)
    table = format_profile(records)
    assert table.index("device time by component") < \
        table.index("per-module profile")


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="op-level device tracing needs TPU")
def test_engine_module_profile_live():
    import numpy as np
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2(gpt2_tiny(dtype=jnp.bfloat16)), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "steps_per_print": 1000000})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 256, size=(2, 128)).astype(
        np.int32)}
    records, table = engine.module_profile(batch, depth=2, n_steps=2)
    assert any("h_0" in r["module"] for r in records)
    assert {"mlp", "optimizer", "loss"} <= {r["component"] for r in records}
    assert {"fwd", "bwd"} <= {r["pass"] for r in records}
    assert "TOTAL" in table and "device time by component" in table
