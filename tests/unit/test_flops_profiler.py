"""Flops profiler tests (reference
tests/unit/profiling/flops_profiler/test_flops_profiler.py — asserts the
computed flops are within tolerance of the analytic count)."""

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.profiling.flops_profiler import (FlopsProfiler,
                                                    get_model_profile)

from tests.unit.simple_model import random_lm_data


def test_get_model_profile_matches_analytic():
    from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
    cfg = gpt2_tiny()
    model = GPT2(cfg)
    b, l = 2, 32
    flops, macs, n_params = get_model_profile(
        model, input_shape=(b, l), print_profile=False)
    assert macs == flops / 2
    # analytic fwd flops ~= 2 * params * tokens (embeddings excluded;
    # attention adds more) — cost analysis must land within 3x
    dense_params = n_params - cfg.vocab_size * cfg.hidden_size \
        - cfg.max_seq_len * cfg.hidden_size
    analytic = 2 * dense_params * b * l
    assert analytic / 3 < flops < analytic * 5, (flops, analytic)


def test_get_model_profile_as_string():
    from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
    f, m, p = get_model_profile(GPT2(gpt2_tiny()), input_shape=(1, 16),
                                as_string=True, print_profile=False)
    assert all(isinstance(s, str) for s in (f, m, p))


def test_engine_flops_profile_and_config_hook(capsys):
    from tests.unit.simple_model import SimpleModel, simple_loss_fn, \
        random_regression_data
    model = SimpleModel()
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "mesh": {"data": 8},
        "flops_profiler": {"enabled": True, "profile_step": 1},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, loss_fn=simple_loss_fn(model))
    batch = random_regression_data(n=32)
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()  # profile_step fires here

    prof = engine.flops_profile()
    assert prof["flops_per_step"] > 0
    assert prof["params"] == sum(
        int(np.prod(np.shape(x))) for x in jax.tree.leaves(
            engine.state.params))

    fp = FlopsProfiler(engine)
    fp.start_profile()
    l2 = engine.forward(batch)
    engine.backward(l2)
    engine.step()
    fp.print_profile(step=2)
    assert fp.get_total_flops() == prof["flops_per_step"]


def test_flops_profile_with_gas():
    from tests.unit.simple_model import SimpleModel, simple_loss_fn, \
        random_regression_data
    model = SimpleModel()
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 2,
        "train_batch_size": 64,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "mesh": {"data": 8},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, loss_fn=simple_loss_fn(model))
    batch = random_regression_data(n=32)
    for _ in range(2):
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
    prof = engine.flops_profile()
    assert prof["flops_per_step"] > 0


def test_peak_table_keyed_by_reported_device_kind():
    """Peaks are keyed by the ``device_kind`` string a device reports; an
    unknown TPU is an error, never another chip's figure; non-TPU
    platforms keep the nominal row the CPU MFU-trend tests read."""
    from types import SimpleNamespace as Dev
    from deepspeed_tpu.profiling.flops_profiler.profiler import (
        peak_flops_per_device)
    assert peak_flops_per_device(
        Dev(platform="tpu", device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops_per_device(Dev(platform="tpu", device_kind="TPU v9"))
    assert peak_flops_per_device(
        Dev(platform="cpu", device_kind="cpu")) == 1e12
    assert peak_flops_per_device() == 1e12      # this CPU test run
