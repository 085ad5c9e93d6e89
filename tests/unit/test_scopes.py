"""Every operation of a cell's programs carries a scope that
``tracing.component`` can name (PR 60).

The programs are lowered on the CPU at each family's tiny config and
read BEFORE any XLA pass: every instruction of that module is one JAX
emitted, with the name stack it was traced under as ``op_name``.  An
instruction inside a called function holds a path relative to its call
site; the paths are composed here as XLA's inliner composes them on the
way to the compiled text a device profile's ``tf_op`` comes from.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.lib import xla_client as xc

import deepspeed_tpu
from deepspeed_tpu import tracing
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.serving import ServingScheduler

# what computes nothing: "not a constant, reshape, broadcast, tuple or
# convert alone", the instructions the conversion to HLO adds, the
# wrappers (their bodies are walked), sharding annotations
NOT_COMPUTING = {"parameter", "constant", "reshape", "broadcast", "tuple",
                 "get-tuple-element", "convert", "bitcast-convert", "iota",
                 "call", "while", "conditional", "annotation"}
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEES = re.compile(r"(?:to_apply|body|condition|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def lowered_paths(lowered):
    """``[(opcode, path)]`` of every instruction of a lowered program.
    A ``call``'s callee is walked under the call's own path, a loop's or
    a conditional's bodies under their instruction's prefix (JAX names
    what it traces inside them ``while/body/...`` itself); a reducer or
    a comparator is part of its instruction."""
    module = lowered.compiler_ir(dialect="hlo").get_hlo_module()
    options = xc._xla.HloPrintOptions()
    options.print_metadata = True
    computations, entry, current = {}, None, None
    for line in module.to_string(options).splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(2)
            computations[current] = []
            entry = current if m.group(1) else entry
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        rest = m.group(1)
        # "<shape> <opcode>(operands), attributes": a tuple shape is
        # parenthesised and holds spaces
        at = rest.find(") ") + 1 if rest.startswith("(") else rest.find(" ")
        opcode = rest[at:].lstrip().split("(")[0]
        if opcode == "custom-call" and "Sharding\"" in rest:
            opcode = "annotation"
        name = _OP_NAME.search(rest)
        callees = _CALLEES.findall(rest)
        branches = _BRANCHES.search(rest)
        if branches:
            callees += [b.strip().lstrip("%")
                        for b in branches.group(1).split(",")]
        computations[current].append(
            (opcode, name.group(1) if name else "", callees))
    out = []

    def walk(computation, prefix):
        for opcode, name, callees in computations[computation]:
            path = "/".join(p for p in (prefix, name) if p)
            out.append((opcode, path))
            if opcode in ("call", "while", "conditional"):
                for callee in callees:
                    walk(callee, path if opcode == "call" else prefix)
    walk(entry, "")
    return out


def unnamed(lowered, model):
    """The computing operations that no token names.  Inside the model
    (after ``.../<Model>/``) a token of the model's own has to decide:
    the ``horizon`` / ``train_loop`` scopes round a whole loop would
    name anything."""
    bad = []
    for opcode, path in lowered_paths(lowered):
        if opcode in NOT_COMPUTING:
            continue
        inside = re.split(rf"[/(]{model}\)*/", path, maxsplit=1)
        where = tracing.component(inside[-1] if len(inside) > 1 else path,
                                  opcode)
        if where in ("other", "unattributed"):
            bad.append((opcode, path, where))
    return bad


# ------------------------------------------------------------- serving

def _family(name):
    if name == "llama":
        from deepspeed_tpu.models.llama import Llama, llama_tiny
        return Llama(llama_tiny())
    if name == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
        return GPT2(gpt2_tiny())
    if name == "nemotron_h":
        from deepspeed_tpu.models.nemotron_h import (NemotronH,
                                                     nemotron_h_tiny)
        return NemotronH(nemotron_h_tiny(first_held_expert=4))
    if name == "falcon_h1":
        from deepspeed_tpu.models.falcon_h1 import FalconH1, falcon_h1_tiny
        return FalconH1(falcon_h1_tiny())
    if name == "mimo_v2":
        from deepspeed_tpu.models.mimo_v2 import MiMoV2, mimo_v2_tiny
        return MiMoV2(mimo_v2_tiny())
    if name == "afmoe":
        from deepspeed_tpu.models.afmoe import AFMoE, afmoe_tiny
        return AFMoE(afmoe_tiny())
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3,
                                                  deepseek_v3_tiny)
    return DeepseekV3(deepseek_v3_tiny())


@functools.lru_cache(maxsize=None)
def serving_programs(family):
    """{"prefill" | "decode_multi": the lowered program} of one short
    request through the scheduler, re-lowered through the comm ledger's
    capture: exactly the executables serving ran."""
    engine = deepspeed_tpu.init_inference(
        _family(family), dtype="float32", kv_cache_dtype="float32")
    engine.init_params(seed=3)
    sched = ServingScheduler(engine, num_slots=2, num_pages=12,
                             page_size=16, max_pages_per_slot=6,
                             prefill_chunk=8, comm_telemetry=True)
    sched.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    sched.run()
    out = {}
    for (name, _, _), (fn_attr, specs, statics) in \
            engine._comm_capture.items():
        if name not in out:
            with engine._serving_scope():
                out[name] = getattr(engine, fn_attr).lower(*specs, *statics)
    return type(engine.module).__name__, out


@pytest.mark.parametrize("program", ["prefill", "decode_multi"])
@pytest.mark.parametrize("family", ["llama", "gpt2", "nemotron_h",
                                    "falcon_h1", "mimo_v2", "deepseek_v3",
                                    "afmoe"])
def test_every_serving_operation_has_a_component(family, program):
    model, programs = serving_programs(family)
    assert unnamed(programs[program], model) == []


# ------------------------------------------------------------ training

def _train_program(stage):
    from deepspeed_tpu.models.gpt2 import GPT2, gpt2_tiny
    shape = {"data": 4} if stage == 3 else {"data": 1}
    mesh = make_mesh(MeshConfig(**shape),
                     devices=jax.devices()[:shape["data"]])
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": stage,
                              "stage3_param_persistence_threshold": 1000},
        "mesh": shape, "steps_per_print": 10 ** 9}
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, 256, (2 * shape["data"], 32)).astype(np.int32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2(gpt2_tiny(dtype=jnp.float32)), config=config, mesh=mesh,
        example_batch=batch, seed=3)
    if stage == 3:
        # the loop program the train cells run, through the census's
        # own record of what was dispatched
        engine.train_loop([batch], sync=True)
        assert engine._gather_plan is not None
        name, args = engine._census_probe
        return getattr(engine, name).lower(*args)
    # the one-step program, through compiled_step_text's seam: no loop
    # scope round the loss and the update
    _, state, rest, dev_batch, rng_key, lr = engine._step_probe_args(batch)
    return engine._step_gas1.lower(state.params, state.opt_state, rest,
                                   dev_batch, rng_key, lr)


@pytest.mark.parametrize("stage", [0, 3])
def test_every_training_operation_has_a_component(stage):
    lowered = _train_program(stage)
    assert unnamed(lowered, "GPT2") == []
    seen = {tracing.component(path, opcode)
            for opcode, path in lowered_paths(lowered)
            if opcode not in NOT_COMPUTING}
    assert {"loss", "optimizer", "mlp", "attn_core", "head"} <= seen
    passes = {tracing.pass_of(path) for _, path in lowered_paths(lowered)}
    assert passes == {"", "fwd", "bwd"}


# ------------------------------------------------------- the vocabulary

@pytest.mark.parametrize("path, opcode, component, which", [
    ("jit(decode_multi)/horizon/while/body/closed_call/Llama/layers_3/attn/"
     "pallas_call", "", "attn_core", ""),
    ("jit(prefill)/Llama/layers_3/attn/q_proj/dot_general:", "",
     "attn_proj", ""),
    ("jit(prefill)/Llama/layers_3/attn/rope/mul", "", "attn_proj", ""),
    ("jit(prefill)/Llama/layers_3/attn/cache/scatter", "", "cache", ""),
    ("jit(prefill)/Llama/layers_3/mlp/w_down/dot_general", "", "mlp", ""),
    ("jit(step)/transpose(jvp(GPT2))/h_3/mlp/fc_in/dot_general", "", "mlp",
     "bwd"),
    ("jit(step)/jvp(GPT2)/h_3/ln_1/reduce_sum", "", "norm", "fwd"),
    ("jit(step)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add", "",
     "loss", "bwd"),
    ("jit(prefill)/MiMoV2/layers_2/swa/wq/dot_general", "", "attn_proj", ""),
    ("jit(prefill)/AFMoE/layers_2/swa/attn_proj/wg/dot_general", "",
     "attn_proj", ""),
    ("jit(prefill)/AFMoE/layers_2/swa/attn_proj/q_norm/rsqrt", "",
     "attn_proj", ""),
    ("jit(prefill)/AFMoE/layers_2/swa/jit(paged_prefill)/paged_prefill", "",
     "attn_core", ""),
    ("jit(prefill)/AFMoE/layers_2/norm/post_ff_norm/mul", "", "norm", ""),
    ("jit(prefill)/AFMoE/layers_2/moe/shared/w_up/dot_general", "", "mlp",
     ""),
    ("jit(prefill)/NemotronH/layers_0/mamba/ssm/mul", "", "ssm", ""),
    ("jit(prefill)/NemotronH/layers_1/moe/router/top_k", "", "router", ""),
    ("jit(prefill)/Llama/norm/rsqrt", "", "norm", ""),
    ("jit(prefill)/NemotronH/norm_f/rsqrt", "", "head", ""),
    ("jit(decode_multi)/horizon/while/body/add", "", "sample", ""),
    ("jit(step_loop)/train_loop/while/body/closed_call/jvp(GPT2)/h_0/mlp/"
     "fc_in/zero_gather/sharding_constraint", "%all-gather-start.3",
     "comm", "fwd"),
    ("jit(step)/jvp(GPT2)/h_0/mlp/fc_in/dot_general", "%all-reduce.1",
     "comm", "fwd"),
    ("jit(f)/jit(_where)/select_n", "", "other", ""),
    ("", "%copy-done.7", "unattributed", ""),
    # what the compiler gives the operations it makes itself: the loop's
    # or the call's own path, an argument's name, its own kernel's name
    ("jit(decode_multi)/horizon/while/body/closed_call", "%slice-done.3",
     "unattributed", ""),
    ("jit(f)/Llama/layers_0/mlp/jit(silu)", "", "mlp", ""),
    ("params['layers_22']['moe']['w_up']:", "%copy.4", "experts", ""),
    ("params['layers_3']['attn']['wk']['kernel']", "", "attn_proj", ""),
    ("pools['layers'][0]['k_pages']", "%copy.9", "cache", ""),
    ("ragged-dot-none:", "%ragged-dot-none.2", "experts", ""),
])
def test_component_resolves_as_written(path, opcode, component, which):
    assert tracing.component(path, opcode) == component
    assert tracing.pass_of(path) == which


def test_vocabulary_is_closed_and_one_token_means_one_component():
    assert list(tracing.COMPONENTS)[-2:] == ["other", "unattributed"]
    tokens = [t for toks in tracing.COMPONENTS.values() for t in toks]
    assert len(tokens) == len(set(tokens))
    assert not tracing.COMPONENTS["other"]
    assert not tracing.COMPONENTS["unattributed"]
