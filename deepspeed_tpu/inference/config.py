"""Inference config (reference: ``deepspeed/inference/config.py:128``
``DeepSpeedInferenceConfig`` + ``DeepSpeedTPConfig`` :49, ``DeepSpeedMoEConfig``
:67, quant config :114).

Same JSON/kwargs surface; TPU semantics: `tensor_parallel.tp_size` becomes
the `model` mesh axis size, dtype becomes the compute dtype, and
`replace_with_kernel_inject` selects the Pallas attention path (on TPU the
"kernel injection" decision is just an attention-impl flag — the model is
already native).
"""

from typing import Any, Dict, Optional

from pydantic import BaseModel, ConfigDict, Field


class DeepSpeedTPConfig(BaseModel):
    model_config = ConfigDict(extra="allow", populate_by_name=True)
    enabled: bool = True
    tp_size: int = 1
    mpu: Optional[Any] = None
    tp_group: Optional[Any] = None


class DeepSpeedMoEConfig(BaseModel):
    model_config = ConfigDict(extra="allow")
    enabled: bool = True
    ep_size: int = 1
    moe_experts: Any = 1
    type: str = "standard"


class QuantizationConfig(BaseModel):
    model_config = ConfigDict(extra="allow")
    enabled: bool = False
    num_bits: int = 8
    group_size: int = 64


class InferenceCheckpointConfig(BaseModel):
    model_config = ConfigDict(extra="allow")
    checkpoint_dir: Optional[str] = None
    save_mp_checkpoint_path: Optional[str] = None
    base_dir: Optional[str] = None


class DeepSpeedInferenceConfig(BaseModel):
    """Mirrors the reference's field surface (inference/config.py:128)."""
    model_config = ConfigDict(extra="allow", populate_by_name=True)

    replace_with_kernel_inject: bool = Field(False, alias="kernel_inject")
    dtype: str = "bfloat16"            # torch.* names accepted via validator
    tensor_parallel: DeepSpeedTPConfig = Field(
        default_factory=DeepSpeedTPConfig, alias="tp")
    moe: DeepSpeedMoEConfig = Field(default_factory=DeepSpeedMoEConfig)
    quant: QuantizationConfig = Field(default_factory=QuantizationConfig)
    checkpoint: Optional[Any] = None
    max_out_tokens: int = Field(1024, alias="max_tokens")
    min_out_tokens: int = Field(1, alias="min_tokens")
    max_batch_size: int = 1
    replace_method: str = "auto"
    enable_cuda_graph: bool = False    # accepted, no-op (XLA always compiles)
    zero: Dict[str, Any] = Field(default_factory=dict)
    triangular_masking: bool = True
    return_tuple: bool = True
    # TPU additions
    mesh: Optional[Dict[str, int]] = None
    # multi-slice topologies: ICI (within-slice) sizes ride `mesh`,
    # the across-slice DCN factors ride this — per-axis mesh size is
    # their product (parallel/topology.make_hybrid_mesh; pure config,
    # the serving axis rules are untouched)
    mesh_dcn: Optional[Dict[str, int]] = None
    kv_cache_dtype: str = "bfloat16"
    # paged-attention kernel dispatch policy (ops/attention/decode.py
    # paged_kernel_decision): "auto" picks the Pallas kernels — paged
    # decode, and paged flash-prefill for prefill/verify — on TPU
    # with 128-aligned pages (shard_mapped per-shard on a multi-device
    # mesh) and the jnp gather reference otherwise; "force" pins the
    # kernel (interpret mode off-TPU — the CI parity oracle);
    # "reference" pins the gather fallback.  Trace-time static: set it
    # before the first serving dispatch, not mid-flight.
    paged_kernel: str = "auto"
    # pluggable checkpoint backend (checkpoint/backend.py) — must match
    # the backend the training engine saved with
    checkpoint_engine: Dict[str, Any] = Field(default_factory=dict)

    def model_post_init(self, _ctx):
        # normalize torch-style dtype strings ("torch.float16", "fp16", "half")
        name = str(self.dtype).lower().replace("torch.", "")
        aliases = {"half": "float16", "fp16": "float16", "bf16": "bfloat16",
                   "float": "float32", "fp32": "float32", "int8": "int8"}
        name = aliases.get(name, name)
        if name == "int8":
            # reference semantics (inference/config.py): dtype=torch.int8
            # means int8 weight quantization with half-precision compute
            self.quant.enabled = True
            name = "bfloat16"
        object.__setattr__(self, "dtype", name)
        # kv_cache_dtype takes the same float aliases PLUS the quantized
        # paged-pool dtypes: "int8" / "fp8" (e4m3) store int8/fp8 KV
        # pages with parallel per-row f32 scale pools (ops/quant/kv.py);
        # unlike dtype, kv "int8" is NOT weight quantization — the two
        # knobs are independent
        kv = str(self.kv_cache_dtype).lower().replace("torch.", "")
        kv_aliases = dict(aliases, fp8="fp8", float8="fp8",
                          float8_e4m3fn="fp8")
        object.__setattr__(self, "kv_cache_dtype",
                           kv_aliases.get(kv, kv))
