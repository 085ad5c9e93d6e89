"""deepspeed_tpu: TPU-native large-scale training & inference framework.

Keeps the reference's user-facing factory surface
(``deepspeed/__init__.py`` — ``initialize`` :53, ``init_inference`` :215,
``add_config_arguments`` :192) on a JAX/XLA/Pallas/pjit core.
"""

from deepspeed_tpu.version import __version__  # noqa: F401
from deepspeed_tpu import comm  # noqa: F401
from deepspeed_tpu.utils.logging import log_dist, logger  # noqa: F401


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, mpu=None, mesh=None,
               dist_init_required=None, collate_fn=None, config=None,
               config_params=None, loss_fn=None, example_batch=None, seed=0):
    """Build a training engine (reference ``deepspeed.initialize``).

    Arguments mirror the reference where meaningful on TPU:
      model: a flax.linen Module (the "client model").
      loss_fn: optional ``loss_fn(params, batch, rng) -> scalar``; defaults to
        the causal-LM contract (module(input_ids)->logits, next-token CE).
      config: JSON path or dict (same schema as the reference config).
      mesh: optional prebuilt jax.sharding.Mesh; otherwise built from the
        config's "mesh" section over all visible devices.
      example_batch: optional batch for eager parameter initialization;
        otherwise params initialize lazily on the first forward().

    Returns (engine, optimizer, training_dataloader, lr_scheduler) like the
    reference; `optimizer` is the engine's optax transformation.
    """
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    assert model is not None, "deepspeed_tpu.initialize: model is required"
    assert config is not None, "deepspeed_tpu.initialize: config is required"

    engine = DeepSpeedEngine(model=model, config=config, loss_fn=loss_fn,
                             mesh=mesh, training_data=training_data,
                             lr_scheduler=lr_scheduler, collate_fn=collate_fn,
                             example_batch=example_batch, seed=seed,
                             client_optimizer=optimizer)
    return engine, engine.tx, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Build an inference engine (reference ``deepspeed.init_inference``).

    ``model`` may be a native flax module, a HF transformers model
    instance, or a path to an HF checkpoint directory — the latter two
    are ingested through the policy system
    (``module_inject/replace_module.py:274`` capability)."""
    from deepspeed_tpu.inference.engine import DTYPES, InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    params = kwargs.pop("params", None)
    mesh = kwargs.pop("mesh_obj", None)
    if isinstance(config, DeepSpeedInferenceConfig):
        # re-validate so nested dicts/aliases in kwargs are coerced
        cfg = DeepSpeedInferenceConfig(**{**config.model_dump(), **kwargs}) \
            if kwargs else config
    else:
        if isinstance(config, str):
            import json
            with open(config) as f:
                config = json.load(f)
        merged = dict(config or {})
        merged.update(kwargs)
        cfg = DeepSpeedInferenceConfig(**merged)

    is_hf_instance = hasattr(model, "state_dict") and hasattr(model, "config")
    is_hf_dir = False
    if isinstance(model, str):
        import os
        is_hf_dir = os.path.isdir(model) and (
            os.path.exists(os.path.join(model, "config.json")))
    if is_hf_instance or is_hf_dir:
        if cfg.dtype not in DTYPES:
            raise ValueError(
                f"unsupported inference dtype {cfg.dtype!r}; pick one of "
                f"{sorted(DTYPES)} or 'int8' (weight-only quantization)")
        from deepspeed_tpu.module_inject import from_hf
        model, params = from_hf(model, dtype=DTYPES[cfg.dtype])
    return InferenceEngine(model, cfg, params=params, mesh=mesh)


def add_config_arguments(parser):
    """Add --deepspeed / --deepspeed_config to an argparse parser
    (reference ``deepspeed/__init__.py:192``)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag, parity with reference)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration")
    return parser
