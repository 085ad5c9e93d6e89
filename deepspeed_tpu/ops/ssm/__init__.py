"""State-space (Mamba-2) ops: the mixer's mathematics (``mamba2``) and
the recurrent-state contract of the serving path (``state``, the
sibling of ``ops/attention/kv_cache.py``)."""

from deepspeed_tpu.ops.ssm import mamba2, state  # noqa: F401
