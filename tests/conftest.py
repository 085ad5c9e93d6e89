"""Test configuration: force an 8-device virtual CPU platform so multi-chip
sharding logic is exercised without TPU hardware (SURVEY.md §4 implication).
Child processes the tests spawn inherit ``JAX_PLATFORMS=cpu`` and the
device-count flag through the environment.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)
