"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; sharding/protocol tests run on 8
virtual CPU devices (the TPU-native analogue of the reference's manual
16-subtask workstation runs, hs_err_pid77107.log:21).

``JAX_PLATFORMS=cpu`` selects the backend (jax reads it at import, and the
subprocesses tests spawn inherit it); ``XLA_FLAGS`` must be set before jax
initializes its CPU client. The ``jax.config.update`` below covers a pytest
plugin having imported jax before this file ran.
"""

import os
import re

flags = os.environ.get("XLA_FLAGS", "")
# replace (not merely keep) any preset device count: the suite requires 8
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)
