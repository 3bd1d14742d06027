"""Test harness config: force JAX onto CPU with 8 virtual devices so the
multi-chip sharding paths (jax.sharding.Mesh over the node axis) are
exercised without TPU hardware — the analog of the reference running its
integration suite against an in-process apiserver instead of a real cluster
(test/integration/util/util.go:42).

Both the env var and jax's ``jax_platforms`` config are set before any
backend initializes. The tests never claim a chip: on-chip checks live
in ``chip_smoke.py``, and tests/test_chip_compile.py only compiles for a
described one.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after env mutation is the point)

jax.config.update("jax_platforms", "cpu")
