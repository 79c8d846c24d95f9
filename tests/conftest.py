"""Test configuration.

Tests run on the XLA CPU backend with 8 virtual devices so multi-chip
sharding paths (jax.sharding.Mesh over ICI in production) are exercised
without TPU hardware, per the project's multi-chip test strategy.  The
chip itself is reached through the chip tool with `python chip_smoke.py`,
never from the tests.

The platform is a hard set, not setdefault, and is also forced via
jax.config: an interpreter start-up hook may have imported jax already.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the heavy pairing-kernel compiles are
# identical across runs, so pay them once per machine, not per pytest
# invocation — at JAX_COMPILATION_CACHE_DIR where that is set, at the
# fixed <checkout>/.jax_cache otherwise.  (The cache key includes
# platform/flags, so the 8-device CPU programs never leak into TPU runs.)
from teku_tpu.infra import compilecache  # noqa: E402

compilecache.configure(min_compile_s=2)
