"""Test harness: run everything on a virtual 8-device CPU mesh.

This reproduces the reference's "multi-node without a cluster" trick
(reference: lab/hw01/homework 1 b/homework_1_b1.sh spawns N localhost gloo
processes) in-process: XLA fakes 8 host devices, so every shard_map/pjit
code path exercises real multi-device partitioning and collectives.

The env vars MUST be set before jax is imported anywhere.
"""

import os

from experiments._cpu_pin import COLLECTIVE_TIMEOUT_FLAGS

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "collective" not in os.environ["XLA_FLAGS"]:
    # Oversubscribed-core hardening — rationale in experiments/_cpu_pin.py.
    os.environ["XLA_FLAGS"] += COLLECTIVE_TIMEOUT_FLAGS

import jax  # noqa: E402
import pytest  # noqa: E402

# The tests run on the CPU whatever platform the environment names: the
# config update takes effect because no backend has been initialized yet.
jax.config.update("jax_platforms", "cpu")
# Serialize dispatch: overlapped steps' collectives can deadlock the virtual
# CPU mesh (failure mode 2 in experiments/_cpu_pin.py).
jax.config.update("jax_cpu_enable_async_dispatch", False)
# Persistent XLA compilation cache: $JAX_COMPILATION_CACHE_DIR where set
# (tier1.yml sets it), else <repo>/.jax_cache — utils/compilation_cache.py.
from ddl25spring_tpu.utils.compilation_cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {devs}"
    return devs
