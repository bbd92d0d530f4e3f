"""Pin the CPU platform with virtual devices — shared __main__ boilerplate.

Must be called BEFORE the first jax device use (this module itself imports
jax only inside the function, after setting XLA_FLAGS, so importing it is
side-effect free). The pin goes through ``jax.config`` so that it also holds
when the caller's environment names another platform.
"""

from __future__ import annotations

import os

# Virtual multi-device CPU hardening, shared by the experiments runner,
# tests/conftest.py, and __graft_entry__.dryrun_multichip. Two distinct
# failure modes on an oversubscribed (1-core) host, both observed on the
# 6-device DP×PP run:
#
# 1. STARVATION: a device busy computing reaches its collective long after
#    its peers. XLA-CPU's default 40 s rendezvous *termination* timeout
#    (rendezvous.cc) assumes a core per participant and aborts the process;
#    raise it and the stuck-warning window (the flags below).
# 2. DEADLOCK: with async dispatch, consecutive train steps overlap in
#    flight, and their cross-module collectives can interleave into a
#    rendezvous that never completes (wedged at a ppermute with 5/6
#    arrivals at both 40 s and 1200 s). No timeout fixes this one —
#    dispatch must be serialized (`jax_cpu_enable_async_dispatch=False`,
#    applied in pin_cpu_virtual / conftest).
# 3. RESIDUAL STOCHASTIC WEDGE: even with 1+2 applied, the 6-participant
#    DP×PP topology still wedges within ~100 iterations at a cross-module
#    ppermute with 4-5/6 arrivals and 0% CPU — the thunk executor runs
#    collective thunks on a shared worker pool that a blocking rendezvous
#    can park, and on this host the pool is smaller than 6. (The
#    3-participant pp3 topology fits and never wedges; a 50-iter
#    6-participant smoke can pass by luck.) There is NO runtime-level fix
#    in this XLA build — the legacy non-thunk runtime is gone
#    (``--xla_cpu_use_thunk_runtime`` warns "no longer supported" and is a
#    no-op). Long runs on big virtual topologies must instead be made
#    kill-safe: orbax checkpoint/resume + incremental CSV sinking +
#    ``experiments/watchdog.py`` (kill on progress stall, relaunch,
#    resume).
COLLECTIVE_TIMEOUT_FLAGS = (
    " --xla_cpu_collective_timeout_seconds=1200"
    " --xla_cpu_collective_call_terminate_timeout_seconds=1200")


def pin_cpu_virtual(n_devices: int = 8) -> None:
    os.environ.setdefault("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] += \
            f" --xla_force_host_platform_device_count={n_devices}"
    if "collective" not in os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] += COLLECTIVE_TIMEOUT_FLAGS
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_enable_async_dispatch", False)  # mode 2 above
