"""Test harness setup: run JAX on CPU with 8 virtual devices.

Multi-chip code paths (mesh/shard_map/ppermute) are validated without TPU
hardware by forcing the host platform to expose 8 devices — the strategy
SURVEY.md section 4 prescribes. The tests run on the CPU
(``JAX_PLATFORMS=cpu``, which the platform pin below repeats so a bare
``pytest`` on a machine with a chip stays off it); the chip path is
``python chip_smoke.py`` through the chip tool. ``XLA_FLAGS`` must be set
before the CPU client spins up.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (flags must be set first)

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache shared across test processes/runs: the
# suite's wall is dominated by CPU compiles (SPMD partitioning,
# interpret-mode pallas), and every entry is keyed by the HLO hash so
# re-runs of unchanged kernels skip straight to execution. The directory
# comes from the one resolver the runtimes use (tune.compile_cache:
# JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
from distributed_backtesting_exploration_tpu.tune import (  # noqa: E402
    compile_cache as _compile_cache)

_compile_cache.configure()

# Runtime lockdep (analysis.lockdep): DBX_LOCKDEP=1 turns the WHOLE
# tier-1 suite into a race harness — every in-process gRPC integration
# fixture then runs with instrumented package locks recording real
# acquisition edges and blocking-under-lock violations. Installed here,
# before any fixture constructs a queue/worker/cache, so every package
# lock is wrapped; a no-op (nothing patched) when the knob is unset.
from distributed_backtesting_exploration_tpu.analysis import (  # noqa: E402
    lockdep as _lockdep)

_lockdep.maybe_install()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {devs}"
    return devs
