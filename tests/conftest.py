"""Test configuration.

Multi-device tests run on a virtual 8-device CPU mesh — the analog of
the reference's multi-node-on-one-machine pattern (SURVEY.md §4.2:
``ray.cluster_utils.Cluster``): N simulated devices on the XLA CPU
backend let all sharding/collective invariants run without TPU
hardware. These env vars must be set before jax is first imported
anywhere in the test process.
"""

import os

# Force CPU: tests must never grab the chip, whatever JAX_PLATFORMS
# the caller's shell exported. The env var is for the subprocesses we
# spawn; jax.config pins this process.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Persistent XLA compilation cache: the model tests compile the same
# tiny graphs every run — warm runs skip straight to execution. Worker
# subprocesses get the same directory from the runtime.
from ray_tpu.util import compile_cache  # noqa: E402

compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402


# -- host-contention gate (tests import this from conftest) ------------
# Perf floors measured on an idle box are meaningless under load: the
# documented runner must stay green on a busy 1-core host. Floors
# divide by ``relax`` when the load factor crosses SOFT; tests skip
# outright past HARD (a number measured at 6x oversubscription guards
# nothing).

LOAD_SOFT, LOAD_HARD = 1.5, 4.0


def host_load_factor() -> float:
    """1-minute loadavg per core (0.0 where unavailable)."""
    try:
        return os.getloadavg()[0] / max(1, os.cpu_count() or 1)
    except (OSError, AttributeError):
        return 0.0


def perf_floor_gate():
    """-> relax divisor for perf floors; skips the calling test on a
    hopelessly contended host."""
    load = host_load_factor()
    if load > LOAD_HARD:
        pytest.skip(f"host load factor {load:.1f} > {LOAD_HARD}: "
                    f"perf floors are meaningless here")
    return 4.0 if load > LOAD_SOFT else 1.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running learning/e2e test")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection run (ResourceKiller / drain / "
        "preemption)")
    config.addinivalue_line(
        "markers",
        "partition: network-fault run (ChaosTransport frame faults "
        "/ silent partitions)")
    config.addinivalue_line(
        "markers",
        "scale: full-N scale-envelope run (scripts/run_scale.sh; "
        "tier-1 runs the small-N variants)")


@pytest.fixture
def rt():
    """A fresh multiprocess runtime per test."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def rt_local():
    """In-process (local_mode) runtime — fast, for API-shape tests."""
    import ray_tpu
    ray_tpu.init(local_mode=True)
    yield ray_tpu
    ray_tpu.shutdown()
