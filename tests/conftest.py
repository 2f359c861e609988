"""Test config: run on a virtual 8-device CPU mesh so multi-chip sharding
paths are exercised without TPU hardware (the driver separately dry-runs
multi-chip via __graft_entry__.dryrun_multichip). The platform is pinned
here as well as by the tier-1 command's JAX_PLATFORMS=cpu, so a bare
``pytest`` on a machine with a chip still leaves the chip alone.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Program verification + per-pass translation validation are ON for the
# whole suite (framework/analysis.py): every compile-cache miss verifies
# the program and every optimization pass's output. Off by default in
# production (FLAGS_verify_passes=0) — the bench measures the overhead.
os.environ.setdefault("FLAGS_verify_passes", "1")

# bench.py and __graft_entry__.py turn JAX's persistent compilation cache
# on (paddle_tpu/utils/compile_cache.py). The children the tests start
# inherit this switch and leave it off: on a CPU sandbox every cache hit
# makes the XLA:CPU AOT loader print a machine-feature mismatch error
# (ROADMAP D9).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # keep `-m "not slow"` (the tier-1 filter) warning-free
    config.addinivalue_line(
        "markers",
        "slow: long kill/restart or multi-process tests excluded from the "
        "fast tier-1 run")


@pytest.fixture(autouse=True)
def _fresh_retry_budget():
    """The retry budget is PROCESS-global by design (one bucket bounds
    every layer's amplification); across a test suite that would let a
    retry-heavy test starve an unrelated later test's legitimate
    retries, so each test starts with a fresh bucket."""
    from paddle_tpu import resilience
    resilience.reset_retry_budget()
    yield
    resilience.reset_retry_budget()


@pytest.fixture
def fault_points():
    """Fault-injection handle (paddle_tpu.resilience): arm named failure
    points in wire/io with ``fault_points.fault_injection(point, ...)``;
    everything armed is cleared after the test, pass or fail."""
    from paddle_tpu import resilience
    resilience.clear_faults()
    yield resilience
    resilience.clear_faults()
