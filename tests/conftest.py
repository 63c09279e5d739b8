"""Shared test fixtures.

NOTE: tests run with the real single CPU device (no
xla_force_host_platform_device_count here by design — only
launch/dryrun.py sets that, see system requirements). Multi-device tests
spawn subprocesses via ``tests/util_subproc.py``.
"""
import os

# Keep CPU tests deterministic and small-memory.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:  # container has no hypothesis; use the shim
    from _hypothesis_shim import install as _install_hypothesis_shim

    _install_hypothesis_shim()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _reset_obs():
    """Fresh global tracer + metrics registry + fault injector per test
    (all three are process-global by design; tests must not see each
    other's spans, counters, or per-site fault counters)."""
    yield
    from repro.obs import metrics, trace
    from repro.resilience import faults

    trace.reset()
    metrics.reset()
    faults.reset()
