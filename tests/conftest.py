"""Test harness: single-process multi-device simulation.

The reference spawns N processes with real NCCL for every distributed test
(tests/unit/common.py:16 @distributed_test). On TPU/JAX we instead force the
CPU backend to expose 8 virtual devices, so every mesh/sharding/collective
path runs in-process (SURVEY §4 'lesson for the TPU rebuild'). This must run
before jax initializes, hence module-level in conftest.
"""

import os

# hard override: the tests run on the CPU backend whatever the machine has
# (chip_smoke.py is the on-chip proof; tests/test_tpu_compile.py asks the
# TPU compiler about a described chip without one attached)
os.environ["JAX_PLATFORMS"] = "cpu"
# The suite is XLA-compile-bound on the CPU backend (tiny programs, hundreds
# of engine builds; the per-module cache clear below re-pays compiles), and
# the tier-1 runner has a hard wall-clock budget. Skipping XLA's expensive
# optimization passes cuts module times ~35% and changes nothing the suite
# asserts (numerics stay fp32-exact enough for every allclose; jaxpr-level
# structure tests never see XLA passes). Export-level so spawned worker
# processes (examples / launcher tests) inherit it; set it to 0 to measure
# with full optimizations.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The suite sets no persistent compilation cache; compile cost is bounded
# by the per-module clear below.
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight end-to-end variants excluded from the "
        "wall-clock-budgeted tier-1 run (run them with -m slow); each has "
        "a faster sibling covering the same subsystem in tier-1")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _np_seed():
    np.random.seed(0)


@pytest.fixture(autouse=True, scope="module")
def _bound_compile_cache():
    """Free jitted executables between test MODULES: the full suite
    (300+ tests) accumulates enough XLA CPU executables to OOM-abort the
    compiler partway through on small hosts (the r4 suite died with a
    Fatal abort inside backend_compile at ~70%); per-module clearing
    bounds the live set while keeping intra-module cache hits."""
    yield
    jax.clear_caches()
