"""Test harness: single-process multi-device simulation.

The reference spawns N processes with real NCCL for every distributed test
(tests/unit/common.py:16 @distributed_test). On TPU/JAX we instead force the
CPU backend to expose 8 virtual devices, so every mesh/sharding/collective
path runs in-process (SURVEY §4 'lesson for the TPU rebuild'). This must run
before jax initializes, hence module-level in conftest.

The budget (ROADMAP.md D5, PR 52). The driver runs tier-1 under ``-n 6 --dist
loadfile`` with a limit of 1,470 s on a machine at least 1.28 x slower than the
builder's, and a run the clock cuts is counted where it stopped. So on the
builder's machine the whole run takes at most 950 s, and no FILE more than
200 s of its cases' junit seconds: ``loadfile`` deals a file to ONE worker, so
the wall is (all files' seconds) / 6 plus the tail the last long file leaves
while five workers idle — a long file costs more than its seconds. A file
past 200 s is cut at a seam it already has, what its parts share in an
importable module beside them (``tests/flash_cases.py``,
``tests/described_chip.py``, ``tests/zero_matrix.py``,
``tests/model_cases.py``). And the suite is compile-bound, so a test
differentiates or runs a whole model under ``jax.jit`` — one program, where
the same call unjitted dispatches and compiles every primitive of the forward
and backward pass on its own — takes a jaxpr's text from that one trace
(``tests/hlo_text.run_with_jaxpr``), and builds what a file's cases share
(a model, its weights, a reference's gradients) once a module. A kernel
with a ``custom_vjp`` has two forward programs — the primal call and the
forward rule a gradient program runs in its place — so a test that compares
values calls the kernel undifferentiated too (its own small ``jax.jit``).
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
