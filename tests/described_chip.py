"""What the ``tests/test_tpu_compile*.py`` files share: the described v5e host
(no chip attached), the fixture that steers a module's compiles to it, and
the questions their tests ask of a lowered or compiled text."""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
# no chip is opened here, only described: parallel test workers may each load
# libtpu (its /tmp lockfile otherwise admits one process)
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import pytest  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402  (the model, batch and serving block it runs)
from tests import hlo_text  # noqa: E402


@functools.cache
def topo():
    """The described four-chip v5e host. Asked for when the first test runs,
    not at import: collection stays cheap and the same in every worker."""
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


SDS = jax.ShapeDtypeStruct
BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(autouse=True, scope="module")
def _compile_for_the_chip():
    """Skip the whole module where the topology cannot be described. Else
    steer the code under test to its TPU branch, in the test and not by an
    option of the program: ``is_tpu_backend()`` asks ``jax.default_backend()``.
    The persistent compile cache is off around these compiles (an entry for
    a described chip cannot be read back without one, and warns), and XLA's
    optimizations are on (conftest turns them off for CPU speed)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo()
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    opt_was = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_disable_most_optimizations", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_disable_most_optimizations", opt_was)
    cc.reset_cache()
    mp.undo()
    jax.clear_caches()


def _paged_pool(bits):
    cfg = chip_smoke.model_config(rehearse=False)
    from deepspeed_tpu.serving import PagedKVCache, cache_spec_from_config
    spec = cache_spec_from_config(cfg, "gpt2",
                                  {"serving": dict(chip_smoke.SERVING,
                                                   kv_cache_bits=bits)})
    return spec, jax.eval_shape(lambda: PagedKVCache(spec).pool)


def on_chip(tree, sharding=None):
    """Shapes placed on the described chip (or under ``sharding``)."""
    sharding = sharding or SingleDeviceSharding(topo().devices[0])
    return jax.tree_util.tree_map(
        lambda s: SDS(s.shape, s.dtype, sharding=sharding), tree)


def compile_on_chip(fn, *shapes):
    """(lowered text, compiled) of ``fn`` for one described v5e chip."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jitted.lower(*on_chip(shapes))
    return lowered.as_text(), lowered.compile()


def kernel_names(text):
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


def pallas_grids(fn, *shapes):
    """The grid of every ``pallas_call`` that tracing ``fn`` reaches."""
    return hlo_text.pallas_grids(jax.make_jaxpr(fn)(*shapes).jaxpr)


def flash_calls(hlo):
    """The compiled text's Pallas calls, cut before their serialized
    bodies: result shapes, operands and their layout constraints."""
    return [ln.split("backend_config=")[0] for ln in hlo.splitlines()
            if "tpu_custom_call" in ln]


def head_major_operands(calls):
    """Shapes [.., S, 64] among the calls' operands and results: a
    head-major block of head_dim 64, padded to 128 lanes in HBM."""
    return [shape for ln in calls
            for shape in re.findall(r"\w+\[[\d,]*,64\]", ln)]


def rematted(attend):
    """``attend`` as a block under remat has it: ``jax.checkpoint`` with the
    policy of ``models/gpt2.block_remat_policy``."""
    from deepspeed_tpu.models.gpt2 import block_remat_policy
    return jax.checkpoint(attend, prevent_cse=True,
                          policy=block_remat_policy())


def assert_dense_lse_kept(hlo, calls, dense):
    """A rematted call's gradient program holds the forward kernel ONCE
    (``calls``: the forward and the single-pass backward — ``flash_o`` /
    ``flash_lse`` are kept; the window family's forward, dq and dkv), the
    forward kernel writes lse as ``dense`` ([BH, S / 128, 1, 128]: 128 real
    lanes), every backward kernel reads it so, and no [.., S, 1] column,
    128 x the size in HBM, is anywhere in the step."""
    assert len(calls) in (2, 3), calls
    assert hlo_text.rematted_forward_attention(hlo) == []
    assert all(dense in c for c in calls), (dense, calls)
    assert sum(dense in c.split(" custom-call(")[0] for c in calls) == 1
    assert not re.search(r"f32\[\d+,\d{4,},1\]", hlo)
