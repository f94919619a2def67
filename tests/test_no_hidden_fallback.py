"""Nothing on the main path may hide that the device did not do the work
(ISSUE 21): a flash kernel that fails raises, the TPU rule is the default
backend and an initialisation error is not "no TPU", a device mesh that cannot
be built on a TPU raises, and the compile cache goes where the environment
says."""

import importlib
import os
import subprocess
import sys
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp


def test_broken_flash_kernel_raises_instead_of_reference_attention(
        monkeypatch):
    from deepspeed_tpu.ops import attention
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

    def refused(*a, **k):
        raise RuntimeError("Mosaic refused the kernel")
    monkeypatch.setattr(fa, "flash_attention", refused)
    q = jnp.ones((1, 2, 64, 32), jnp.float32)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        attention.dot_product_attention(q, q, q, causal=True, use_flash=True)
    # the auto rule picks flash on a TPU backend — and still raises
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        attention.dot_product_attention(q, q, q, causal=True)
    # off the TPU the rule is the reference, by rule and not by accident
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    out = attention.dot_product_attention(q, q, q, causal=True)
    assert out.shape == q.shape


def test_tpu_rule_is_the_default_backend_and_errors_propagate(monkeypatch):
    from deepspeed_tpu.utils.platform import is_tpu_backend
    kernels = [importlib.import_module(f"deepspeed_tpu.ops.pallas.{m}")
               for m in ("blocksparse", "decode", "quantize",
                         "flash_attention", "rows_to_tokens")]
    assert not is_tpu_backend()
    assert all(m._interpret_default() for m in kernels)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert is_tpu_backend()
    assert not any(m._interpret_default() for m in kernels)

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        is_tpu_backend()          # never read as "interpret mode"


def test_make_mesh_raises_on_tpu_and_reshapes_only_on_cpu(monkeypatch):
    from jax.experimental import mesh_utils
    from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh

    def no_topology(*a, **k):
        raise ValueError("cannot fit the logical mesh to the ICI torus")
    monkeypatch.setattr(mesh_utils, "create_device_mesh", no_topology)
    chips = [types.SimpleNamespace(platform="tpu", id=i) for i in range(4)]
    with pytest.raises(ValueError, match="ICI torus"):
        make_mesh(MeshConfig(data=4), devices=chips)
    mesh = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
    assert mesh.shape["data"] == 4


# ---------------------------------------------------------- compile cache

def _cache_dir_after_enable(**env):
    code = ("import jax\n"
            "from deepspeed_tpu.utils.platform import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run([sys.executable, "-c", code], env={**base, **env},
                         capture_output=True, text=True, check=True,
                         timeout=120,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    return out.stdout.split()


def test_compile_cache_is_placed_from_outside_or_at_the_fixed_path(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert _cache_dir_after_enable() == [os.path.join(repo, ".jax_cache")] * 2
    # set from outside: jax read it itself, the helper only reports it
    assert _cache_dir_after_enable(
        JAX_COMPILATION_CACHE_DIR=str(tmp_path)) == [str(tmp_path)] * 2
    src = open(os.path.join(repo, "deepspeed_tpu", "utils",
                            "platform.py")).read()
    assert src.count('"jax_compilation_cache_dir"') == 1
    assert "jax_compilation_cache_dir" not in open(
        os.path.join(repo, "chip_smoke.py")).read()
