"""The flash kernels' column-block entry against the jnp reference, in the
interpreter (one kernel family a file: ``tests/test_flash_attention.py``)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.attention import (from_head_major,
                                         reference_attention, to_head_major)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from tests.flash_cases import _fa


# ------------------------------------------------------------------------
# the whole-row kernels on the model's own layout (ISSUE 30): heads as
# 128-lane COLUMN blocks of [B, S, H*D] operands — a fused projection read
# in place, or q, k, v apart where a third does not start on a lane block

def _bse_case(heads, D, apart, causal, dtype, S=128, B=1):
    """(column-block out and d(qkv), reference's, head-major kernels')."""
    fa = _fa()
    qkv = jax.random.normal(jax.random.PRNGKey(heads * D + S),
                            (B, S, 3 * heads * D), jnp.float32).astype(dtype)

    def columns(x):
        operands = jnp.split(x, 3, axis=-1) if apart else (x,)
        return fa.flash_attention_bse(*operands, heads=heads, causal=causal,
                                      interpret=True)

    def through(attend):
        return lambda x: from_head_major(attend(*(
            to_head_major(t, heads) for t in jnp.split(x, 3, axis=-1))))
    reference = through(functools.partial(reference_attention,
                                          causal=causal))
    head_major = through(functools.partial(flash_attention, causal=causal,
                                           interpret=True))

    def both(f):
        return f(qkv), jax.grad(lambda x: jnp.sum(jnp.sin(
            f(x).astype(jnp.float32))))(qkv)
    return both(columns), both(reference), both(head_major)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("heads,D,apart,per_block", [
    (4, 64, False, 2),      # E 256: qkv in place, two pairs
    (5, 64, False, 2),      # E 320 = 2.5 lane blocks: split, the tail head
    (4, 64, True, 2),       # the caller's own q, k, v
    (2, 128, False, 1),     # a head a block, in place
    (3, 128, True, 1),
    (4, 32, False, 4),      # four heads a block
], ids=["h4d64-inplace", "h5d64-split-tail", "h4d64-apart", "h2d128-inplace",
        "h3d128-apart", "h4d32-inplace"])
def test_column_block_kernels_match_reference_and_head_major(
        heads, D, apart, per_block, causal, dtype):
    """Forward and d(qkv) (dq | dk | dv) of the column-block entry against
    the float reference at the flash tests' tolerances, and against the
    head-major kernels, whose arithmetic it shares product for product
    (the added terms are exact zeros; delta is summed in the kernel)."""
    from deepspeed_tpu.telemetry.registry import default_registry
    cols, ref, hm = _bse_case(heads, D, apart, causal, dtype)
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == 0       # the head-major run
    f32 = dtype == jnp.float32
    for name, a, b, c, (rtol, atol) in zip(
            ("out", "d(qkv)"), cols, ref, hm,
            ((2e-4, 2e-5), (5e-3, 5e-4)) if f32 else ((5e-2, 5e-2),) * 2):
        assert a.shape == b.shape and a.dtype == b.dtype == dtype
        a, b, c = (np.asarray(t, np.float32) for t in (a, b, c))
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol, err_msg=name)
        np.testing.assert_allclose(a, c, rtol=1e-5 if f32 else 2e-2,
                                   atol=1e-5 if f32 else 2e-2, err_msg=name)
    jax.eval_shape(lambda x: _fa().flash_attention_bse(
        x, heads=heads, causal=causal, interpret=True),
        jax.ShapeDtypeStruct((1, 128, 3 * heads * D), dtype))
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == per_block


def test_column_block_entry_goes_head_major_where_heads_do_not_tile():
    """head_dim 48 tiles no lane block and E 64 fills not one: both run
    the head-major kernels through a transpose, and say so (gauge 0)."""
    from deepspeed_tpu.telemetry.registry import default_registry
    for heads, D in ((4, 48), (2, 32)):
        (out, grad), (ref, ref_grad), _ = _bse_case(heads, D, False, True,
                                                    jnp.float32)
        assert default_registry().peek_gauge(
            "attention/flash_heads_per_block") == 0
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(grad, ref_grad, rtol=5e-3, atol=5e-4)


def test_column_block_residuals_bind_under_dots_flash_fc_lean():
    """Under ``jax.checkpoint`` with the benchmark's remat policy the
    column-block VJP's ``flash_o`` / ``flash_lse`` are SAVED: the gradient
    program holds two Pallas calls (the forward kernel once, the backward
    kernel), where full remat holds a second forward; the gradients are
    the unrematted ones."""
    from jax.ad_checkpoint import checkpoint_name
    from deepspeed_tpu.models.gpt2 import _remat_policy
    fa = _fa()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 768), jnp.float32) / 16

    def block(x, w):
        qkv = checkpoint_name(x @ w, "qkv")
        o = fa.flash_attention_bse(qkv, heads=4, causal=True, interpret=True)
        return jnp.sum(jnp.sin(o))

    lean = jax.checkpoint(block, policy=_remat_policy("dots_flash_fc_lean"))
    full = jax.checkpoint(block)
    kernels = {}
    for name, f in (("lean", lean), ("full", full)):
        text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(x, w))
        kernels[name] = text.count("pallas_call[")
    assert kernels == {"lean": 2, "full": 3}, kernels
    for a, b in zip(jax.grad(lean, argnums=(0, 1))(x, w),
                    jax.grad(block, argnums=(0, 1))(x, w)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("axes", [dict(data=4), dict(data=2, model=2)],
                         ids=["data4", "data2xmodel2"])
def test_fused_qkv_attention_runs_per_device_on_the_engine_mesh(axes):
    """``ops.attention.fused_qkv_attention`` under an engine's pinned mesh:
    the kernels run per device inside a shard_map — batch on the data
    axis; with heads on a model axis the thirds are split first and each
    device takes its own column range of q, k and v — and forward and
    gradient are the reference's."""
    from deepspeed_tpu.ops.attention import fused_qkv_attention
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh
    from deepspeed_tpu.telemetry.registry import default_registry
    if len(jax.devices()) < 4:
        pytest.skip("need 4 devices")
    mesh = make_mesh(MeshConfig(**axes), devices=jax.devices()[:4])
    qkv = jax.random.normal(jax.random.PRNGKey(3), (4, 128, 3 * 256))

    def loss(x, use_flash):
        o = fused_qkv_attention(x, 4, causal=True, use_flash=use_flash)
        return jnp.sum(jnp.sin(o)), o

    with mesh_lib.layout_pins(mesh):
        (_, out), grad = jax.jit(jax.value_and_grad(
            functools.partial(loss, use_flash=True), has_aux=True))(qkv)
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == 2
    (_, ref), ref_grad = jax.value_and_grad(
        functools.partial(loss, use_flash=False), has_aux=True)(qkv)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(grad, ref_grad, rtol=5e-3, atol=5e-4)
