"""Attention dispatch: Pallas flash attention on TPU, jnp reference elsewhere.

This is the TPU answer to the reference's fused softmax/attention CUDA kernels
(csrc/transformer/softmax_kernels.cu and the attention-score path of
ds_transformer_cuda.cpp): one fused kernel that never materializes the
[S, S] score matrix in HBM.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.utils.platform import is_tpu_backend


def reference_attention(q, k, v, causal=False, bias=None, scale=None,
                        segment_ids=None):
    """Pure-XLA attention on [B, H, S, D] tensors. Numerically the ground
    truth for the Pallas kernels (the test methodology of the reference's
    test_cuda_forward.py, SURVEY §4). K/V may carry Hkv < H heads
    (grouped-query); the reference repeats them (the kernels do not)."""
    B, H, S, D = q.shape
    if k.shape[1] != H:
        rep = H // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    neg = jnp.float32(-1e30)
    if causal:
        causal_mask = jnp.tril(jnp.ones((S, k.shape[2]), dtype=bool))
        scores = jnp.where(causal_mask[None, None], scores, neg)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        scores = jnp.where(seg_mask, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs.astype(q.dtype), v)


def _flash(q, k, v, causal, scale):
    """The Pallas flash kernel, placed on the engine's mesh. GSPMD cannot
    partition a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned"), so under a multi-device engine trace the kernel runs
    per device inside a shard_map over the engine's mesh: batch on the
    batch axes (ZeRO data parallelism), heads on the model axis (TP),
    each when it divides. One device, no engine mesh, or a region that
    is already manual: the kernel is called as is."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.parallel import mesh as mesh_lib
    kernel = functools.partial(flash_attention, causal=causal, scale=scale)
    mesh = mesh_lib.pinned_mesh()
    if mesh is None or mesh.size == 1 or mesh_lib.in_manual_region():
        return kernel(q, k, v)
    batch_axes = mesh_lib.batch_sharding(mesh).spec[0]
    n_batch = mesh_lib.dp_world_size(mesh)
    n_model = mesh_lib.mesh_axis_size(mesh, mesh_lib.MODEL_AXIS)
    spec = jax.sharding.PartitionSpec(
        batch_axes if n_batch > 1 and q.shape[0] % n_batch == 0 else None,
        mesh_lib.MODEL_AXIS if n_model > 1 and q.shape[1] % n_model == 0
        and k.shape[1] % n_model == 0 else None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def dot_product_attention(q, k, v, causal=False, bias=None, scale=None,
                          segment_ids=None, use_flash=None):
    """[B, H, S, D] attention. ``use_flash=None`` auto-selects the Pallas
    flash kernel on TPU for flash-compatible shapes. K/V may carry
    Hkv < H heads (grouped-query): the flash kernel streams the reduced
    cache directly via Hkv-aware block maps — full-head K/V is never
    materialized in the forward. A flash kernel that fails to lower
    raises: nothing here drops to the O(S^2) reference behind the
    caller's back."""
    if use_flash is None:
        use_flash = is_tpu_backend() and bias is None and segment_ids is None
    if use_flash:
        return _flash(q, k, v, causal, scale)
    return reference_attention(q, k, v, causal=causal, bias=bias, scale=scale,
                               segment_ids=segment_ids)
