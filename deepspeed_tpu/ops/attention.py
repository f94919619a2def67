"""Attention dispatch: Pallas flash attention on TPU, jnp reference elsewhere.

This is the TPU answer to the reference's fused softmax/attention CUDA kernels
(csrc/transformer/softmax_kernels.cu and the attention-score path of
ds_transformer_cuda.cpp): one fused kernel that never materializes the
[S, S] score matrix in HBM.

Two entries, one per operand layout: ``dot_product_attention`` takes
head-major [B, H, S, D] q, k, v (grouped-query K/V, bias and segment ids
included); ``fused_qkv_attention`` takes a model's fused projection
[B, S, 3*H*D] and returns [B, S, H*D], which on the flash path spares every
transpose between the two layouts.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.utils.platform import is_tpu_backend


def reference_attention(q, k, v, causal=False, bias=None, scale=None,
                        segment_ids=None, window=None):
    """Pure-XLA attention on [B, H, S, D] tensors. Numerically the ground
    truth for the Pallas kernels (the test methodology of the reference's
    test_cuda_forward.py, SURVEY §4). K/V may carry Hkv < H heads
    (grouped-query); the reference repeats them (the kernels do not).
    ``window`` (with ``causal``): key j is visible to query i iff
    ``0 <= i - j < window``."""
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
        if window is not None:
            causal_mask &= ~jnp.tril(jnp.ones_like(causal_mask), -window)
        scores = jnp.where(causal_mask[None, None], scores, neg)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        scores = jnp.where(seg_mask, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs.astype(q.dtype), v)


def check_qkv_shapes(q, k, v):
    """Raise, with the shapes, where k is not as wide as q or v is not one
    value a key (v's own width is free: the output takes it)."""
    if q.shape[-1] != k.shape[-1] or k.shape[:-1] != v.shape[:-1]:
        raise ValueError(
            "attention contracts q with k over one head width and takes one "
            f"value a key: q {tuple(q.shape)}, k {tuple(k.shape)}, "
            f"v {tuple(v.shape)}")


def to_head_major(t, heads):
    """[B, S, H*D] -> [B, H, S, D]."""
    B, S, E = t.shape
    return t.reshape(B, S, heads, E // heads).transpose(0, 2, 1, 3)


def from_head_major(t):
    """[B, H, S, D] -> [B, S, H*D]."""
    B, H, S, D = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def _device_axes(batch, heads):
    """(mesh, batch axes, model axis) to run a Pallas kernel per device.
    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under a multi-device engine trace the
    kernel runs inside a shard_map over the engine's mesh: ``batch`` rows
    on the batch axes (ZeRO data parallelism), ``heads`` on the model
    axis (TP), each when it divides, else None. Mesh None — one device,
    no engine mesh, or a region that is already manual: the kernel is
    called as is."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.pinned_mesh()
    if mesh is None or mesh.size == 1 or mesh_lib.in_manual_region():
        return None, None, None
    n_batch = mesh_lib.dp_world_size(mesh)
    n_model = mesh_lib.mesh_axis_size(mesh, mesh_lib.MODEL_AXIS)
    return (mesh,
            mesh_lib.batch_sharding(mesh).spec[0]
            if n_batch > 1 and batch % n_batch == 0 else None,
            mesh_lib.MODEL_AXIS if n_model > 1 and heads % n_model == 0
            else None)


def _flash(q, k, v, causal, scale, window=None):
    """The head-major Pallas flash kernel ([B, H, S, D]), placed on the
    engine's mesh (``_device_axes``)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    kernel = functools.partial(flash_attention, causal=causal, scale=scale,
                               window=window)
    mesh, batch_axes, model_axis = _device_axes(
        q.shape[0], np.gcd(q.shape[1], k.shape[1]))
    if mesh is None:
        return kernel(q, k, v)
    spec = jax.sharding.PartitionSpec(batch_axes, model_axis)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _flash_fused_qkv(qkv, heads, causal, scale):
    """The Pallas flash kernels on the fused projection [B, S, 3*H*D]
    (``flash_attention_bse``), placed on the engine's mesh. With heads on
    a model axis the thirds are split first — a device's share of each is
    a column range of its own — and the kernel sees three [B, S, H*D / tp]
    arrays and its own head count."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_bse
    mesh, batch_axes, model_axis = _device_axes(qkv.shape[0], heads)
    operands = (qkv,)
    if model_axis is not None:
        operands = tuple(jnp.split(qkv, 3, axis=-1))
        heads //= mesh.shape[model_axis]
    kernel = functools.partial(flash_attention_bse, heads=heads,
                               causal=causal, scale=scale)
    if mesh is None:
        return kernel(*operands)
    spec = jax.sharding.PartitionSpec(batch_axes, None, model_axis)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec,) * len(operands),
                         out_specs=spec, check_vma=False)(*operands)


def dot_product_attention(q, k, v, causal=False, bias=None, scale=None,
                          segment_ids=None, use_flash=None, window=None):
    """[B, H, S, D] (head-major) attention. ``use_flash=None``
    auto-selects the Pallas flash kernel on TPU for flash-compatible
    shapes. K/V may carry Hkv < H heads (grouped-query): the flash kernel
    streams the reduced cache directly via Hkv-aware block maps —
    full-head K/V is never materialized in the forward. A flash kernel
    that fails to lower raises: nothing here drops to the O(S^2)
    reference behind the caller's back. A model whose q, k, v are one
    fused projection has ``fused_qkv_attention``. ``window`` (with
    ``causal``): a query sees itself and the ``window - 1`` keys before
    it; on the flash path the window kernels walk that band alone.

    ``v`` may be [B, Hkv, S, Dv] with Dv != D (latent attention: q·k over
    192, values of 128): the output is Dv wide; on the flash path that is
    the chunked kernels' alone, so with a ``window`` it raises here, with
    the shapes, and not as a block-shape error deep in a kernel."""
    if window is not None and not causal:
        raise ValueError("a window is a causal band: pass causal=True")
    check_qkv_shapes(q, k, v)
    if use_flash is None:
        use_flash = is_tpu_backend() and bias is None and segment_ids is None
    if use_flash and window is not None and v.shape[-1] != q.shape[-1]:
        raise ValueError(
            f"window={window} with a q·k width of {q.shape[-1]} and a value "
            f"width of {v.shape[-1]}: unequal widths run in the chunked flash "
            f"kernels, which take no window (q {tuple(q.shape)}, "
            f"v {tuple(v.shape)})")
    if use_flash:
        return _flash(q, k, v, causal, scale, window)
    return reference_attention(q, k, v, causal=causal, bias=bias, scale=scale,
                               segment_ids=segment_ids, window=window)


def fused_qkv_attention(qkv, heads, causal=False, scale=None,
                        use_flash=None):
    """Attention on the model's own layout: ``qkv`` [B, S, 3*H*D], the
    fused projection (q, k, v its thirds), in; [B, S, H*D] out, ready for
    the output projection. No bias, no segment ids (the flash-compatible
    arguments). On the flash path the whole-row kernels read the thirds
    in place and write o, dq, dk, dv in this layout, so no head-major
    copy of any of them exists; a shape those kernels do not take goes
    through [B, H, S, D] as ``dot_product_attention`` would have it."""
    if use_flash is None:
        use_flash = is_tpu_backend()
    if use_flash:
        return _flash_fused_qkv(qkv, heads, causal, scale)
    q, k, v = (to_head_major(t, heads) for t in jnp.split(qkv, 3, axis=-1))
    return from_head_major(reference_attention(q, k, v, causal=causal,
                                               scale=scale))


def block_diffusion_mask(L, block_length):
    """bool [2L, 2L]: may query row r see key row s, over the L noised rows
    then the L clean rows of a block-diffusion training step (BD3-LMs,
    arXiv 2503.09573). With blk(r) = (r mod L) // block_length: a noised
    query sees its own block among the noised rows (both directions) and the
    clean rows of strictly earlier blocks; a clean query the clean rows of
    its own and earlier blocks."""
    r = jnp.arange(2 * L)
    clean, blk = r >= L, (r % L) // block_length
    cq, ck, bq, bk = clean[:, None], clean[None], blk[:, None], blk[None]
    return (~cq & ~ck & (bk == bq)) | (~cq & ck & (bk < bq)) \
        | (cq & ck & (bk <= bq))


def reference_block_diffusion_attention(q, k, v, block_length, scale=None):
    """``block_diffusion_attention`` with the dense mask, in plain XLA: the
    oracle of the kernels (``ops/pallas/block_diffusion_attention.py``), and
    the one place a [2L, 2L] array is built."""
    bias = jnp.where(block_diffusion_mask(q.shape[2] // 2, block_length),
                     0.0, -1e30)
    return reference_attention(q, k, v, bias=bias[None, None], scale=scale)


def block_diffusion_attention(q, k, v, block_length, scale=None,
                              use_flash=None):
    """[B, H, 2L, D] attention of a block-diffusion training step: the rows
    are a sequence's L noised tokens, then its L clean ones, and the mask
    (``block_diffusion_mask``) is given by ``block_length`` and the rows'
    count alone, never as an array. The Pallas kernels run on every backend
    (the interpreter off a TPU); ``use_flash=False`` is the dense-mask
    oracle. K/V may carry Hkv < H heads."""
    check_qkv_shapes(q, k, v)
    if use_flash is not None and not use_flash:
        return reference_block_diffusion_attention(q, k, v, block_length,
                                                   scale)
    from deepspeed_tpu.ops.pallas.block_diffusion_attention import \
        block_diffusion_attention as kernel
    kernel = functools.partial(kernel, block_length=block_length, scale=scale)
    mesh, batch_axes, model_axis = _device_axes(
        q.shape[0], np.gcd(q.shape[1], k.shape[1]))
    if mesh is None:
        return kernel(q, k, v)
    spec = jax.sharding.PartitionSpec(batch_axes, model_axis)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# The dispatch of this file in one place (at its END: a line added above a
# kernel's calling frame moves the frames' line numbers inside every cell's
# serialized kernels, and ``benchmark/tools/lowered_step_hash.py`` holds the
# accepted cells' lowered steps to their parent's, unmasked).
DISPATCH = """The kernel families this file routes to, and the rule for each (what decides
is the caller's arguments and the shapes, never a model's name):

    causal / full    ``dot_product_attention`` (``fused_qkv_attention`` on the
                     fused layout): the whole-row flash kernels while a head's
                     row fits VMEM, the chunked ones past that and for a value
                     width that is not the q·k width
    band             ``dot_product_attention(window=)`` with a window shorter
                     than the sequence: the window kernels walk the band alone
    block-diffusion  ``block_diffusion_attention``: the mask given by
                     ``block_length`` and the rows' count, its own walk
    learned-sparse   ``learned_sparse_attention``: the mask is DATA, chosen a
                     step at a time by an indexer's exact top-k; the causal
                     tiles walked under it, with the indexer's KL

Each takes the Pallas kernels on a TPU (``use_flash=None``) and has a dense
plain-XLA form (``use_flash=False``, the kernels' oracle); under a
multi-device engine mesh the kernels run per device (``_device_axes``).
"""

def learned_sparse_attention(q, k, v, index_q, index_k, index_w, topk,
                             scale=None, use_flash=None):
    """(o [B, H, S, D], kl [B, S] float32, kept keys a query [B, S] int32,
    the kept set's bits uint8 [B, ceil(S / 8), S] by key and query: a
    caller's look, dead code where nothing reads it) of
    causal attention pruned by a learned indexer (DeepSeek-V3.2's sparse
    attention): query t scores every key s <= t as ``sum_j index_w[t, j]
    relu(index_q[t, j] . index_k[s])`` in float32, keeps the ``topk`` largest
    (all while t < topk; a tie goes to the smaller s; exact, no gradient
    through the choice), and every head attends to those keys alone; ``kl``
    is KL(mean over heads of the attention's probabilities || softmax of the
    kept scores), whose gradient reaches the indexer's three operands and
    nothing else, as ``o``'s reaches q, k, v alone. q [B, H, S, D]; k, v
    [B, Hkv, S, D]; index_q [B, J, S, Di]; index_k [B, S, Di] (one key a
    token); index_w [B, S, J], every scale folded in. The Pallas kernels
    (``ops/pallas/learned_sparse_attention.py``) on a TPU; ``use_flash=False``
    (and off a TPU) the dense plain-XLA oracle."""
    from deepspeed_tpu.ops.pallas import learned_sparse_attention as lsa
    check_qkv_shapes(q, k, v)
    scale = float(scale) if scale is not None else 1.0 / float(
        np.sqrt(q.shape[-1]))
    if use_flash is None:
        use_flash = is_tpu_backend()
    if not use_flash:
        return lsa.reference_learned_sparse_attention(
            q, k, v, index_q, index_k, index_w, int(topk), scale)
    kernel = functools.partial(lsa.learned_sparse_attention, topk=int(topk),
                               scale=scale)
    mesh, batch_axes, _ = _device_axes(q.shape[0], 1)
    if mesh is None:
        return kernel(q, k, v, index_q, index_k, index_w)
    spec = jax.sharding.PartitionSpec(batch_axes)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec,) * 6,
                         out_specs=(spec,) * 4, check_vma=False)(
        q, k, v, index_q, index_k, index_w)
