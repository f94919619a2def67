"""Ulysses-style sequence parallelism — all-to-all head scatter.

The second of the two modern long-context strategies this rebuild provides
(with ring attention, parallel/ring_attention.py) as the upgrade of the
reference's single-device sparse-attention story (SURVEY §5.7). The design
is DeepSpeed-Ulysses (arXiv:2309.14509): activations arrive sequence-
sharded [B, S/n, H, D]; an all_to_all over the `seq` axis re-shards them to
head-sharded [B, S, H/n, D]; each device runs EXACT full-sequence attention
over its head subset (flash kernel); a reverse all_to_all restores sequence
sharding. Communication is O(B·S·E/n) per direction — constant in n vs
ring's n-step pipeline — and rides ICI.

Requires n_head % axis_size == 0. Works under autodiff (all_to_all
transposes to the reverse all_to_all).
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import mesh as mesh_lib


def _a2a(x, axis_name, scatter_dim, gather_dim):
    """all_to_all wrapper on a local block: scatter `scatter_dim` over the
    axis, gather `gather_dim` from it."""
    return jax.lax.all_to_all(x, axis_name, split_axis=scatter_dim,
                              concat_axis=gather_dim, tiled=True)


def ulysses_attention(q, k, v, mesh, causal=False, scale=None,
                      axis: str = mesh_lib.SEQ_AXIS):
    """[B, H, S, D] attention with S sharded over ``axis`` (Ulysses).

    Inputs may be replicated or seq-sharded; GSPMD reshards to the
    in_specs. Output shards like q ([B, H, S, D] with S over ``axis``).
    """
    n = mesh.shape.get(axis, 1)
    B, H, S, D = q.shape
    scale = float(scale) if scale is not None \
        else 1.0 / float(np.sqrt(D))  # sync-ok: python scalar at trace time
    if n == 1:
        from deepspeed_tpu.ops.attention import dot_product_attention
        return dot_product_attention(q, k, v, causal=causal, scale=scale)
    assert H % n == 0, f"n_head {H} not divisible by seq axis {n}"
    assert S % n == 0, f"seq len {S} not divisible by seq axis {n}"
    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, axis_names=frozenset({axis}),
        in_specs=(spec, spec, spec), out_specs=spec)
    def run(ql, kl, vl):
        # local blocks [B, H, S/n, D] → head-sharded full-seq
        # [B, H/n, S, D]: scatter heads (dim 1), gather sequence (dim 2)
        qh = _a2a(ql, axis, 1, 2)
        kh = _a2a(kl, axis, 1, 2)
        vh = _a2a(vl, axis, 1, 2)
        from deepspeed_tpu.ops.attention import dot_product_attention
        oh = dot_product_attention(qh, kh, vh, causal=causal, scale=scale)
        # back: scatter sequence (dim 2), gather heads (dim 1)
        return _a2a(oh, axis, 2, 1)

    return run(q, k, v)
