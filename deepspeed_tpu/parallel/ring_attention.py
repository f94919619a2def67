"""Ring attention — sequence/context parallelism over ICI.

The reference's long-context story is single-device block-sparse attention
(SURVEY §5.7); ring attention is the modern distributed upgrade this rebuild
provides as a first-class axis: the sequence dim is sharded over the 'seq'
mesh axis, K/V blocks rotate around the ring with `ppermute` while each
device accumulates online-softmax partial results for its local Q block —
exact attention over the full sequence with O(S/n) memory per device and
compute/communication overlap on ICI (Liu et al. 2023, Ring Attention).

Numerics: accumulators (o, m, l) in fp32; K/V travel in their compute dtype.
Works under autodiff (ppermute transposes to the reverse rotation).
"""

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import mesh as mesh_lib

NEG_INF = -1e30


def ring_attention(q, k, v, mesh, causal=False, scale=None,
                   axis: str = mesh_lib.SEQ_AXIS):
    """[B, H, S, D] attention with S sharded over ``axis``.

    Accepts fully-replicated or seq-sharded inputs (GSPMD reshards to the
    in_specs); returns output sharded the same way as q.
    """
    n = mesh.shape.get(axis, 1)
    B, H, S, D = q.shape
    scale = float(scale) if scale is not None \
        else 1.0 / float(np.sqrt(D))  # sync-ok: python scalar at trace time
    if n == 1:
        from deepspeed_tpu.ops.attention import dot_product_attention
        return dot_product_attention(q, k, v, causal=causal, scale=scale)

    assert S % n == 0, f"seq len {S} not divisible by seq axis {n}"
    chunk = S // n
    perm = [(j, (j + 1) % n) for j in range(n)]
    # manual over the seq axis only: batch/head mesh axes stay auto, so
    # the body sees global B and H and GSPMD keeps their tiling
    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, axis_names=frozenset({axis}),
        in_specs=(spec, spec, spec), out_specs=spec)
    def run(ql, kl, vl):
        idx = jax.lax.axis_index(axis)
        qf = ql.astype(jnp.float32)

        q_pos = idx * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, chunk), 0)

        def step(carry, s):
            o, m, l, kc, vc = carry
            src = (idx - s) % n  # which global chunk kc/vc currently is
            sc = jnp.einsum("bhqd,bhkd->bhqk", qf,
                            kc.astype(jnp.float32)) * scale
            if causal:
                k_pos = src * chunk + jax.lax.broadcasted_iota(
                    jnp.int32, (chunk, chunk), 1)
                sc = jnp.where((q_pos >= k_pos)[None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            o_new = o * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
            # rotate K/V one hop around the ring
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return (o_new, m_new, l_new, kc, vc), None

        zeros_f32 = functools.partial(jnp.zeros, dtype=jnp.float32)
        var = lambda x: jax.lax.pcast(x, (axis,), to="varying")  # noqa: E731
        o0 = var(zeros_f32((B, H, chunk, D)))
        m0 = var(jnp.full((B, H, chunk), NEG_INF, jnp.float32))
        l0 = var(zeros_f32((B, H, chunk)))
        (o, m, l, _, _), _ = jax.lax.scan(
            step, (o0, m0, l0, kl, vl), jnp.arange(n))
        l_safe = jnp.maximum(l, 1e-30)
        return (o / l_safe[..., None]).astype(ql.dtype)

    return run(q, k, v)
