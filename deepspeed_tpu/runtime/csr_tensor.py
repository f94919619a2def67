"""Row-compressed sparse gradients — reference runtime/csr_tensor.py:11
`CSRTensor` and the engine's sparse allreduce (engine.py:195-202,1444-1515).

The reference compresses embedding gradients to (row indices, dense rows)
before the data-parallel allreduce: each rank touches only the vocabulary
rows present in its local batch, so exchanging compressed rows beats
allreducing the full [V, E] matrix.

TPU shape: XLA needs static shapes, so compression selects up to a fixed
`max_rows` budget of touched rows (sized from batch·seq, exact when every
batch touches ≤ max_rows distinct ids). The collective is an `all_gather` of
(indices, rows) over the data axis inside `shard_map`, followed by a
scatter-add — the all-gather rides ICI, and the scatter-add lands on the
owning shard under GSPMD. With dense row-occupancy the engine's default
psum path wins; this is the opt-in for large-vocab embedding layers, exactly
the trade the reference makes (sparse_gradients_enabled, engine.py:195).
"""

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSRTensor:
    """Row-compressed tensor: `indices[i]` is the dense row of `values[i]`.
    Padding slots carry index == dense_shape[0] (dropped on scatter).
    Mirrors the reference CSRTensor surface (runtime/csr_tensor.py:11):
    sparse/dense construction, addition, to_dense."""
    indices: jax.Array            # [max_rows] int32
    values: jax.Array             # [max_rows, width]
    dense_shape: Tuple[int, int]  # static

    def tree_flatten(self):
        return (self.indices, self.values), self.dense_shape

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    @classmethod
    def from_dense(cls, dense, max_rows: int) -> "CSRTensor":
        """Compress the nonzero rows of [V, E] into a static [max_rows, E]
        buffer. If more than max_rows rows are nonzero, the largest-magnitude
        rows win (lossy overflow is asserted against in sparse_all_reduce
        by budget sizing)."""
        V, E = dense.shape
        row_mag = jnp.sum(jnp.abs(dense), axis=1)
        # top-k by magnitude, nonzero rows first
        _, idx = jax.lax.top_k(row_mag, min(max_rows, V))
        got = row_mag[idx] > 0
        idx = jnp.where(got, idx, V)          # pad slot → out-of-range
        vals = jnp.where(got[:, None],
                         dense[jnp.clip(idx, 0, V - 1)], 0)
        if idx.shape[0] < max_rows:           # V < max_rows: pad up
            pad = max_rows - idx.shape[0]
            idx = jnp.concatenate([idx, jnp.full((pad,), V, idx.dtype)])
            vals = jnp.concatenate([vals, jnp.zeros((pad, E), vals.dtype)])
        return cls(idx.astype(jnp.int32), vals, (V, E))

    def to_dense(self) -> jax.Array:
        V, E = self.dense_shape
        out = jnp.zeros((V, E), self.values.dtype)
        return out.at[self.indices].add(self.values, mode="drop")

    def add(self, other: "CSRTensor") -> "CSRTensor":
        """Concatenating row lists implements addition (duplicates resolve in
        to_dense's scatter-add), like reference CSRTensor.add."""
        assert self.dense_shape == other.dense_shape
        return CSRTensor(jnp.concatenate([self.indices, other.indices]),
                         jnp.concatenate([self.values, other.values]),
                         self.dense_shape)

    @property
    def nnz_rows(self):
        return jnp.sum(self.indices < self.dense_shape[0])


def sparse_all_reduce(dense_grad, mesh, axis: str, max_rows: int):
    """Data-parallel sum of a row-sparse gradient via compressed exchange:
    per-rank compress → all_gather(idx, rows) over `axis` → scatter-add.
    Numerically equals psum when each rank touches ≤ max_rows rows
    (the engine sparse path, reference engine.py:1444-1515).

    `dense_grad` carries the per-rank gradient stacked over the axis — i.e.
    call this inside shard_map/pjit where `dense_grad` is the local [V, E]
    shard-view; here we provide the host-level entry taking a global array
    sharded over `axis` on its leading (batch-of-grads) dim is NOT the
    layout — instead pass the per-rank grads as [world, V, E]."""
    world = mesh.shape[axis]

    def local_reduce(g):          # g: [1, V, E] local block
        g = g[0]
        csr = CSRTensor.from_dense(g, max_rows)
        all_idx = jax.lax.all_gather(csr.indices, axis)    # [W, max_rows]
        all_val = jax.lax.all_gather(csr.values, axis)     # [W, max_rows, E]
        V, E = csr.dense_shape
        out = jnp.zeros((V, E), g.dtype)
        out = out.at[all_idx.reshape(-1)].add(
            all_val.reshape(-1, E), mode="drop")
        return out[None]

    fn = jax.shard_map(local_reduce, mesh=mesh,
                   in_specs=P(axis, None, None),
                   out_specs=P(axis, None, None))
    summed = fn(dense_grad)
    # every rank computed the same full sum; return rank-0's copy
    return summed[0]
