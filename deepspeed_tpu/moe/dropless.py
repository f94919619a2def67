"""Dropless mixture-of-experts FFN: every token reaches its k experts.

The capacity path (``moe/layer.py``) routes through one-hot
``[G, S, E, C]`` dispatch masks and DROPS what overflows an expert's
buffer; at 64 experts top-8 (OLMoE) that mask is the wrong mechanism and
dropping the wrong mathematics. Here the ``T x k`` assignments are sorted by
expert and the expert bank is one grouped matmul per projection
(``ops/pallas/grouped_matmul.py``) over rows in expert order:

    router (float32): logits = h Wg, softmax, top-k          ``moe_router``
    stable argsort of the T*k expert ids, the row gather     ``moe_dispatch``
    gate / up grouped matmuls, silu * up, down               ``moe_gmm*``
    rows back to token order, weighted by the routing
    probability, summed over each token's k                  ``moe_combine``

No capacity factor exists and nothing is dropped; the group sizes are data,
so one compiled program serves every routing pattern. Both row moves are
PERMUTATIONS (each of the T*k rows moves to exactly one place), so their
backward passes are gathers by the inverse permutation and not scatter-adds.

Expert weights are stacked ``[E, ...]`` leaves (``[L, E, ...]`` under a
layer scan): ZeRO's ``shard_spec_for_leaf`` and the gather edge treat them
like any kernel. The router's two auxiliary terms arrive in the ``losses``
collection ALREADY multiplied by their coefficients (``moe_balance``: the
Switch load-balancing loss ``E * sum_e f_e P_e``; ``moe_z``: the mean of
``logsumexp(logits)^2``); their unweighted values and the routing's balance
go to the ``stats`` collection, which the engine carries out of the step and
folds into the ``moe/*`` gauges at a ``steps_per_print`` boundary.
"""

import functools
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.moe.layer import load_balance_loss
from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul
from deepspeed_tpu.telemetry.spans import annotate


# what ``DroplessMoE`` sows into ``stats``, and the gauge each is read under
STAT_GAUGES = {"moe_aux_loss": "moe/aux_loss", "moe_z_loss": "moe/z_loss",
               "moe_rows_max_over_mean": "moe/rows_max_over_mean",
               "moe_dropped_rows": "moe/dropped_rows"}


def route(logits, k, norm_topk_prob):
    """(weights [T, k] float32, experts [T, k] int32, probabilities [T, E])
    of float32 router logits [T, E]: softmax over the experts, the k
    largest probabilities as they are (renormalised to sum to one only when
    ``norm_topk_prob``)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_w, top_e.astype(jnp.int32), probs


def sort_by_expert(top_e):
    """(order, inverse): ``order[r]`` is the assignment (token * k + choice)
    that lands in sorted row r — a stable sort, so an expert's rows keep
    token order — and ``inverse[a]`` the sorted row of assignment a."""
    flat = top_e.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    return order, inverse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gather_rows(x, order, inverse, k):
    """Token rows [T, H] -> sorted rows [T*k, H]: row r is the token of
    assignment ``order[r]``."""
    return jnp.take(x, order // k, axis=0)


def _gather_rows_fwd(x, order, inverse, k):
    return gather_rows(x, order, inverse, k), inverse


def _gather_rows_bwd(k, inverse, g):
    # each token's k rows gathered back beside each other, then summed
    back = jnp.take(g, inverse, axis=0)
    return (back.reshape(-1, k, g.shape[-1]).sum(axis=1).astype(g.dtype),
            None, None)


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def unsort_rows(y, order, inverse):
    """Sorted rows [T*k, H] -> assignment order (token-major)."""
    return jnp.take(y, inverse, axis=0)


def _unsort_rows_fwd(y, order, inverse):
    return unsort_rows(y, order, inverse), order


def _unsort_rows_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)


class DroplessMoE(nn.Module):
    """[B, S, H] -> [B, S, H] through ``num_experts`` SwiGLU experts of
    width ``d_ff``, ``k`` a token. ``balance_coeff`` / ``z_coeff`` weight
    the two auxiliary losses sown into ``losses``."""
    num_experts: int
    k: int
    d_ff: int
    norm_topk_prob: bool = False
    balance_coeff: float = 0.01
    z_coeff: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, S, H = x.shape
        E, K, F = self.num_experts, self.k, self.d_ff
        T = B * S
        init = nn.initializers.normal(0.02)
        wg = self.param("router", init, (H, E), self.param_dtype)
        w_gate = self.param("gate_proj", init, (E, H, F), self.param_dtype)
        w_up = self.param("up_proj", init, (E, H, F), self.param_dtype)
        w_down = self.param("down_proj", init, (E, F, H), self.param_dtype)
        xt = x.reshape(T, H)

        with annotate("moe_router"):
            # float32 from a float32 cast of the hidden state, at full
            # precision (a TPU's default float32 matmul rounds its
            # operands to bf16): the 8th and 9th probabilities of a token
            # can tie to bf16 rounding
            logits = jnp.dot(xt.astype(jnp.float32), wg.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            top_w, top_e, probs = route(logits, K, self.norm_topk_prob)
            chosen = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(axis=1)
            group_sizes = chosen.sum(axis=0).astype(jnp.int32)      # [E]
            balance = load_balance_loss(probs, chosen)
            z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

        with annotate("moe_dispatch"):
            order, inverse = sort_by_expert(top_e)
            xs = gather_rows(xt, order, inverse, K)                 # [T*K, H]
        dt = self.dtype
        gate = grouped_matmul(xs, w_gate.astype(dt), group_sizes)
        up = grouped_matmul(xs, w_up.astype(dt), group_sizes)
        with annotate("moe_act"):
            h = checkpoint_name(nn.silu(gate) * up, "mlp_fc")
        ys = grouped_matmul(h, w_down.astype(dt), group_sizes)
        with annotate("moe_combine"):
            ya = unsort_rows(ys, order, inverse).reshape(T, K, H)
            y = jnp.sum(ya.astype(jnp.float32) * top_w[:, :, None], axis=1)
            y = y.astype(dt).reshape(B, S, H)

        if self.is_mutable_collection("losses"):
            self.sow("losses", "moe_balance", self.balance_coeff * balance)
            self.sow("losses", "moe_z", self.z_coeff * z)
        if self.is_mutable_collection("stats"):
            rows = group_sizes.astype(jnp.float32)
            for name, value in (
                    ("moe_aux_loss", balance), ("moe_z_loss", z),
                    ("moe_rows_max_over_mean",
                     jnp.max(rows) * E / (T * K)),
                    # rows routed less rows the grouped matmuls computed
                    ("moe_dropped_rows", T * K - jnp.sum(rows))):
                self.sow("stats", name, jax.lax.stop_gradient(value))
        if self.is_mutable_collection("intermediates"):
            self.sow("intermediates", "top_e", top_e)
        return checkpoint_name(y, "mlp_proj")

