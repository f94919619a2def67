"""Dropless mixture-of-experts FFN: every token reaches its k experts.

The capacity path (``moe/layer.py``) routes through one-hot
``[G, S, E, C]`` dispatch masks and DROPS what overflows an expert's
buffer; at 64 experts top-8 (OLMoE) that mask is the wrong mechanism and
dropping the wrong mathematics. Here the ``T x k`` assignments are sorted by
expert and the expert bank is one grouped matmul per projection
(``ops/pallas/grouped_matmul.py``) over rows in expert order:

    router (float32): logits = h Wg, softmax, top-k          ``moe_router``
    stable argsort of the T*k expert ids, the row gather     ``moe_dispatch``
    gate / up grouped matmuls, act(gate) * up, down          ``moe_gmm*``
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

**One rank's share of an expert-parallel layer** (``experts_held`` /
``expert_share``): the layer holds experts ``[held * share, held * (share +
1))`` of ``num_experts``. The router is as wide as ever and the top-k is
renormalised over the k chosen; only the assignments whose expert is held
are rows here, sorted to the front (every other assignment carries the
sentinel key ``held``), and the output is the PARTIAL sum over the held
experts — nothing stands in for the absent experts, their rows or an
all-to-all; the shares of all ranks add up to the whole layer. Rows held
are data, so the row arrays are SLABS of a static ``_HELD_ROWS_SLACK`` times
the mean share: the rows held fit the first slab unless the routing sends
more than that here; then a ``lax.cond`` takes a second slab, and past two a
scan takes as many further slabs as the rows fill, one at a time, every slab
past the first recomputed in the backward pass so that none of them keeps
anything (exact for every routing, all ``T x k`` rows included;
the grouped matmul stops at the sum of a slab's group sizes;
``moe_held_slabs`` in ``stats`` says how many a layer took).
With fewer than ``T x k`` rows the two row moves are no permutations any
more: ``tokens_to_rows`` is a gather and its transpose ``rows_to_tokens`` a
segmented sum — the rows brought into token order (a sort of the slab's keys
and one row gather), then ONE kernel pass over the rows that have a token
(``ops/pallas/rows_to_tokens.py``: each block of tokens walks its contiguous
run of sorted rows and sums it in float32; rows past the rows held are not
read, ``moe_combine_rows_walked`` in ``stats`` is rows read over rows held) —
each the other's backward pass, neither a scatter-add. A ``shared_d_ff`` adds
one SwiGLU expert every token passes through, under a sigmoid gate
(``moe_shared``).

What a model may say otherwise, and no more: the experts' non-linearity
(``act``: ``silu``, ``relu`` — a ReGLU expert — or ``relu2``, its square);
whether an expert is a GATED unit at all (``gated=False``: two matrices,
``down(act(up(x)))``, and the shared expert likewise); whether the shared
expert sits under a sigmoid gate (``shared_gate=False``: it is added as it
is); the router's score (``score="sigmoid"``: each expert's own sigmoid in
place of the softmax over all) and a selection bias (``choice_bias``: a
vector added to the scores for the CHOICE of the k experts only, the
weights being the scores at the chosen experts without it); and the tensor
the ROUTER reads (``__call__(x, router_x=...)``: a router that sits ahead
of the mixer reads the block's input while the experts read the normed
stream after it; its gradient then enters the stream before the mixer). The
defaults are one input, a softmax, gated ``silu`` experts and a gated
shared expert. With both coefficients zero no auxiliary term is traced.
"""

import functools
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.moe.layer import load_balance_loss
from deepspeed_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                     lane_padded)
from deepspeed_tpu.ops.pallas.rows_to_tokens import (rows_walked,
                                                     sum_rows_by_token)
from deepspeed_tpu.telemetry.spans import annotate


# what ``DroplessMoE`` sows into ``stats``, and the gauge each is read under
STAT_GAUGES = {"moe_aux_loss": "moe/aux_loss", "moe_z_loss": "moe/z_loss",
               "moe_rows_max_over_mean": "moe/rows_max_over_mean",
               "moe_dropped_rows": "moe/dropped_rows"}
# ... and by a layer that holds a share of its experts
HELD_STAT_GAUGES = dict(STAT_GAUGES,
                        moe_rows_held_share="moe/rows_held_share",
                        moe_held_slabs="moe/held_slabs",
                        moe_combine_rows_walked="moe/combine_rows_walked")
# the experts' gate non-linearity, by the name a model gives ``act``
_ACTS = {"silu": nn.silu, "relu": nn.relu,
         "relu2": lambda x: jnp.square(nn.relu(x))}
# the name of a layer's selection bias leaf: a model whose layers carry one
# lists it in its ``buffer_leaves`` (``DroplessMoE``'s docstring)
CHOICE_BIAS = "e_score_correction_bias"
# static length of a held layer's row arrays over the mean rows held (4 did
# not fit the one cell that holds a share: PERF.md Findings PR 31)
_HELD_ROWS_SLACK = 2


def route(logits, k, norm_topk_prob, pin_choice=False, routed_scale=1.0,
          score="softmax", choice_bias=None):
    """(weights [T, k] float32, experts [T, k] int32, scores [T, E])
    of float32 router logits [T, E]: softmax over the experts (``score``
    ``"sigmoid"``: each expert's own sigmoid), the k
    largest scores as they are (renormalised to sum to one only when
    ``norm_topk_prob``), times ``routed_scale`` where it is not one (a
    published scaling factor on the routed experts' output).
    ``choice_bias`` [E]: added to the scores for the CHOICE alone — the k
    experts are the largest of ``scores + choice_bias``, their weights the
    scores at them without it (so no gradient reaches the bias).
    ``pin_choice``: the experts carry the checkpoint
    name ``moe_experts`` and the weights are the probabilities AT them, so
    that a remat policy which saves that name makes a recomputed forward
    pass route as the first one did (a tie between the k-th and the
    (k+1)-th probability can break the other way when XLA fuses the
    recomputation differently, and the backward pass would then be another
    routing's)."""
    logits = logits.astype(jnp.float32)
    probs = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    if choice_bias is None:
        top_w, top_e = jax.lax.top_k(probs, k)
    else:
        _, top_e = jax.lax.top_k(
            probs + choice_bias.astype(jnp.float32), k)
    if pin_choice:
        top_e = checkpoint_name(top_e, "moe_experts")
    if pin_choice or choice_bias is not None:
        top_w = jnp.take_along_axis(probs, top_e, axis=1)
    if norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    if routed_scale != 1.0:
        top_w = top_w * routed_scale
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def tokens_to_rows(x, tok, k):
    """Token rows [T, H] -> rows [M, H]: row r is token ``tok[r]``, zero
    where ``tok[r] == T`` (no token: a row past the rows held). A token
    appears in at most ``k`` rows."""
    return jnp.take(x, tok, axis=0, mode="fill", fill_value=0)


def _tokens_to_rows_fwd(x, tok, k):
    return tokens_to_rows(x, tok, k), (tok, x.shape[0])


def _tokens_to_rows_bwd(k, saved, g):
    tok, T = saved
    return rows_to_tokens(g, tok, T, k), None


tokens_to_rows.defvjp(_tokens_to_rows_fwd, _tokens_to_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rows_to_tokens(rows, tok, T, k):
    """Rows [M, H] -> token rows [T, H]: token t is the float32 sum of the
    rows r with ``tok[r] == t`` (at most ``k`` of them; rows with ``tok[r] ==
    T`` go nowhere), in ``rows.dtype``. The transpose of ``tokens_to_rows``
    without a scatter-add: the rows sorted by token, and one kernel pass over
    the rows that have a token (``ops/pallas/rows_to_tokens.py``)."""
    return sum_rows_by_token(rows, tok, T)


def _rows_to_tokens_fwd(rows, tok, T, k):
    return rows_to_tokens(rows, tok, T, k), tok


def _rows_to_tokens_bwd(T, k, tok, g):
    return tokens_to_rows(g, tok, k), None


rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


class DroplessMoE(nn.Module):
    """[B, S, H] -> [B, S, H] through ``num_experts`` SwiGLU experts of
    width ``d_ff``, ``k`` a token. ``balance_coeff`` / ``z_coeff`` weight
    the two auxiliary losses sown into ``losses``. ``experts_held`` > 0:
    this layer holds that many experts, the ``expert_share``-th such group
    of the ``num_experts`` the router chooses among, and returns their
    partial sum (module docstring). ``shared_d_ff`` > 0: plus one shared
    expert of that width under a sigmoid gate, in full. ``routed_scale``:
    a factor on the routed experts' weights (``route``), not on the shared
    expert. ``pin_choice``: a
    caller that recomputes this layer under a remat policy which saves the
    name ``moe_experts`` asks for it (``route``); whether a share is held
    has nothing to do with it. ``act``: the experts' non-linearity,
    ``act(gate) * up`` (``silu``: SwiGLU; ``relu``: ReGLU; ``relu2``:
    ``relu(.)^2``). ``gated=False``: an expert is ``down(act(up(x)))`` — no
    ``gate_proj`` leaf exists, here or in the shared expert.
    ``shared_gate=False``: the shared expert is added as it is (no
    ``shared_expert_gate`` leaf). ``score`` / ``choice_bias``: ``route``'s;
    the bias is the leaf ``e_score_correction_bias`` [E], zero at
    initialisation (``choice_bias_init`` says otherwise) — a BUFFER that a balancing rule outside the loss moves:
    it enters only the choice of the experts, so its gradient is exactly
    zero, and a model that carries one lists the leaf's name in its
    ``buffer_leaves``, for which the engine's ``_apply_grads`` hands the
    leaf back as it came (no gradient step, no weight decay).
    ``balance_coeff`` and ``z_coeff`` BOTH zero: the layer has no auxiliary
    loss and neither term is computed or sown (one of them zero keeps its
    term's value in ``stats``). ``router_x`` of
    ``__call__``: the tensor the router reads where it is not ``x`` (same
    shape; the experts still read ``x``)."""
    num_experts: int
    k: int
    d_ff: int
    norm_topk_prob: bool = False
    balance_coeff: float = 0.01
    z_coeff: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    experts_held: int = 0            # 0: all of them
    expert_share: int = 0
    shared_d_ff: int = 0
    pin_choice: bool = False
    routed_scale: float = 1.0
    act: str = "silu"
    gated: bool = True
    shared_gate: bool = True
    score: str = "softmax"
    choice_bias: bool = False
    choice_bias_init: Any = nn.initializers.zeros

    @nn.compact
    def __call__(self, x, router_x=None):
        B, S, H = x.shape
        E, K, F = self.num_experts, self.k, self.d_ff
        held = self.experts_held or E
        T = B * S
        init = nn.initializers.normal(0.02)
        wg = self.param("router", init, (H, E), self.param_dtype)
        w_gate = self.param("gate_proj", init, (held, H, F),
                            self.param_dtype) if self.gated else None
        w_up = self.param("up_proj", init, (held, H, F), self.param_dtype)
        w_down = self.param("down_proj", init, (held, F, H), self.param_dtype)
        bias = self.param(CHOICE_BIAS, self.choice_bias_init, (E,),
                          self.param_dtype) if self.choice_bias else None
        xt = x.reshape(T, H)
        rt = xt if router_x is None else router_x.reshape(T, H)

        with annotate("moe_router"):
            # float32 from a float32 cast of the hidden state, at full
            # precision (a TPU's default float32 matmul rounds its
            # operands to bf16): the 8th and 9th probabilities of a token
            # can tie to bf16 rounding
            logits = _router_logits(rt, wg, named=self.pin_choice)
            # (the defaults keep OLMoE's call and program; ``moe_scores``, the
            # logits' name under ``pin_choice``: ``_router_logits``)
            top_w, top_e, probs = route(
                logits, K, self.norm_topk_prob,
                **({"pin_choice": True} if self.pin_choice else {}),
                **({"routed_scale": self.routed_scale}
                   if self.routed_scale != 1.0 else {}),
                **({"score": self.score} if self.score != "softmax" else {}),
                **({"choice_bias": bias} if self.choice_bias else {}))
            chosen = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(axis=1)
            group_sizes = chosen.sum(axis=0).astype(jnp.int32)      # [E]
            # both coefficients zero: the layer has no auxiliary loss, and
            # nothing of either term is traced
            aux = bool(self.balance_coeff or self.z_coeff)
            if aux:
                balance = load_balance_loss(probs, chosen)
                z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

        dt = self.dtype
        weights = tuple(w if w is None else w.astype(dt)
                        for w in (w_gate, w_up, w_down))
        if lane_padded(F) != F:
            # an expert width no multiple of 128 divides (1,856): padded
            # ONCE a layer, here, with zero columns of gate / up and zero
            # rows of down — every ``act`` is 0 at 0, so the padded lanes
            # add nothing, exactly — and the three grouped matmuls run at
            # the padded width with nothing cut or copied between them
            more = (0, lane_padded(F) - F)
            columns, rows = ((0, 0), (0, 0), more), ((0, 0), more, (0, 0))
            weights = tuple(w if w is None else jnp.pad(w, where) for w, where
                            in zip(weights, (columns, columns, rows)))
        if held == E:
            with annotate("moe_dispatch"):
                order, inverse = sort_by_expert(top_e)
                xs = gather_rows(xt, order, inverse, K)             # [T*K, H]
            ys = self._experts(xs, weights, group_sizes)
            with annotate("moe_combine"):
                ya = unsort_rows(ys, order, inverse).reshape(T, K, H)
                y = jnp.sum(ya.astype(jnp.float32) * top_w[:, :, None],
                            axis=1)
                y = y.astype(dt).reshape(B, S, H)
        else:
            lo = held * self.expert_share
            group_sizes = group_sizes[lo:lo + held]
            rows_held = jnp.sum(group_sizes)
            with annotate("moe_dispatch"):
                local = top_e - lo
                key = jnp.where((local >= 0) & (local < held), local, held)
                order = jnp.argsort(key.reshape(-1),
                                    stable=True).astype(jnp.int32)
            cap = _HELD_ROWS_SLACK * T * K * held // E
            cap = min(T * K, -(-max(cap, 1) // 8) * 8)     # whole sublanes
            slabs = -(-T * K // cap)
            order = jnp.pad(order, (0, slabs * cap - T * K))
            operands = (xt, weights, group_sizes, order, top_w, rows_held)
            slab = functools.partial(self._held_rows, cap)
            # the first slab always; the rows held fit it unless the
            # routing sends more than _HELD_ROWS_SLACK times its share here
            y = slab(0, *operands)
            if slabs > 1:
                # ... then a second slab, recomputed in the backward pass
                # (nothing of it is kept): a router that drifts towards the
                # held experts pays one slab more and nothing for the rest
                y = jax.lax.cond(
                    cap < rows_held,
                    lambda y, *operands: y + jax.checkpoint(
                        functools.partial(slab, cap))(*operands),
                    lambda y, *operands: y, y, *operands)
            if slabs > 2:
                # ... and past twice that as many further slabs as the rows
                # fill, one at a time, the rest skipped (all T x k rows
                # included)
                def further(y, *operands):
                    # the checkpoint round the cond, the operands closed
                    # over: what the backward pass keeps of a slab is its
                    # start (a cond's own residuals would be stacked once a
                    # slab, the weights among them)
                    @jax.checkpoint
                    def one(start):
                        return jax.lax.cond(
                            start < rows_held,
                            lambda: slab(start, *operands),
                            lambda: jnp.zeros((T, H), jnp.float32))

                    return jax.lax.scan(
                        lambda y, start: (y + one(start), None), y,
                        jnp.arange(2, slabs, dtype=jnp.int32) * cap)[0]

                y = jax.lax.cond(2 * cap < rows_held, further,
                                 lambda y, *operands: y, y, *operands)
            y = y.astype(dt).reshape(B, S, H)

        if self.shared_d_ff:
            Fs = self.shared_d_ff
            s_gate = self.param("shared_gate_proj", init, (H, Fs),
                                self.param_dtype) if self.gated else None
            s_up = self.param("shared_up_proj", init, (H, Fs),
                              self.param_dtype)
            s_down = self.param("shared_down_proj", init, (Fs, H),
                                self.param_dtype)
            w_sg = self.param("shared_expert_gate", init, (H, 1),
                              self.param_dtype) if self.shared_gate else None
            with annotate("moe_shared"):
                # ``mlp_fc``: what the activation's backward pass reads
                pre = lambda w: checkpoint_name(  # noqa: E731
                    x @ w.astype(dt), "mlp_fc")
                hs = _ACTS[self.act](pre(s_gate)) * pre(s_up) \
                    if self.gated else _ACTS[self.act](pre(s_up))
                hs = hs @ s_down.astype(dt)
                if self.shared_gate:
                    open_ = jax.nn.sigmoid(
                        (x @ w_sg.astype(dt)).astype(jnp.float32))
                    hs = (open_ * hs).astype(dt)
                y = y + hs

        if aux and self.is_mutable_collection("losses"):
            self.sow("losses", "moe_balance", self.balance_coeff * balance)
            self.sow("losses", "moe_z", self.z_coeff * z)
        if self.is_mutable_collection("stats"):
            rows = group_sizes.astype(jnp.float32)
            # rows routed (here) less rows the grouped matmuls computed
            routed = T * K if held == E else rows_held
            stats = [("moe_aux_loss", balance), ("moe_z_loss", z)] \
                if aux else []
            stats += [("moe_rows_max_over_mean", jnp.max(rows) * E / (T * K)
                      if held == E else
                      jnp.max(rows) * held / jnp.maximum(jnp.sum(rows), 1.0)),
                      ("moe_dropped_rows", routed - jnp.sum(rows))]
            if held != E:
                stats += [("moe_rows_held_share", jnp.sum(rows) / (T * K)),
                          # slabs of rows this layer took (1: they fit the
                          # first)
                          ("moe_held_slabs", jnp.maximum(
                              -(-rows_held // cap), 1).astype(jnp.float32)),
                          # rows the combine's kernel read over rows held (1:
                          # none but them, up to a row tile's rounding)
                          ("moe_combine_rows_walked",
                           self._rows_walked(cap, slabs, rows_held)
                           / jnp.maximum(rows_held, 1))]
            for name, value in stats:
                self.sow("stats", name, jax.lax.stop_gradient(value))
        if self.is_mutable_collection("intermediates"):
            self.sow("intermediates", "top_e", top_e)
        return checkpoint_name(y, "mlp_proj")

    def _experts(self, xs, weights, group_sizes):
        """Rows in expert order through their experts' unit (gated, or
        ``down(act(up))``)."""
        w_gate, w_up, w_down = weights
        if self.gated:
            gate = grouped_matmul(xs, w_gate, group_sizes)
        up = grouped_matmul(xs, w_up, group_sizes)
        if self.gated:
            with annotate("moe_act"):
                h = checkpoint_name(_ACTS[self.act](gate) * up, "moe_act")
        else:
            with annotate("moe_act"):
                h = checkpoint_name(_ACTS[self.act](up), "moe_act")
        return grouped_matmul(h, w_down, group_sizes)

    @staticmethod
    def _rows_walked(cap, slabs, rows_held):
        """Rows ``rows_to_tokens`` read in the slabs a layer took (the first
        always, a further one where rows reach it)."""
        starts = jnp.arange(slabs, dtype=jnp.int32) * cap
        walked = rows_walked(jnp.clip(rows_held - starts, 0, cap))
        return jnp.sum(jnp.where(starts < jnp.maximum(rows_held, 1),
                                 walked, 0))

    def _held_rows(self, M, start, xt, weights, group_sizes, order, top_w,
                   rows_held):
        """The held experts' partial sum [T, H] float32 over the ``M``
        sorted assignments from ``start`` on: each group's rows within the
        slab; rows past the rows held belong to no token, reach no expert
        (the grouped matmul stops at the sum of its group sizes) and are
        zeroed on the way back."""
        T, K = top_w.shape
        with annotate("moe_dispatch"):
            rows = jax.lax.dynamic_slice(order, (start,), (M,))
            valid = start + jnp.arange(M, dtype=jnp.int32) < rows_held
            tok = jnp.where(valid, rows // K, T)
            ends = jnp.cumsum(group_sizes)
            group_sizes = jnp.clip(ends, start, start + M) \
                - jnp.clip(ends - group_sizes, start, start + M)
            xs = tokens_to_rows(xt, tok, K)                         # [M, H]
        ys = self._experts(xs, weights, group_sizes)
        with annotate("moe_combine"):
            w_row = jnp.take(top_w.reshape(-1), rows)
            # zeroed BEFORE the product: what the grouped matmul left
            # undefined must reach neither the sum nor w_row's cotangent
            ys = jnp.where(valid[:, None], ys.astype(jnp.float32), 0.0)
            return rows_to_tokens(ys * w_row[:, None], tok, T, K)


def _router_logits(rt, wg, named):
    """float32 logits [T, E] of the router's input ``rt`` [T, H] at full
    precision; ``named``: under the checkpoint name ``moe_scores``, so that
    a rematted block which keeps it (``runtime/remat_budget.py``) does not
    run the matmul again — named HERE, where every reader (``route``, the z
    term) takes them from, not on a copy."""
    logits = jnp.dot(rt.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return checkpoint_name(logits, "moe_scores") if named else logits


def remat_row_bytes(num_experts, shared_d_ff=0, gated=True, itemsize=2):
    """{checkpoint name: bytes a token} one ``DroplessMoE`` layer holds under
    the names a rematted block may keep (``runtime/remat_budget.py``): the
    router's float32 logits, and the shared expert's pre-activations (two
    where ``gated``). The routed experts' rows carry no name a block keeps:
    their product ``act(gate) * up`` is named ``moe_act`` (``mlp_fc`` before
    PR 61) and saves no matmul — the activation's backward pass reads
    ``gate`` and ``up`` — and those two kept for a slab of 49,152 rows cost
    SmallThinker's step more in its row gather than their grouped matmuls
    took (PERF.md Findings PR 61)."""
    return {"moe_scores": 4 * num_experts,
            "mlp_fc": (2 if gated else 1) * itemsize * shared_d_ff}


def inflight_row_bytes(hidden, d_ff, top_k, num_experts, held, shared_d_ff=0,
                       gated=True, itemsize=2):
    """Bytes a token one ``DroplessMoE`` layer's backward holds in flight
    (what ``runtime/remat_budget.reserve_bytes`` counts beside the kept
    names): the one slab of held rows — ``_HELD_ROWS_SLACK`` x the mean
    share of the ``top_k`` assignments a token, all of them where every
    expert is held — as gathered and as the experts return it (the second
    in float32 for the weighted sum), with the rows' pre-activations and
    the activation's cotangent, and the shared expert's the same."""
    per = (3 if gated else 2) * itemsize
    rows = top_k * min(1.0, _HELD_ROWS_SLACK * held / num_experts)
    return int(rows * ((itemsize + 4) * hidden + per * d_ff)) \
        + per * shared_d_ff
