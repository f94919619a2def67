"""Keye-VL-2.0-30B-A3B's language model with its learned sparse attention, in
plain float32 ``jax.numpy``: the yardstick.

Written from the published ``config.json`` of Kwai-Keye/Keye-VL-2.0-30B-A3B
(``model_type`` ``KeyeVL2``: the qwen3_moe graph with ``sa_config`` and
``mrope_section``), DeepSeek-V3.2's description of its sparse attention and
sparse-training stage, and ISSUE 65's equations; independent of
``deepspeed_tpu/``: no kernel, no scan over layers, no grouped matmul, no
remat policy of the program's. Every matmul runs under
``jax.default_matmul_precision("highest")``.

One layer on tokens t = 0..S-1 with positions P [3, S] (temporal, height,
width; on text the three rows are equal), X [S, 2048]:

    a     = RMSNorm(X; w_in, 1e-6)
    q_h   = mRoPE(RMSNorm_128(a W_q [h]; w_qn), P)       h = 0..31
    k_g   = mRoPE(RMSNorm_128(a W_k [g]; w_kn), P)       g = 0..3 ; v_g = a W_v [g]
            mRoPE: frequency pair i of 64 turns by P[r(i), t] theta^(-i/64),
            r(i) = 0 for i < 16, 1 for 16 <= i < 40, 2 for i >= 40 (sections
            [16, 24, 24]), rotate-half pairing, theta 1e7
    abar  = stop_gradient(a)
    iq_j  = RoPE_64(abar W_iq [j], P[0])                 j = 0..15
    ik    = RoPE_64(LayerNorm_64(abar W_ik; g, b), P[0])  (one key a token)
    w     = abar W_iw * 16^-1/2 * 64^-1/2
    I[t, s] = sum_j w[t, j] relu(iq_j[t] . ik[s])         s <= t, float32
    S_t   = the 2,048 keys s <= t of largest I[t, s] (all while t < 2,048; a
            tie goes to the smaller s); no gradient through the choice
    a_h[t, s] = softmax over s in S_t of q_h[t] . k_{h//8}[s] / sqrt(128)
    o_h[t] = sum_{s in S_t} a_h[t, s] v_{h//8}[s] ;  X = X + concat_h(o_h) W_o
    p[t, s] = stop_gradient(mean_h a_h[t, s])
    kl[t]  = sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t, .])[s])
    b     = RMSNorm(X; w_post, 1e-6) ; the expert layer of ``reference/sdar``
            (softmax router over 128, top-8 renormalised, the experts HELD)

    loss  = mean over t of -log p(token t+1) + dsa_kl_weight * mean over
            layers and t of kl[t]

Departures from the published model, each the configuration file's
``assumed`` or ``reduced``: QK-norm a head; the indexer reading the block's
normed input; LayerNorm with bias (eps 1e-6) on its key and the two factors
on its weight; its rotary (1-D rotate-half over all 64 dims, the temporal
row, the same theta); the tie rule; the KL and its weight; the experts held
and the vocabulary's slice (``reference/sdar.py``).

For MEMORY only (same arithmetic): the index scores in blocks of query rows
(the 16 heads' [rows, S] products exist a block at a time), attention one KV
head's group at a time in blocks of query rows against all keys under the
mask, each recomputed in the backward pass; the mean probabilities p and the
selection in blocks of query rows too, so [32, S, S] never exists.

``selection`` pins the kept set to one chosen elsewhere (bool [B, S, S],
[query, key]), as ``experts`` pins the router's choice: a bf16 program and
this float32 model rank the scores next to the 2,048th differently, a top-k
is not continuous, and near-uniform attention over the kept keys moves by
~sqrt(2 s) of its length where a share s of them differs — so to compare
ARITHMETIC (the branches, the loss, the backward pass) the caller hands over
the set its program chose, and compares the CHOICE apart (``index_scores`` +
``select`` on one and the same input).

``control`` names one deliberate fault: "topk_less_one" (2,047 keys kept),
"relu_left_out",
"head_weight_dropped" (the indexer's first head's weight read as 0),
"kl_over_all_causal" (the KL and both softmaxes of it over every causal key),
"stop_gradient_left_out" (the indexer reads the input attached: the KL's
gradient reaches the trunk), "mrope_one_row" (every pair turned by the
temporal row — visible only where the rows differ).

Weights (float32): top = {"embed", "norm", "lm_head"}; a layer has
"input_norm", "post_attn_norm" [H], "q" [H, n_head D], "k", "v" [H, n_kv D],
"o" [n_head D, H], "q_norm", "k_norm" [D], "index_q" [H, J Di], "index_k"
[H, Di], "index_k_norm", "index_k_bias" [Di], "index_w" [H, J], "router"
[H, E], "gate", "up" [held, H, F], "down" [held, F, H].
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.olmoe import (grad_norm,  # noqa: F401
                                       head_nll_sum, rms_norm as norm,
                                       rotate_half)
from benchmark.reference.sdar import moe

F32 = jnp.float32
# (a kept set by ``lax.approx_max_k`` was a control until the chip read it
# EXACTLY as the honest reference: at k = 2,048 of 16,384 it keeps every bin)
CONTROLS = ("topk_less_one", "relu_left_out", "head_weight_dropped",
            "kl_over_all_causal", "stop_gradient_left_out")


def turn(x, ang):
    """x [.., S, D] rotated by ``ang`` [S, D / 2], rotate-half pairing."""
    ang = jnp.concatenate([ang, ang], axis=-1)
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def rope_angles(positions, D, theta):
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    return positions.astype(F32)[:, None] * inv[None, :]


def mrope_angles(positions, D, theta, sections, control=None):
    """[S, D / 2]: pair i by row r(i) of ``positions`` [3, S]."""
    rows = [rope_angles(positions[0 if control == "mrope_one_row" else r],
                        D, theta) for r in range(3)]
    cuts = [0, sections[0], sections[0] + sections[1], D // 2]
    return jnp.concatenate([rows[r][:, cuts[r]:cuts[r + 1]]
                            for r in range(3)], axis=1)


def layer_norm(x, w, b, eps=1e-6):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w + b


def index_scores(a, p, positions, *, index_heads, index_head_dim, theta,
                 control=None, q_block=512):
    """I [B, S, S] float32 ([query, key]; every pair, causal or not)."""
    B, S, _ = a.shape
    J, Di = index_heads, index_head_dim
    ang = rope_angles(positions[0], Di, theta)
    iq = turn((a @ p["index_q"]).reshape(B, S, J, Di).transpose(0, 2, 1, 3),
              ang)
    ik = turn(layer_norm(a @ p["index_k"], p["index_k_norm"],
                         p["index_k_bias"]), ang)
    w = (a @ p["index_w"]) * (J ** -0.5 * Di ** -0.5)
    if control == "head_weight_dropped":
        w = w.at[..., 0].set(0.0)
    step = min(q_block, S)

    @jax.checkpoint
    def rows(iq_blk, w_blk):
        s = jnp.einsum("bjrd,bsd->bjrs", iq_blk, ik)
        if control != "relu_left_out":
            s = jax.nn.relu(s)
        return jnp.einsum("bjrs,brj->brs", s, w_blk)

    pad = (-S) % step
    iq, w = (jnp.pad(iq, ((0, 0), (0, 0), (0, pad), (0, 0))),
             jnp.pad(w, ((0, 0), (0, pad), (0, 0))))
    n = (S + pad) // step
    out = jax.lax.map(lambda xs: rows(*xs), (
        iq.reshape(B, J, n, step, Di).transpose(2, 0, 1, 3, 4),
        w.reshape(B, n, step, J).transpose(1, 0, 2, 3)))
    return out.transpose(1, 0, 2, 3).reshape(B, n * step, S)[:, :S]


def select(scores, topk, control=None, q_block=512):
    """bool [B, S, S]: the ``topk`` causal keys of largest score a query, a
    block of query rows at a time."""
    B, S, _ = scores.shape
    if control == "topk_less_one":
        topk = topk - 1
    step = min(q_block, S)
    pad = (-S) % step
    n = (S + pad) // step

    def rows(xs):
        blk, t0 = xs                                   # [B, step, S]
        t = t0 + jnp.arange(step)[:, None]
        causal = jnp.arange(S)[None, :] <= t
        if topk >= S:
            return jnp.broadcast_to(causal, blk.shape)
        masked = jnp.where(causal, blk, -jnp.inf)
        # the k-th largest, the keys above it, and of the keys that tie with
        # it the first ones
        kth = jax.lax.top_k(masked, topk)[0][..., -1:]
        above = masked > kth
        ties = (masked == kth) & causal
        need = topk - jnp.sum(above, axis=-1, keepdims=True)
        chosen = above | (ties & (jnp.cumsum(ties, axis=-1) <= need))
        return jnp.where(t < topk, causal, chosen)

    blocks = jnp.pad(scores, ((0, 0), (0, pad), (0, 0))) \
        .reshape(B, n, step, S).transpose(1, 0, 2, 3)
    out = jax.lax.map(rows, (blocks, jnp.arange(n) * step))
    return out.transpose(1, 0, 2, 3).reshape(B, n * step, S)[:, :S]


def mean_probabilities(q, k, mask, q_block=128):
    """[B, S, S] float32: the heads' mean attention probability a pair under
    ``mask`` (zero off it), a block of query rows at a time; no gradient."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    B, n_head, S, D = q.shape
    k = jnp.repeat(k, n_head // k.shape[1], axis=1)
    step = min(q_block, S)
    pad = (-S) % step
    n = (S + pad) // step
    # a padded query row sees key 0: its row is dropped
    seen = jnp.pad(mask, ((0, 0), (0, pad), (0, 0))).at[:, S:, 0].set(True)

    def rows(xs):
        q_blk, seen_blk = xs               # [B, n_head, step, D], [B, step, S]
        scores = jnp.einsum("bhrd,bhsd->bhrs", q_blk, k) / math.sqrt(D)
        probs = jax.nn.softmax(
            jnp.where(seen_blk[:, None], scores, -jnp.inf), axis=-1)
        return jnp.mean(jnp.where(seen_blk[:, None], probs, 0.0), axis=1)

    out = jax.lax.map(rows, (
        jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        .reshape(B, n_head, n, step, D).transpose(2, 0, 1, 3, 4),
        seen.reshape(B, n, step, S).transpose(1, 0, 2, 3)))
    return out.transpose(1, 0, 2, 3).reshape(B, n * step, S)[:, :S]


def attention(a, p, positions, mask, *, n_kv_head, head_dim, eps, theta,
              mrope_section, control=None, q_block=512):
    """(the attention branch [B, S, H], ``mean_probabilities`` of it) under
    ``mask`` bool [B, S, S] ([query, key])."""
    B, S, _ = a.shape
    D = head_dim
    n_head = p["q"].shape[1] // D
    group = n_head // n_kv_head
    ang = mrope_angles(positions, D, theta, mrope_section, control)

    def heads(t, w):
        t = t.reshape(B, S, -1, D)
        if w is not None:
            t = norm(t, w, eps)
        return t.transpose(0, 2, 1, 3)

    q = turn(heads(a @ p["q"], p["q_norm"]), ang)
    k = turn(heads(a @ p["k"], p["k_norm"]), ang)
    v = heads(a @ p["v"], None)
    step = min(q_block, S)
    pad = (-S) % step
    n = (S + pad) // step

    @jax.checkpoint
    def rows(q_blk, k_g, v_g, seen):
        # q_blk [B, group, step, D] against one KV head's k_g, v_g [B, S, D]
        scores = jnp.einsum("bhrd,bsd->bhrs", q_blk, k_g) / math.sqrt(D)
        probs = jax.nn.softmax(
            jnp.where(seen[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhrs,bsd->bhrd", probs, v_g)

    # a padded query row sees key 0: its output is dropped
    seen = jnp.pad(mask, ((0, 0), (0, pad), (0, 0)))
    seen = seen.at[:, S:, 0].set(True).reshape(B, n, step, S) \
        .transpose(1, 0, 2, 3)

    def one_group(qkv):
        q_g, k_g, v_g = qkv
        q_g = jnp.pad(q_g, ((0, 0), (0, 0), (0, pad), (0, 0)))
        blocks = q_g.reshape(B, group, n, step, D).transpose(2, 0, 1, 3, 4)
        ctx = jax.lax.map(lambda xs: rows(xs[0], k_g, v_g, xs[1]),
                          (blocks, seen))
        return ctx.transpose(1, 2, 0, 3, 4).reshape(B, group, n * step, D)

    ctx = jax.lax.map(one_group, (
        q.reshape(B, n_kv_head, group, S, D).transpose(1, 0, 2, 3, 4),
        k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3)))
    ctx = ctx.transpose(1, 0, 2, 3, 4).reshape(B, n_head, n * step, D)[
        :, :, :S]
    out = ctx.transpose(0, 2, 1, 3).reshape(B, S, n_head * D) @ p["o"]
    return out, mean_probabilities(q, k, mask)


def index_kl(scores, p_mean, mask):
    """kl [B, S]: KL(p_mean || softmax of ``scores`` over ``mask``) a row."""
    logq = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    live = mask & (p_mean > 0)
    return jnp.sum(jnp.where(
        live, p_mean * (jnp.log(jnp.where(live, p_mean, 1.0))
                        - jnp.where(live, logq, 0.0)), 0.0), axis=-1)


def layer(x, p, experts=None, selection=None, positions=None, *, n_kv_head,
          head_dim, k, eps, theta, mrope_section, index_heads,
          index_head_dim, topk, expert_lo=0, control=None):
    """(the stream after one layer, the layer's mean KL, {"top_e",
    "attn_out", "ffn_out", "selection", "scores"}) of the stream ``x``
    [B, S, H]; ``experts`` pins the router's choice, ``selection`` the
    kept set."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (3, S))
    a = norm(x, p["input_norm"], eps)
    abar = a if control == "stop_gradient_left_out" \
        else jax.lax.stop_gradient(a)
    scores = index_scores(abar, p, positions, index_heads=index_heads,
                          index_head_dim=index_head_dim, theta=theta,
                          control=control)
    mask = select(jax.lax.stop_gradient(scores), topk, control) \
        if selection is None else selection
    attn, p_mean = attention(a, p, positions, mask, n_kv_head=n_kv_head,
                             head_dim=head_dim, eps=eps, theta=theta,
                             mrope_section=mrope_section, control=control)
    if control == "kl_over_all_causal":
        causal = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)),
                                  mask.shape)
        _, p_mean = attention(a, p, positions, causal, n_kv_head=n_kv_head,
                              head_dim=head_dim, eps=eps, theta=theta,
                              mrope_section=mrope_section)
        kl = index_kl(scores, p_mean, causal)
    else:
        kl = index_kl(scores, p_mean, mask)
    x = x + attn
    h = norm(x, p["post_attn_norm"], eps).reshape(-1, x.shape[-1])
    out, top_e = moe(h, p, k, expert_lo, experts)
    out = out.reshape(x.shape)
    return x + out, jnp.mean(kl), {
        "top_e": top_e, "attn_out": attn, "ffn_out": out, "selection": mask,
        "scores": scores}


def head_loss(x, top, ids, *, eps):
    """Next-token cross-entropy from the final stream ``x`` [B, S, H]."""
    B, S = ids.shape
    return head_nll_sum(x, top["norm"], top["lm_head"], ids, eps) \
        / (B * (S - 1))


def forward(top, layers, ids, positions=None, *, experts=None,
            selections=None, kl_weight=1.0, control=None, **sizes):
    """(loss, detail): detail holds the cross-entropy, the indexer's loss
    L_I (the mean over layers of the layers' mean KL) and per layer the
    chosen experts, the kept set and the two branches' outputs."""
    x = top["embed"][ids]
    per_layer, kls = [], []
    for i, p in enumerate(layers):
        x, kl, detail = layer(
            x, p, None if experts is None else experts[i],
            None if selections is None else selections[i], positions,
            control=control, **sizes)
        del detail["scores"]
        per_layer.append(detail)
        kls.append(kl)
    ce = head_loss(x, top, ids, eps=sizes["eps"])
    l_i = sum(kls) / len(kls)
    return ce + kl_weight * l_i, {"ce": ce, "index_kl": l_i,
                                  "layers": per_layer}


def loss(weights, ids, positions=None, view=lambda w: w, **sizes):
    """(loss, detail) of ``forward`` at full matmul precision; ``view`` turns
    the caller's ``weights`` into ``(top, layers)``."""
    with jax.default_matmul_precision("highest"):
        return forward(*view(weights), ids, positions, **sizes)


def loss_and_grads(weights, ids, positions=None, view=lambda w: w, **sizes):
    """((loss, detail), gradients shaped like ``weights``) in one piece: the
    tests' sizes. At the cell's size a caller walks the same gradient a
    layer at a time with ``layer`` and ``head_loss`` under ``jax.vjp``
    (``families/keye_vl2.py``)."""
    return jax.value_and_grad(
        lambda w: loss(w, ids, positions, view, **sizes), has_aux=True)(
            weights)
