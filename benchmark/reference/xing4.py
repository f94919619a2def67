"""Xing4.0-29B-A4B (``model_type: xing4_0``) as published, in plain float32
``jax.numpy``: the yardstick.

Written from the published ``config.json`` of XingChen-AGI/Xing4.0-29B-A4B,
the papers it names its mechanisms after — DeepSeek-V2 / V3 (arXiv
2405.04434 section 2.1, 2412.19437 sections 2.1-2.2: latent attention with
compressed queries, the sigmoid router with a selection bias, multi-token
prediction eq. 21-25), YaRN (arXiv 2309.00071, in DeepSeek's form) and
manifold-constrained hyper-connections (arXiv 2512.24880) — and ISSUE 56's
equations; independent of ``deepspeed_tpu/``: no kernel, no remat, no chunked
head of the program's, no sort, no grouped matmul, no sharding. Every matmul
runs under ``jax.default_matmul_precision("highest")``.

    X in R^{n x C} a token: the embedding row copied into n streams.
    every layer, round EACH of its two branches F (attention with its input
    norm; FFN with its norm):
        v = flatten(X);  v' = v / sqrt(mean(v^2) + hc_eps)     (no weight)
        Ht_pre = a_pre (v' phi_pre) + b_pre         [n]
        Ht_post = a_post (v' phi_post) + b_post     [n]
        Ht_res = a_res mat(v' phi_res) + b_res      [n, n], row-major
        H_pre = sigmoid(Ht_pre);  H_post = 2 sigmoid(Ht_post)
        M = exp(clip(Ht_res, clamp_min, clamp_max)); ``iters`` rounds (a
            Python loop) of: every column over its sum, then every row over
            its sum; H_res = M after the last
        u = sum_j H_pre[j] X[j];  y = F(u)
        X_new[i] = sum_j H_res[i, j] X[j] + H_post[i] y
    after the last layer h = sum_i X[i]; logits = norm(h) W_head^T.
    norm:  x / sqrt(mean(x^2) + eps) * w
    Attn: c_q = norm(u W_qa) (q_lora_rank numbers), q = c_q W_qb, a head's
          [q_nope ; q_rope]. [c ; k_r] = u W_kva; c <- norm(c); [k_nope_h ;
          v_h] = c W_kvb. RoPE at positions 0..S-1 on q_rope of every head
          and on k_r (ONE vector a token), the pair (2i, 2i+1) turned IN
          PLACE by pos x f_i, f the YaRN blend of theta^(-2i/d) and that over
          ``factor`` (``benchmark/reference/laguna.yarn_inv_freq``: the
          linear ramp between the dimensions that beta_fast and beta_slow
          rotations in the original context pick), cos and sin times
          yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim);
          scores q . k x (nope + rope_dim)^-0.5 x yarn_mscale(factor,
          mscale_all_dim)^2, yarn_mscale(f, m) = 0.1 m ln f + 1; key j
          visible to query i iff j <= i; softmax; o_h = P_h v_h; concat W_o.
    FFN: ``benchmark/reference/deepseek_v3.experts`` (sigmoid scores over
          ALL E experts, the k largest of score + bias, weights the scores
          renormalised x routed_scale, the partial sum of the experts HELD,
          the shared expert ungated) or, in a leading layer, the dense SwiGLU.
    MTP (depth 1): h'_i = [norm_h(h_i) ; norm_e(E[t_{i+1}])] M, h_i the
          summed streams BEFORE the final norm; h' copied into n streams, one
          more expert layer, the streams summed, its own head norm, the SAME
          W_head. loss = CE(next token) + lambda CE(head_mtp_i, t_{i+2}),
          each a mean over the positions that have the target.

Departures from the published description, each the configuration file's
``assumed`` or ``reduced``: the rotated pairs are turned where they lie (HF
permutes to the half-split layout first: a relabelling, ``reference/
deepseek_v3.py``); the stream mixer's norm carries no weight, the embedding
is COPIED into the streams and they are SUMMED at the end (the papers' ends;
the config has no key for either); the order of ``eh_proj``'s two halves
(h first) and the MTP loss weight; a share of the experts and of the
vocabulary held; the shared expert one SwiGLU.

For MEMORY only (same arithmetic): attention ``head_group`` heads at a time
and in blocks of query rows, each recomputed in the backward pass; the
experts in a scan; the head in chunks of tokens; and ``pinned_backward``
walks the gradient a BRANCH at a time from the heads down, so that one
branch's activations and one layer's gradients are alive at once (the
engine's state leaves no room for a float32 gradient tree).

Weights (float32): top = {"embed" [V, C], "norm" [C], "lm_head" [V, C]}; a
layer = ``reference/deepseek_v3.py``'s with "q_a" [C, Q], "q_a_norm" [Q],
"q_b" [Q, heads (nope + rope_dim)] in place of "q", and "attn_hc", "ffn_hc":
{"phi" [n C, 2n + n^2], "bias" [2n + n^2], "gate" [3]}; mtp = {"hnorm",
"enorm", "norm" [C], "eh_proj" [2 C, C], "layer": an expert layer} or None.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import experts
from benchmark.reference.laguna import dense_mlp, norm, yarn_inv_freq
from benchmark.reference.olmoe import grad_norm  # noqa: F401

F32 = jnp.float32


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_in_place(x, inv_freq, table_scale=1.0):
    """x [..., S, d]: the pair (2i, 2i+1) of the last axis turned by ``pos x
    inv_freq[i]`` at positions 0..S-1, cos and sin times ``table_scale``;
    the columns stay where they are."""
    S = x.shape[-2]
    ang = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.repeat(f(ang) * table_scale, 2, axis=-1)
                for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    partner = jnp.stack([-odd, even], axis=-1).reshape(x.shape)
    return x * cos + partner * sin


def attention(x, p, *, n_head, nope, rope_dim, v_dim, theta, yarn, eps,
              query_norm=True, yarn_score_scale=True, yarn_blend=True,
              q_block=256, head_group=8):
    """The latent-attention branch with compressed queries under YaRN
    (``yarn``: the published ``rope_scaling`` dict). ``query_norm``,
    ``yarn_score_scale`` (the softmax scale's mscale^2) and ``yarn_blend``
    (False: plain frequencies) exist so that the tests can show each
    omission failing the check."""
    B, S, _ = x.shape
    H, R = n_head, p["kv_a_norm"].shape[0]
    factor = float(yarn["factor"])
    inv = yarn_inv_freq(rope_dim, theta, factor if yarn_blend else 1.0,
                        yarn["original_max_position_embeddings"],
                        float(yarn["beta_fast"]), float(yarn["beta_slow"]))
    all_dim = yarn_mscale(factor, yarn["mscale_all_dim"])
    table = yarn_mscale(factor, yarn["mscale"]) / all_dim
    scale = float(nope + rope_dim) ** -0.5 \
        * (all_dim ** 2 if yarn_score_scale else 1.0)
    c_q = x @ p["q_a"]
    if query_norm:
        c_q = norm(c_q, p["q_a_norm"], eps)
    down = x @ p["kv_a"]
    c, k_r = norm(down[..., :R], p["kv_a_norm"], eps), down[..., R:]
    k_r = rope_in_place(k_r, inv, table)                # [B, S, r]: ONE
    step, G = min(q_block, S), min(head_group, H)

    # for memory only: ``head_group`` heads at a time and blocks of query
    # rows against ALL keys, each recomputed in the backward pass
    @jax.checkpoint
    def group(c_q, c, k_r, w_qb, w_kvb):
        q = jnp.einsum("bsq,qgd->bgsd", c_q, w_qb)
        kv = jnp.einsum("bsr,rgd->bgsd", c, w_kvb)
        q = jnp.concatenate(
            [q[..., :nope], rope_in_place(q[..., nope:], inv, table)], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r[:, None], (B, G, S, rope_dim))], -1)
        v = kv[..., nope:]

        @jax.checkpoint
        def rows(q_blk, start):
            scores = q_blk @ k.transpose(0, 1, 3, 2) * scale
            behind = (start + jnp.arange(q_blk.shape[2]))[:, None] \
                - jnp.arange(S)[None, :]
            return jax.nn.softmax(jnp.where(behind >= 0, scores, -jnp.inf),
                                  axis=-1) @ v

        blocks = q.reshape(B, G, S // step, step, -1).transpose(2, 0, 1, 3, 4)
        ctx = jax.lax.map(lambda xs: rows(*xs),
                          (blocks, jnp.arange(0, S, step)))
        return ctx.transpose(1, 2, 0, 3, 4).reshape(B, G, S, v_dim)

    def by_group(w, width):
        return w.reshape(w.shape[0], H // G, G, width).transpose(1, 0, 2, 3)

    ctx = jax.lax.map(lambda xs: group(c_q, c, k_r, *xs),
                      (by_group(p["q_b"], nope + rope_dim),
                       by_group(p["kv_b"], nope + v_dim)))
    return ctx.transpose(1, 3, 0, 2, 4).reshape(B, S, H * v_dim) @ p["o"]


def stream_coefficients(X, p, *, hc_eps, iters, clamp, post_scale=2.0,
                        zero_dynamic=None, use_exp=True):
    """(H_pre [B, S, n], H_post [B, S, n], H_res [B, S, n, n]) of the stream
    ``X`` [B, S, n, C] and one branch's mixer leaves. ``iters``, ``clamp``
    (None: no clip), ``post_scale``, ``zero_dynamic`` ("pre" | "post" |
    "res": that term's token-dependent part zeroed) and ``use_exp`` exist so
    that the tests can show each omission failing the check."""
    B, S, n, C = X.shape
    v = X.reshape(B, S, n * C)
    v = v / jnp.sqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + hc_eps)
    proj = v @ p["phi"]
    parts = {"pre": (0, n), "post": (n, 2 * n), "res": (2 * n, 2 * n + n * n)}
    ht = {}
    for g, (name, (lo, hi)) in enumerate(parts.items()):
        dyn = proj[..., lo:hi] * (0.0 if zero_dynamic == name else 1.0)
        ht[name] = p["gate"][g] * dyn + p["bias"][lo:hi]
    h_pre = jax.nn.sigmoid(ht["pre"])
    h_post = post_scale * jax.nn.sigmoid(ht["post"])
    m = ht["res"].reshape(B, S, n, n)
    if clamp is not None:
        m = jnp.clip(m, *clamp)
    if use_exp:
        m = jnp.exp(m)
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-2, keepdims=True)       # every column
        m = m / jnp.sum(m, axis=-1, keepdims=True)       # then every row
    return h_pre, h_post, m


def branch(X, hp, f, **mix):
    """(X_new, y, the three coefficient sets) of one branch ``f`` round the
    stream ``X`` [B, S, n, C]."""
    h_pre, h_post, h_res = stream_coefficients(X, hp, **mix)
    y = f(jnp.einsum("bsj,bsjc->bsc", h_pre, X))
    new = jnp.einsum("bsij,bsjc->bsic", h_res, X) \
        + h_post[..., None] * y[:, :, None, :]
    return new, y, (h_pre, h_post, h_res)


def spread(x, n):
    return jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], n, x.shape[-1]))


def _branches(p, chosen, *, n_head, nope, rope_dim, v_dim, theta, yarn, eps,
              k, expert_lo, routed_scale, norm_topk_prob, attn_over,
              experts_over):
    """One layer's two branch functions of (u, layer's weights): attention
    -> y; FFN -> (y, experts used, the router's own choice)."""
    def attn(u, p):
        return attention(norm(u, p["input_norm"], eps), p, n_head=n_head,
                         nope=nope, rope_dim=rope_dim, v_dim=v_dim,
                         theta=theta, yarn=yarn, eps=eps, **(attn_over or {}))

    def ffn(u, p):
        h = norm(u, p["post_attn_norm"], eps).reshape(-1, u.shape[-1])
        if "mlp_gate" in p:
            return dense_mlp(h, p).reshape(u.shape), None, None
        out, top_e, own_e = experts(
            h, p, k, expert_lo, routed_scale=routed_scale,
            norm_topk_prob=norm_topk_prob, chosen=chosen,
            **(experts_over or {}))
        return out.reshape(u.shape), top_e, own_e

    return jax.checkpoint(attn), ffn


def layer(X, p, chosen, mix, sizes):
    """(X after the layer, {"x_mid", "x_out", "mixer_out", "ffn_out",
    "attn_hc", "ffn_hc" (the coefficient sets), "top_e", "own_top_e"})."""
    attn, ffn = _branches(p, chosen, **sizes)
    picked = {}

    def ffn_y(u):
        y, picked["top_e"], picked["own_top_e"] = ffn(u, p)
        return y

    X, mixed, c_attn = branch(X, p["attn_hc"], lambda u: attn(u, p), **mix)
    x_mid = X
    X, out, c_ffn = branch(X, p["ffn_hc"], ffn_y, **mix)
    return X, dict(picked, x_mid=x_mid, x_out=X, mixer_out=mixed,
                   ffn_out=out, attn_hc=c_attn, ffn_hc=c_ffn)


def head_nll_mean(h, norm_w, lm_head, ids, eps, offset=1, chunk=2048):
    """Mean over the positions that have one of -log p(token i + offset)
    from position i; chunks of tokens one after the other, each recomputed
    in the backward pass: the [tokens, vocabulary] logits never exist."""
    B, S, C = h.shape
    xs = norm(h[:, :-offset], norm_w, eps).reshape(-1, C)
    tgt = ids[:, offset:].reshape(-1)
    count = tgt.shape[0]
    pad = (-count) % chunk
    xs = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, chunk, C)
    live = jnp.pad(jnp.ones_like(tgt, F32), (0, pad)).reshape(-1, chunk)
    tgt = jnp.pad(tgt, (0, pad)).reshape(-1, chunk)

    @jax.checkpoint
    def part(xc, tc, mc):
        logp = jax.nn.log_softmax(xc @ lm_head.T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[:, None], -1)[:, 0] * mc)

    total, _ = jax.lax.scan(lambda acc, c: (acc + part(*c), None),
                            jnp.zeros((), F32), (xs, tgt, live))
    return total / count


def mtp_input(h, embed, mtp, ids, eps, h_first=True):
    """h'_i = [norm_h(h_i) ; norm_e(E[t_{i+1}])] M (``h_first`` False: the
    halves the other way round, a test's omission). Position S - 1 has no
    next token: it reads token 0 and lies behind every scored position."""
    e = embed[jnp.roll(ids, -1, axis=1)]
    halves = [norm(h, mtp["hnorm"], eps), norm(e, mtp["enorm"], eps)]
    return jnp.concatenate(halves if h_first else halves[::-1], -1) \
        @ mtp["eh_proj"]


def _split(sizes):
    """(the branches' sizes, the stream mixers', the rest) of ``forward``'s
    keywords."""
    sizes = dict(sizes)
    mix = dict(hc_eps=sizes.pop("hc_eps"), iters=sizes.pop("iters"),
               clamp=sizes.pop("clamp"), **(sizes.pop("mix_over", None) or {}))
    rest = {k: sizes.pop(k) for k in ("n", "mtp_weight", "mtp_offset",
                                      "mtp_h_first") if k in sizes}
    sizes.setdefault("attn_over", None)
    sizes.setdefault("experts_over", None)
    return sizes, mix, rest


def forward(top, layers, mtp, ids, *, chosen=None, mtp_chosen=None, **sizes):
    """(loss, detail): detail holds both cross-entropies, per layer
    ``layer``'s row and the prediction module's under "mtp". ``chosen`` (per
    layer [T, k] or None) / ``mtp_chosen`` pin the experts a token is sent
    to (``benchmark/reference/olmoe.forward`` says why). ``mix_over``,
    ``attn_over``, ``experts_over``, ``mtp_offset`` and ``mtp_h_first``
    among ``sizes`` are the tests' omissions."""
    sizes, mix, rest = _split(sizes)
    n, eps = rest["n"], sizes["eps"]
    X = spread(top["embed"][ids], n)
    rows = []
    for i, p in enumerate(layers):
        X, row = layer(X, p, None if chosen is None else chosen[i], mix, sizes)
        rows.append(row)
    h = jnp.sum(X, axis=2)
    ce = head_nll_mean(h, top["norm"], top["lm_head"], ids, eps)
    detail = {"ce": ce, "layers": rows, "trunk": h}
    if mtp is None:
        return ce, detail
    joined = mtp_input(h, top["embed"], mtp, ids, eps,
                       rest.get("mtp_h_first", True))
    Xm, row = layer(spread(joined, n), mtp["layer"], mtp_chosen, mix, sizes)
    mtp_ce = head_nll_mean(jnp.sum(Xm, axis=2), mtp["norm"], top["lm_head"],
                           ids, eps, offset=rest.get("mtp_offset", 2))
    return ce + rest["mtp_weight"] * mtp_ce, dict(
        detail, mtp_ce=mtp_ce, mtp=dict(row, joined=joined))


def loss(weights, ids, view=lambda w: w, **sizes):
    """(loss, detail) of ``forward`` at full matmul precision; ``view``
    turns the caller's ``weights`` into ``(top, layers, mtp)``."""
    with jax.default_matmul_precision("highest"):
        return forward(*view(weights), ids, **sizes)


def loss_and_grads(weights, ids, view=lambda w: w, **sizes):
    """((loss, detail), gradients shaped like ``weights``) by ``jax.grad``."""
    return jax.value_and_grad(
        lambda w: loss(w, ids, view, **sizes), has_aux=True)(weights)


def pinned_backward(top, layers, mtp, ids, other, fold, **sizes):
    """The gradients of the loss with every branch started from the stream
    of ANOTHER run of the same weights and batch — ``other``: {"layers": per
    layer {"x_mid", "x_out" [B, S, n, C], "top_e"}, "mtp": the prediction
    layer's} — its values, this model's derivatives, walked from the two
    heads down a branch at a time: a layer's attention branch starts from
    the layer below's ``x_out`` (the embedding's copy for layer 0), its FFN
    branch from the run's ``x_mid`` with the run's experts, the head from
    the sum of the last ``x_out``; the prediction module joins that sum with
    the next token's embedding itself, and its layer is pinned the same way.
    ``fold(where, gradients, row)`` — ``where`` a layer's index, "mtp" or
    "top"; ``row`` {"mixer_out", "ffn_out", "attn_hc", "ffn_hc",
    "own_top_e"} at the pinned streams (None for "top") — is handed each
    part's gradients as soon as they are whole and what it returns is kept
    in their place. Returns ((main, MTP) cross-entropies at the pinned
    streams, {where: what ``fold`` returned})."""
    sizes, mix, rest = _split(sizes)
    n, eps = rest["n"], sizes["eps"]
    weight, folded = rest.get("mtp_weight", 0.0), {}

    def walk_layer(p, x_in, row, c):
        """A layer's gradients and the cotangent of its input stream from
        the cotangent ``c`` of its output, FFN branch first."""
        attn, ffn = _branches(p, row["top_e"], **sizes)

        def ffn_branch(X, p):
            got = {}

            def f(u):
                y, _, got["own_top_e"] = ffn(u, p)
                return y
            new, y, coeff = branch(X, p["ffn_hc"], f, **mix)
            return new, (y, coeff, got["own_top_e"])

        def attn_branch(X, p):
            new, y, coeff = branch(X, p["attn_hc"], lambda u: attn(u, p),
                                   **mix)
            return new, (y, coeff)

        # for memory only: each branch's forward pass waits for the
        # cotangent its backward pass needs
        row, c = jax.lax.optimization_barrier((row, c))
        _, back, (out, c_ffn, own_e) = jax.vjp(
            ffn_branch, row["x_mid"].astype(F32), p, has_aux=True)
        dx, g_ffn = back(c)
        x_in, c = jax.lax.optimization_barrier((x_in, dx))
        _, back, (mixed, c_attn) = jax.vjp(attn_branch, x_in.astype(F32), p,
                                           has_aux=True)
        c, g_attn = back(c)
        # each branch's gradient of the other's leaves is zero
        return jax.tree_util.tree_map(jnp.add, g_ffn, g_attn), c, dict(
            mixer_out=mixed, ffn_out=out, attn_hc=c_attn, ffn_hc=c_ffn,
            own_top_e=own_e)

    with jax.default_matmul_precision("highest"):
        rows = other["layers"]
        trunk = jnp.sum(rows[-1]["x_out"].astype(F32), axis=2)
        g_top = {"embed": jnp.zeros_like(top["embed"])}
        c_trunk, mtp_ce = 0.0, jnp.zeros((), F32)
        if mtp is not None:
            small = {k: v for k, v in mtp.items() if k != "layer"}
            row = other["mtp"]
            mtp_ce, back = jax.vjp(
                lambda w, nw, h: head_nll_mean(
                    h, nw, w, ids, eps, offset=rest.get("mtp_offset", 2)),
                top["lm_head"], mtp["norm"],
                jnp.sum(row["x_out"].astype(F32), axis=2))
            g_head, g_norm, c = back(jnp.asarray(weight, F32))
            joined, back_join = jax.vjp(
                lambda e, m, h: mtp_input(h, e, dict(small, **m), ids, eps,
                                          rest.get("mtp_h_first", True)),
                top["embed"], {k: small[k] for k in ("hnorm", "enorm",
                                                     "eh_proj")}, trunk)
            g_layer, c, at = walk_layer(mtp["layer"], spread(joined, n), row,
                                        spread(c, n))
            g_embed, g_small, c_trunk = back_join(jnp.sum(c, axis=2))
            g_top = {"embed": g_embed, "lm_head": g_head}
            c_trunk, folded["mtp"] = jax.lax.optimization_barrier(
                (c_trunk, fold("mtp", dict(g_small, norm=g_norm,
                                           layer=g_layer), at)))
        ce, back = jax.vjp(
            lambda w, nw, h: head_nll_mean(h, nw, w, ids, eps),
            top["lm_head"], top["norm"], trunk)
        g_head, g_top["norm"], c = back(jnp.ones((), F32))
        g_top["lm_head"] = g_top.get("lm_head", 0.0) + g_head
        c = spread(c + c_trunk, n)
        start = spread(top["embed"][ids], n)
        for i in reversed(range(len(layers))):
            grads, c, at = walk_layer(
                layers[i], rows[i - 1]["x_out"] if i else start, rows[i], c)
            c, folded[i] = jax.lax.optimization_barrier(
                (c, fold(i, grads, at)))
        g_top["embed"] = g_top["embed"].at[ids].add(jnp.sum(c, axis=2))
        folded["top"] = fold("top", g_top, None)
        return (ce, mtp_ce), folded
