"""OLMoE as published, in plain float32 ``jax.numpy``: the yardstick.

Written from the published description of allenai/OLMoE-1B-7B (arXiv
2409.02060 and the model's ``config.json``) and independent of
``deepspeed_tpu/models/llama.py`` and ``deepspeed_tpu/moe``: no kernel, no
sort, no grouped matmul, no remat policy of the program's, no sharding.
Every matmul runs under ``jax.default_matmul_precision("highest")``.

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  final RMSNorm;
    an untied output head.
    Attn: q, k, v = x Wq, x Wk, x Wv (no bias); QK-norm: one RMSNorm with a
          learned weight over the WHOLE q projection and one over the whole
          k, before the split into heads and before RoPE (rotate-half,
          theta 10000); causal softmax attention; Wo.
    MoE:  router logits h Wg (no bias), softmax over the experts in
          float32, the k largest probabilities taken as the weights AS THEY
          ARE (``norm_topk_prob`` false: no renormalisation),
          y = sum_e w_e * down_e(silu(gate_e h) * up_e h). EVERY expert is
          applied to EVERY token in a loop over the experts and the result
          is masked by the top-k weights: nothing is routed, nothing can be
          dropped.
    loss: next-token cross-entropy + ``balance_coeff`` * E * sum_e f_e P_e
          (f_e: share of the batch's tokens with e among their k, P_e: mean
          probability of e) + ``z_coeff`` * mean(logsumexp(logits)^2),
          the two auxiliary terms summed over the layers.

Departures, each also the system's and written in the configuration file:
the two coefficients are the paper's (0.01, 0.001), the catalog's config
carries neither. For MEMORY only (same arithmetic): attention runs in query
blocks (``lax.map``), the expert loop is a ``lax.scan`` and the head a scan
over token chunks, each body under ``jax.checkpoint``: one block is alive at
a time and backward recomputes it, instead of keeping 64 experts'
activations, every S x S score and the [tokens, vocabulary] logits beside a
training engine's state.

Weights (float32):

    top = {"embed": [V, H], "norm": [H], "lm_head": [V, H]}
    layers[i] = {"input_norm": [H], "q", "k", "v", "o": [H, H],
                 "q_norm", "k_norm": [H], "post_attn_norm": [H],
                 "router": [H, E], "gate", "up": [E, H, F], "down": [E, F, H]}
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """Rotary embedding of x [B, heads, S, D] at positions 0..S-1."""
    S, D = x.shape[-2:]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def attention(x, p, n_head, eps, theta, qk_norm=True, q_block=512):
    B, S, H = x.shape
    D = H // n_head
    q, k, v = x @ p["q"], x @ p["k"], x @ p["v"]
    if qk_norm:
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    q, k, v = (t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
               for t in (q, k, v))
    q, k = rope(q, theta), rope(k, theta)

    @jax.checkpoint
    def rows(q_blk, start):
        scores = q_blk @ k.transpose(0, 1, 3, 2) / math.sqrt(D)
        seen = (start + jnp.arange(q_blk.shape[2]))[:, None] \
            >= jnp.arange(S)[None, :]
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v

    # one block of queries at a time (``lax.map``: one after the other, so
    # that one block's S x S scores are alive, not all of them)
    step = min(q_block, S)
    blocks = q.reshape(B, n_head, S // step, step, D).transpose(2, 0, 1, 3, 4)
    ctx = jax.lax.map(lambda xs: rows(*xs),
                      (blocks, jnp.arange(0, S, step)))
    ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(B, n_head, S, D)
    return ctx.transpose(0, 2, 1, 3).reshape(B, S, H) @ p["o"]


def router(h, wg, k, norm_topk_prob, experts=None):
    """(dense weights [T, E], experts [T, k], probabilities, logits).
    ``experts`` [T, k], when given, are the experts each token is sent to in
    place of the k largest probabilities' (``forward`` says why); the
    weights are still this router's own probabilities of them."""
    logits = h @ wg
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if experts is not None:
        top_e = experts
        top_w = jnp.take_along_axis(probs, experts, axis=1)
    if norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    E = wg.shape[1]
    dense = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32) * top_w[..., None],
                    axis=1)
    return dense, top_e, probs, logits


def moe(h, p, k, norm_topk_prob, experts=None):
    """(output [T, H], balance loss, z loss, experts [T, k])."""
    dense, top_e, probs, logits = router(h, p["router"], k, norm_topk_prob,
                                         experts)
    E = probs.shape[1]

    @jax.checkpoint
    def one_expert(gate, up, down, w):
        return w[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    y, _ = jax.lax.scan(lambda y, xs: (y + one_expert(*xs), None),
                        jnp.zeros_like(h),
                        (p["gate"], p["up"], p["down"], dense.T))
    chosen = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=1)
    balance = E * jnp.sum(jnp.mean(chosen, axis=0) * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, balance, z, top_e


def head_nll_sum(x, norm, lm_head, ids, eps, chunk=2048):
    """Sum over positions of -log p(next token); a sequence's last position
    has no target. Chunks of tokens one after the other (a scan), each
    recomputed in backward: the [tokens, vocabulary] logits never exist."""
    B, S, H = x.shape
    xs = rms_norm(x[:, :-1], norm, eps).reshape(-1, H)
    tgt = ids[:, 1:].reshape(-1)
    pad = (-xs.shape[0]) % chunk
    xs = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, chunk, H)
    live = jnp.pad(jnp.ones_like(tgt, F32), (0, pad)).reshape(-1, chunk)
    tgt = jnp.pad(tgt, (0, pad)).reshape(-1, chunk)

    @jax.checkpoint
    def part(xc, tc, mc):
        logp = jax.nn.log_softmax(xc @ lm_head.T, axis=-1)
        picked = jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * mc)

    total, _ = jax.lax.scan(lambda acc, c: (acc + part(*c), None),
                            jnp.zeros((), F32), (xs, tgt, live))
    return total


def forward(top, layers, ids, *, n_head, k, eps, theta, norm_topk_prob=False,
            qk_norm=True, balance_coeff=0.01, z_coeff=0.001, drop_token=None,
            experts=None):
    """(total loss, detail): detail holds the cross-entropy, the two
    auxiliary losses (unweighted, summed over layers) and per layer the
    chosen experts and the two branches' outputs. ``qk_norm``,
    ``norm_topk_prob``, a zero coefficient and ``drop_token`` (a token index
    whose expert output is thrown away) exist so that the tests can show
    each omission failing the check.

    ``experts`` (per layer [T, k], default None: this model's own top-k) pins
    the discrete choice to one made elsewhere. A bf16 program and this
    float32 model pick a different k-th expert for the few tokens whose k-th
    and (k+1)-th probabilities tie to bf16 rounding; an expert's weight
    gradient is a sum over its rows of terms of random direction, so rows
    that differ in a share s move it by the order of sqrt(s) of its length
    (6.5 % at 0.6 % on the chip, PERF.md Findings PR 27), more than a
    rounding of the backward pass does. To compare BACKWARD passes the
    caller therefore hands over the choice its program made; the loss, the
    routing and the two branches it compares with ``experts`` None."""
    B, S = ids.shape
    x = top["embed"][ids]
    balance = z = jnp.zeros((), F32)
    per_layer = []
    for i, p in enumerate(layers):
        attn = attention(rms_norm(x, p["input_norm"], eps), p, n_head, eps,
                         theta, qk_norm)
        x = x + attn
        h = rms_norm(x, p["post_attn_norm"], eps).reshape(B * S, -1)
        out, bal, zl, top_e = moe(h, p, k, norm_topk_prob,
                                  None if experts is None else experts[i])
        if drop_token is not None:
            out = out.at[drop_token].set(0.0)
        out = out.reshape(x.shape)
        x = x + out
        balance, z = balance + bal, z + zl
        per_layer.append({"top_e": top_e, "attn_out": attn, "ffn_out": out})
    ce = head_nll_sum(x, top["norm"], top["lm_head"], ids, eps) \
        / (B * (S - 1))
    loss = ce + balance_coeff * balance + z_coeff * z
    return loss, {"ce": ce, "balance": balance, "z": z, "layers": per_layer}


def loss(weights, ids, view=lambda w: w, **sizes):
    """(loss, detail) of ``forward`` at full matmul precision. ``view`` turns
    the caller's ``weights`` into ``(top, layers)`` (a caller whose weights
    live in another tree passes its mapping); the default takes
    ``(top, layers)`` itself."""
    with jax.default_matmul_precision("highest"):
        return forward(*view(weights), ids, **sizes)


def loss_and_grads(weights, ids, view=lambda w: w, **sizes):
    """((loss, detail), gradients shaped like ``weights``) of ``loss`` by
    ``jax.value_and_grad``: a caller with a ``view`` gets the gradients back
    in its own layout."""
    return jax.value_and_grad(
        lambda w: loss(w, ids, view, **sizes), has_aux=True)(weights)


def grad_norm(grads):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
