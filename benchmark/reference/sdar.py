"""SDAR-30B-A3B-Chat's block-diffusion training step, in plain float32
``jax.numpy``: the yardstick.

Written from the published ``config.json`` of JetLM/SDAR-30B-A3B-Chat
(``model_type`` ``sdar_moe``), the block-diffusion objective SDAR (arXiv
2510.06303) trains with (BD3-LMs, arXiv 2503.09573, in its vectorised form)
and ISSUE 60's equations; independent of ``deepspeed_tpu/``: no kernel, no
scan over layers, no sort, no grouped matmul, no remat policy of the
program's. Every matmul runs under ``jax.default_matmul_precision("highest")``.

One layer, on rows r with position p_r (X is [T, 2048], T = 2L):

    a   = RMSNorm(X; w_in, 1e-6)
    q_h = RoPE(RMSNorm_128(a W_q [h]; w_qn), p_r)   h = 0..31   (theta 1e6,
                                                     rotate-half layout)
    k_g = RoPE(RMSNorm_128(a W_k [g]; w_kn), p_r)   g = 0..3 ; v_g = a W_v [g]
    o_h[r] = sum_s softmax_s(q_h[r] . k_{h//8}[s] / sqrt(128) + M[r, s])
             v_{h//8}[s]
    X   = X + concat_h(o_h) W_o                     (W_o is [4096, 2048])
    b   = RMSNorm(X; w_post, 1e-6)
    p   = softmax_float32(b W_r) over the 128 published experts ;
    S   = top-8(p) ; g_e = p_e / sum_{e' in S} p_e'
    X   = X + sum_{e in S, e held here} g_e (SiLU(b W_gate^e) * (b W_up^e))
          W_down^e                                   (width 768)

Block-diffusion training of one sequence x_0 .. x_{L-1}, block length Bk,
block b(i) = i // Bk:

    t_b     = eps + (1 - eps) u_b , u_b ~ U[0, 1) a block ;
    masked_i = (v_i < t_{b(i)}) , v_i ~ U[0, 1)
    noisy_i = MASK if masked_i else x_i
    rows    = [noisy_0 .. noisy_{L-1}, x_0 .. x_{L-1}] ,
    positions = [0 .. L-1, 0 .. L-1]
    M[r, s] = 0 where allowed, -inf elsewhere; with half(r) = r // L
              (0 noised, 1 clean), blk(r) = (r mod L) // Bk:
      allowed = (half_r = 0 and half_s = 0 and blk_s =  blk_r)
             or (half_r = 0 and half_s = 1 and blk_s <  blk_r)
             or (half_r = 1 and half_s = 1 and blk_s <= blk_r)
    logits_i = RMSNorm(X_final[noised row i]; w_f) W_head^T
    loss    = 1 / (B L) * sum_{i masked} (1 / t_{b(i)})
              * (logsumexp(logits_i) - logits_i[x_i])     (no shift)

This file takes ``(noisy_ids, masked, t_row)`` as INPUTS (``t_row``: each
position's own block's t), so it shares no line with the program's noise or
mask code.

Departures from the published model, each the configuration file's
``assumed`` or ``reduced``: the block length (4), the noise schedule (linear,
t a block, eps 1e-3, weight 1 / t) and the normalisation by B L, which the
config has no key for; rotate-half pairing; no auxiliary router loss; the
layer HOLDS experts [lo, lo + held) (``held`` the leading size of its expert
weights: every held expert is applied to every row and masked by the
weights, nothing is routed, and what the absent experts would have added is
left out); a slice of the vocabulary, the mask id the slice's last.

For MEMORY only (same arithmetic): attention one KV head's group of query
heads at a time and, within it, in blocks of query rows against ALL 2L keys
under the explicit mask (each recomputed in the backward pass), the experts
in a scan, the head in chunks of rows.

``control`` names one deliberate fault, for the tests and the configuration
file's recorded controls: "own_block_leaked" (a noised query also sees the
CLEAN rows of its own block: ``<=`` where ``<`` stands), "causal_in_block"
(within a block a query sees no later position), "weight_dropped" (1 where
1 / t stands), "targets_shifted" (row i scored against token i + 1),
"one_t_a_sequence" (every row weighed by the sequence's first block's t).

Weights (float32): top = {"embed": [V, H], "norm": [H], "lm_head": [V, H]};
a layer has "input_norm", "post_attn_norm" [H], "q" [H, n_head D], "k", "v"
[H, n_kv_head D], "o" [n_head D, H], "q_norm", "k_norm" [D], "router"
[H, E], "gate", "up" [held, H, F], "down" [held, F, H].
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.olmoe import (grad_norm,  # noqa: F401
                                       rms_norm as norm, rotate_half)

F32 = jnp.float32
CONTROLS = ("own_block_leaked", "causal_in_block", "weight_dropped",
            "targets_shifted", "one_t_a_sequence")


def rope(x, positions, theta):
    """x [B, heads, T, D]: rotate-half RoPE at ``positions`` [T]."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def allowed(rows, L, block_length, control=None):
    """bool [len(rows), 2L]: the three clauses, for the query rows ``rows``."""
    r, s = rows[:, None], jnp.arange(2 * L)[None, :]
    half_r, half_s = r // L, s // L
    blk_r, blk_s = (r % L) // block_length, (s % L) // block_length
    before = blk_s <= blk_r if control == "own_block_leaked" \
        else blk_s < blk_r
    ok = ((half_r == 0) & (half_s == 0) & (blk_s == blk_r)) \
        | ((half_r == 0) & (half_s == 1) & before) \
        | ((half_r == 1) & (half_s == 1) & (blk_s <= blk_r))
    if control == "causal_in_block":
        ok &= (blk_s != blk_r) | (s % L <= r % L)
    return ok


def attention(x, p, *, n_kv_head, head_dim, eps, theta, block_length,
              control=None, q_block=512):
    B, T, _ = x.shape
    L, D = T // 2, head_dim
    n_head = p["q"].shape[1] // D
    group = n_head // n_kv_head
    positions = jnp.concatenate([jnp.arange(L), jnp.arange(L)])

    def heads(t, w):
        t = t.reshape(B, T, -1, D)
        if w is not None:
            t = norm(t, w, eps)
        return t.transpose(0, 2, 1, 3)

    q = rope(heads(x @ p["q"], p["q_norm"]), positions, theta)
    k = rope(heads(x @ p["k"], p["k_norm"]), positions, theta)
    v = heads(x @ p["v"], None)
    step = min(q_block, T)

    @jax.checkpoint
    def rows(q_blk, k_g, v_g, start):
        # q_blk [B, group, step, D] against one KV head's k_g, v_g [B, T, D]
        scores = jnp.einsum("bhrd,bsd->bhrs", q_blk, k_g) / math.sqrt(D)
        seen = allowed(start + jnp.arange(step), L, block_length, control)
        return jnp.einsum(
            "bhrs,bsd->bhrd",
            jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v_g)

    def one_group(qkv):
        q_g, k_g, v_g = qkv                # [B, group, T, D], [B, T, D] x 2
        blocks = q_g.reshape(B, group, T // step, step, D) \
            .transpose(2, 0, 1, 3, 4)
        ctx = jax.lax.map(lambda xs: rows(xs[0], k_g, v_g, xs[1]),
                          (blocks, jnp.arange(0, T, step)))
        return ctx.transpose(1, 2, 0, 3, 4).reshape(B, group, T, D)

    ctx = jax.lax.map(one_group, (
        q.reshape(B, n_kv_head, group, T, D).transpose(1, 0, 2, 3, 4),
        k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3)))
    ctx = ctx.transpose(1, 0, 2, 3, 4).reshape(B, n_head, T, D)
    return ctx.transpose(0, 2, 1, 3).reshape(B, T, n_head * D) @ p["o"]


def moe(h, p, k, expert_lo, experts=None):
    """(the held experts' partial sum [T, H], experts [T, k]) of rows ``h``:
    softmax over ALL experts, the k largest renormalised; ``experts`` pins
    the choice (``reference/olmoe.forward`` says why)."""
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if experts is not None:
        top_e = experts
        top_w = jnp.take_along_axis(probs, experts, axis=1)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    dense = jnp.sum(jax.nn.one_hot(top_e, probs.shape[1], dtype=F32)
                    * top_w[..., None], axis=1)
    held = p["up"].shape[0]

    @jax.checkpoint
    def one_expert(gate, up, down, w):
        return w[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    y, _ = jax.lax.scan(
        lambda y, xs: (y + one_expert(*xs), None), jnp.zeros_like(h),
        (p["gate"], p["up"], p["down"],
         dense[:, expert_lo:expert_lo + held].T))
    return y, top_e


def weighted_nll_sum(x, w_norm, lm_head, targets, weights, eps, chunk=2048):
    """sum_i weights_i (logsumexp(logits_i) - logits_i[targets_i]) over the
    rows of ``x`` [B, L, H], in chunks of rows one after the other."""
    H = x.shape[-1]
    xs = norm(x, w_norm, eps).reshape(-1, H)
    pad = (-xs.shape[0]) % chunk
    xs = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, chunk, H)
    tgt = jnp.pad(targets.reshape(-1), (0, pad)).reshape(-1, chunk)
    wts = jnp.pad(weights.reshape(-1), (0, pad)).reshape(-1, chunk)

    @jax.checkpoint
    def part(xc, tc, wc):
        logits = xc @ lm_head.T
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(wc * (jax.nn.logsumexp(logits, axis=-1) - picked))

    total, _ = jax.lax.scan(lambda acc, c: (acc + part(*c), None),
                            jnp.zeros((), F32), (xs, tgt, wts))
    return total


def layer(x, p, experts=None, *, n_kv_head, head_dim, k, eps, theta,
          block_length, expert_lo=0, control=None):
    """(the stream after one layer, {"top_e", "attn_out", "ffn_out"}) of the
    stream ``x`` [B, 2L, H]; ``experts`` pins the router's choice."""
    attn = attention(norm(x, p["input_norm"], eps), p, n_kv_head=n_kv_head,
                     head_dim=head_dim, eps=eps, theta=theta,
                     block_length=block_length, control=control)
    x = x + attn
    h = norm(x, p["post_attn_norm"], eps).reshape(-1, x.shape[-1])
    out, top_e = moe(h, p, k, expert_lo, experts)
    out = out.reshape(x.shape)
    return x + out, {"top_e": top_e, "attn_out": attn, "ffn_out": out}


def head_loss(x, top, ids, masked, t_row, *, eps, control=None):
    """The loss from the final stream ``x`` [B, 2L, H]: the noised half
    through the final norm and the head, the masked rows weighted 1 / t."""
    B, L = ids.shape
    t = t_row[:, :1] if control == "one_t_a_sequence" else t_row
    weights = masked.astype(F32) * (
        1.0 if control == "weight_dropped" else 1.0 / t)
    targets = jnp.roll(ids, -1, axis=1) if control == "targets_shifted" \
        else ids
    return weighted_nll_sum(x[:, :L], top["norm"], top["lm_head"], targets,
                            weights, eps) / (B * L)


def embed_rows(top, ids, noisy_ids):
    return top["embed"][jnp.concatenate([noisy_ids, ids], axis=1)]


def forward(top, layers, ids, noisy_ids, masked, t_row, *, experts=None,
            control=None, **sizes):
    """(loss, detail): detail holds per layer the chosen experts [2 B L, k]
    and the two branches' outputs over all 2L rows. ``experts`` (per layer)
    pins the choice for a comparison of BACKWARD passes."""
    x = embed_rows(top, ids, noisy_ids)
    per_layer = []
    for i, p in enumerate(layers):
        x, detail = layer(x, p, None if experts is None else experts[i],
                          control=control, **sizes)
        per_layer.append(detail)
    return head_loss(x, top, ids, masked, t_row, eps=sizes["eps"],
                     control=control), {"layers": per_layer}


def loss(weights, ids, noise, view=lambda w: w, **sizes):
    """(loss, detail) of ``forward`` at full matmul precision; ``noise`` is
    ``(noisy_ids, masked, t_row)``; ``view`` turns the caller's ``weights``
    into ``(top, layers)``."""
    with jax.default_matmul_precision("highest"):
        return forward(*view(weights), ids, *noise, **sizes)


def loss_and_grads(weights, ids, noise, view=lambda w: w, **sizes):
    """((loss, detail), gradients shaped like ``weights``) in one piece: the
    tests' sizes. At the cell's size a caller walks the same gradient a
    layer at a time with ``layer`` and ``head_loss`` under ``jax.vjp``
    (``families/sdar.py``): six layers' activations and a whole float32
    gradient tree do not fit beside a training engine's state."""
    return jax.value_and_grad(
        lambda w: loss(w, ids, noise, view, **sizes), has_aux=True)(weights)
