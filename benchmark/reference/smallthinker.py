"""SmallThinker-21BA3B as published, in plain float32 ``jax.numpy``: the
yardstick.

Written from the published ``config.json`` of
PowerInfer/SmallThinker-21BA3B-Instruct and ISSUE 38's layer equations, and
independent of ``deepspeed_tpu/``: no kernel, no scan over layers, no sort,
no grouped matmul, no sharding. Every matmul runs under
``jax.default_matmul_precision("highest")``.

    per layer l, x the residual stream [B, S, H] at the block's input:
    r   = x W_r                     the ROUTER's logits, from the block's
                                    input before any norm
    h   = norm(x; w_1);  q, k, v = h W_q, h W_k, h W_v     (no bias)
    rope_layout[l] == 1: rotate-half RoPE on the whole head of q and k
          (theta, no scaling); == 0: q and k as projected (no position
          encoding at all)
    sliding_window_layout[l] == 1: query i sees key j iff 0 <= i - j <
          window; == 0: iff 0 <= i - j
    a   = softmax(q k^T / sqrt(head_dim) + mask) v; query head n reads KV
          head n // (n_head / n_kv_head)
    x'  = x + concat_n(a_n) W_o
    h'  = norm(x'; w_2)
    P   = softmax(r) over ALL E experts; the k largest; g = P_top / sum
          (``norm_topk_prob``)
    y   = sum over the chosen experts HELD here of
          g_e * (relu(h' W_g^e) * (h' W_u^e)) W_d^e
    out = x' + y
    norm: x / sqrt(mean(x^2) + eps) * w;  final norm; an untied head.
    loss: next-token cross-entropy over the held slice of the vocabulary +
          ``balance_coeff`` * E * sum_e f_e P_e over all E, summed over the
          layers.

The layer HOLDS experts [lo, lo + held) (``held`` is the leading size of its
expert weights): every held expert is applied to every token and masked by
the weights, nothing is routed, and what the absent experts would have added
is left out.

Departures from the published model, each the configuration file's
``assumed`` or ``reduced``: the router reads the block's input (the config
has no key for it); rotate-half pairing; the balance coefficient 0.001 and
no z-loss; a share of the experts and of the vocabulary held; the family's
"secondary experts" and inference-time sparsity are no part of the 21B
config and are not here.

For MEMORY only (same arithmetic): attention one KV head's group of query
heads at a time and, within it, in blocks of query rows against ALL keys
under an explicit mask (each recomputed in the backward pass), the experts
in a scan, the head in chunks of tokens, each layer's two branches
checkpointed whole.

Weights (float32): top = {"embed": [V, H], "norm": [H], "lm_head": [V, H]};
a layer has "input_norm", "post_attn_norm" [H], "q" [H, n_head D], "k", "v"
[H, n_kv_head D], "o" [n_head D, H], "router" [H, E], "gate", "up"
[held, H, F], "down" [held, F, H].
"""

import jax
import jax.numpy as jnp

from benchmark.reference.olmoe import (grad_norm, head_nll_sum,  # noqa: F401
                                       rotate_half)
from benchmark.reference.qwen3_next import pinned

F32 = jnp.float32


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, heads, S, D]: rotate-half RoPE at positions 0..S-1 on all of
    D, no scaling."""
    S, D = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def attention(x, p, *, n_kv_head, head_dim, theta=None, window=None,
              kv_of_head=None, q_block=256):
    """``theta`` None: no rotation. ``window`` None: every key behind the
    query. ``kv_of_head``: another map of query head -> KV head than
    ``n // rep`` (for the tests: [n_head] ints)."""
    B, S, _ = x.shape
    D = head_dim
    n_head = p["q"].shape[1] // D
    rep = n_head // n_kv_head       # consecutive query heads a KV head serves
    q = (x @ p["q"]).reshape(B, S, n_head, D)
    k = (x @ p["k"]).reshape(B, S, n_kv_head, D)
    v = (x @ p["v"]).reshape(B, S, n_kv_head, D)
    if kv_of_head is not None:
        order = jnp.argsort(jnp.asarray(kv_of_head), stable=True)
        q = q[:, :, order]          # heads grouped by the KV head they read
    q = q.reshape(B, S, n_kv_head, rep, D)
    k, v = k[:, :, :, None], v[:, :, :, None]
    step = min(q_block, S)

    # for memory only: one KV head's group of query heads at a time, and
    # within it blocks of query rows against ALL keys, each recomputed in
    # the backward pass
    @jax.checkpoint
    def group(q, k, v):                                 # [B, S, heads, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if theta is not None:
            q, k = rope(q, theta), rope(k, theta)

        @jax.checkpoint
        def rows(q_blk, start):
            scores = q_blk @ k.transpose(0, 1, 3, 2) * D ** -0.5
            behind = (start + jnp.arange(q_blk.shape[2]))[:, None] \
                - jnp.arange(S)[None, :]
            seen = behind >= 0
            if window is not None:
                seen &= behind < window
            return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                  axis=-1) @ v

        blocks = q.reshape(B, rep, S // step, step, D).transpose(
            2, 0, 1, 3, 4)
        ctx = jax.lax.map(lambda xs: rows(*xs),
                          (blocks, jnp.arange(0, S, step)))
        ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(B, rep, S, D)
        return ctx.transpose(0, 2, 1, 3)                # [B, S, rep, D]

    ctx = jax.lax.map(lambda xs: group(*xs), tuple(
        t.transpose(2, 0, 1, 3, 4) for t in (q, k, v)))
    ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(B, S, n_head, D)
    if kv_of_head is not None:
        ctx = ctx[:, :, jnp.argsort(order)]
    return ctx.reshape(B, S, n_head * D) @ p["o"]


def moe(h, logits, p, k, lo, norm_topk_prob=True, act=jax.nn.relu,
        experts=None):
    """(output [T, H], balance loss, experts [T, k], this router's own
    choice [T, k]) of the experts' input ``h`` [T, H] and the router's
    ``logits`` [T, E]. ``experts`` [T, k], when given, replace the router's
    own choice (``benchmark/reference/olmoe.forward`` says why); the
    weights are still this router's probabilities of them."""
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    own_e = top_e
    if experts is not None:
        top_e = experts
        top_w = jnp.take_along_axis(probs, experts, axis=1)
    if norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    T, E = probs.shape
    rows = jnp.arange(T)[:, None]
    dense = jnp.zeros((T, E), F32).at[rows, top_e].add(top_w)
    chosen = jnp.zeros((T, E), F32).at[rows, top_e].add(1.0)
    held = p["gate"].shape[0]

    @jax.checkpoint
    def one_expert(gate, up, down, w):
        return w[:, None] * ((act(h @ gate) * (h @ up)) @ down)

    y, _ = jax.lax.scan(lambda y, xs: (y + one_expert(*xs), None),
                        jnp.zeros_like(h),
                        (p["gate"], p["up"], p["down"],
                         dense[:, lo:lo + held].T))
    balance = E * jnp.sum(jnp.mean(chosen, axis=0) * jnp.mean(probs, axis=0))
    return y, balance, top_e, own_e


def forward(top, layers, ids, *, sliding_window_layout, rope_layout,
            n_kv_head, head_dim, window, theta, eps, k, expert_lo=0,
            balance_coeff=0.001, norm_topk_prob=True, router_input="block",
            act=jax.nn.relu, kv_of_head=None, experts=None, streams=None):
    """(total loss, detail): detail holds the cross-entropy, the balance
    loss (unweighted, summed over the layers) and per layer the experts
    used and the router's own choice, the residual stream after the mixer
    and the two branches' outputs.

    ``sliding_window_layout`` / ``rope_layout`` are the published lists.
    ``router_input`` ("block": the block's input, as published;
    "post_attn_norm": the experts' input), ``act``, ``kv_of_head``,
    ``norm_topk_prob`` and overridden lists exist so that the tests can show
    each omission failing the check. ``experts`` / ``streams``: the two pins
    of ``benchmark/reference/qwen3_next.forward`` — per layer the experts a
    token is sent to, and per layer (the residual stream after the mixer,
    after the experts) of ANOTHER run of the same weights and batch, whose
    values each layer then starts from with this model's gradients."""
    B, S = ids.shape
    x = top["embed"][ids]
    balance = jnp.zeros((), F32)
    per_layer = []
    for i, (p, windowed, rotates) in enumerate(zip(
            layers, sliding_window_layout, rope_layout)):
        block_in = x
        h = norm(x, p["input_norm"], eps)
        mixed = jax.checkpoint(
            lambda h, p, windowed=windowed, rotates=rotates: attention(
                h, p, n_kv_head=n_kv_head, head_dim=head_dim,
                theta=theta if rotates else None,
                window=window if windowed else None,
                kv_of_head=kv_of_head))(h, p)
        x = x + mixed
        if streams is not None:
            x = pinned(x, streams[i][0])
        x_mid = x
        h = norm(x, p["post_attn_norm"], eps).reshape(B * S, -1)
        read = block_in.reshape(B * S, -1) if router_input == "block" else h
        out, bal, top_e, own_e = jax.checkpoint(
            lambda h, read, p, e: moe(h, read @ p["router"], p, k, expert_lo,
                                      norm_topk_prob, act, e))(
            h, read, p, None if experts is None else experts[i])
        balance = balance + bal
        out = out.reshape(x.shape)
        x = x + out
        if streams is not None:
            x = pinned(x, streams[i][1])
        per_layer.append({"top_e": top_e, "own_top_e": own_e, "x_mid": x_mid,
                          "mixer_out": mixed, "ffn_out": out})
    ce = head_nll_sum(x, top["norm"], top["lm_head"], ids, eps) \
        / (B * (S - 1))
    loss = ce + balance_coeff * balance
    return loss, {"ce": ce, "balance": balance, "layers": per_layer}


def loss(weights, ids, view=lambda w: w, **sizes):
    """(loss, detail) of ``forward`` at full matmul precision; ``view``
    turns the caller's ``weights`` into ``(top, layers)``."""
    with jax.default_matmul_precision("highest"):
        return forward(*view(weights), ids, **sizes)


def loss_and_grads(weights, ids, view=lambda w: w, **sizes):
    """((loss, detail), gradients shaped like ``weights``)."""
    return jax.value_and_grad(
        lambda w: loss(w, ids, view, **sizes), has_aux=True)(weights)
