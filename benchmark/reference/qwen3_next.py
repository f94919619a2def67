"""Qwen3-Next as published, in plain float32 ``jax.numpy``: the yardstick.

Written from the published description of Qwen/Qwen3-Next-80B-A3B-Instruct
(its ``config.json``, HF's ``modeling_qwen3_next.py`` for the order of
operations, Gated Delta Networks arXiv:2412.06464 for the recurrence) and
independent of ``deepspeed_tpu/models/qwen3_next.py``, ``ops/gated_delta.py``
and ``deepspeed_tpu/moe``: no chunked form, no kernel, no sort, no grouped
matmul, no sharding. Every matmul runs under
``jax.default_matmul_precision("highest")``.

    layer i is full attention where (i + 1) % 4 == 0, else Gated DeltaNet
    (here: by the leaves a layer's weights carry);
    x += mixer(norm(x));  x += moe(norm(x));  final norm; an untied head.
    norm:  x / sqrt(mean(x^2) + eps) * (1 + w)   (zero-centred weight)
    Gated DeltaNet: q, k, v, z = x W_qkvz (columns q | k | v | z, each
          head-major); b, a = x W_ba; q | k | v through a causal depthwise
          convolution (taps [W, C], no bias) and SiLU;
          beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias);
          q, k L2-normalised over the head (eps 1e-6), q times Dk^-0.5, a key
          head serving Hv / Hk consecutive value heads. Per value head,
          TOKEN BY TOKEN (a ``lax.scan`` over the sequence):
              S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T
              o_t = S^T q_t
          then o / rms(o) * w_norm (plain weight) * silu(z), W_out.
    Gated attention: q_proj gives per head [query | gate]; per-head
          zero-centred norm of query and of key; RoPE (rotate-half) on the
          first ``rotary_dim`` of each head; each KV head serves H / Hkv
          consecutive query heads; causal softmax at head_dim^-0.5; the
          context times sigmoid(gate); W_o.
    MoE:  router logits h Wg in float32 over ALL ``E`` experts, softmax, the
          k largest renormalised to sum to one; the layer HOLDS experts
          [lo, lo + held) (``held`` is the leading size of its expert
          weights) and sums w_e * down_e(silu(gate_e h) * up_e h) over those
          alone — every held expert applied to every token and masked by the
          weights, nothing routed — plus sigmoid(h w_sg) * shared(h).
    loss: next-token cross-entropy over the held slice of the vocabulary +
          ``balance_coeff`` * E * sum_e f_e P_e over all E, summed over layers.

For MEMORY only (same arithmetic): each mixer runs a sequence at a time
(``lax.map``, the sequence's pass under ``jax.checkpoint``), the recurrence
in blocks of tokens under ``jax.checkpoint``, attention in query blocks,
the experts in a scan, the head in chunks of tokens, and each layer's expert
branch is checkpointed whole.

Weights (float32): top = {"embed": [V, H], "norm": [H], "lm_head": [V, H]};
a layer has "input_norm", "post_attn_norm" [H], "router" [H, E], "gate",
"up" [held, H, F], "down" [held, F, H], "shared_gate", "shared_up" [H, Fs],
"shared_down" [Fs, H], "shared_expert_gate" [H, 1] and either (DeltaNet)
"in_qkvz", "in_ba", "conv" [W, C], "A_log", "dt_bias" [Hv], "gdn_norm" [Dv],
"out" or (attention) "q", "k", "v", "o", "q_norm", "k_norm" [D].
"""

import jax
import jax.numpy as jnp

from benchmark.reference.olmoe import (grad_norm, head_nll_sum,  # noqa: F401
                                       rotate_half)

F32 = jnp.float32


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * (1.0 + w)


def partial_rope(x, rotary_dim, theta):
    """x [B, heads, S, D]: rotate-half RoPE on the first ``rotary_dim`` of
    D at positions 0..S-1, the rest as it is."""
    S = x.shape[-2]
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=F32)
                          / rotary_dim)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rot = rot * jnp.cos(ang) + rotate_half(rot) * jnp.sin(ang)
    return jnp.concatenate([rot, rest], axis=-1)


def attention(x, p, *, n_head, n_kv_head, head_dim, rotary_dim, theta, eps,
              output_gate=True, q_block=512):
    B, S, _ = x.shape
    D = head_dim
    qg = (x @ p["q"]).reshape(B, S, n_head, 2, D)
    q, gate = qg[..., 0, :], qg[..., 1, :]
    k = (x @ p["k"]).reshape(B, S, n_kv_head, D)
    v = (x @ p["v"]).reshape(B, S, n_kv_head, D)
    q, k = norm(q, p["q_norm"], eps), norm(k, p["k_norm"], eps)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    q, k = (partial_rope(t, rotary_dim, theta) for t in (q, k))
    k, v = (jnp.repeat(t, n_head // n_kv_head, axis=1) for t in (k, v))

    @jax.checkpoint
    def rows(q_blk, start):
        scores = q_blk @ k.transpose(0, 1, 3, 2) * D ** -0.5
        seen = (start + jnp.arange(q_blk.shape[2]))[:, None] \
            >= jnp.arange(S)[None, :]
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v

    step = min(q_block, S)
    blocks = q.reshape(B, n_head, S // step, step, D).transpose(2, 0, 1, 3, 4)
    ctx = jax.lax.map(lambda xs: rows(*xs), (blocks, jnp.arange(0, S, step)))
    ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(B, n_head, S, D)
    ctx = ctx.transpose(0, 2, 1, 3)
    if output_gate:
        ctx = ctx * jax.nn.sigmoid(gate)
    return ctx.reshape(B, S, n_head * D) @ p["o"]


def delta_rule(q, k, v, g, beta, block=64):
    """One sequence: q, k [S, Hv, Dk], v [S, Hv, Dv], g, beta [S, Hv] ->
    o [S, Hv, Dv] by the recurrence, one token a step."""
    S, H, Dk = q.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = (-S) % block
    xs = tuple(jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
        (S + pad) // block, block, *t.shape[1:]) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(tokens, jnp.zeros((H, Dk, v.shape[-1]), F32), xs)
    return o.reshape(S + pad, H, -1)[:S]


def delta_net(x, p, *, hk, dk, hv, dv, eps, decay_gate=True, use_beta=True):
    B, S, _ = x.shape
    key, val = hk * dk, hv * dv
    qkvz, ba = x @ p["in_qkvz"], x @ p["in_ba"]
    qkv, z = qkvz[..., :2 * key + val], qkvz[..., 2 * key + val:]
    taps = p["conv"]
    W = taps.shape[0]
    padded = jnp.pad(qkv, ((0, 0), (W - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(taps[j] * padded[:, j:j + S] for j in range(W)))
    q = qkv[..., :key].reshape(B, S, hk, dk)
    k = qkv[..., key:2 * key].reshape(B, S, hk, dk)
    v = qkv[..., 2 * key:].reshape(B, S, hv, dv)
    b, a = ba[..., :hv], ba[..., hv:]
    beta = jax.nn.sigmoid(b) if use_beta else jnp.ones_like(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    if not decay_gate:
        g = jnp.zeros_like(g)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6)

    q, k = l2(q) * dk ** -0.5, l2(k)
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    o = jax.lax.map(lambda xs: delta_rule(*xs), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * p["gdn_norm"] * jax.nn.silu(z.reshape(B, S, hv, dv))
    return o.reshape(B, S, val) @ p["out"]


def moe(h, p, k, lo, norm_topk_prob=True, shared_gate=True, experts=None):
    """(output [T, H], balance loss, experts [T, k], shared part [T, H],
    this router's own choice [T, k]). ``experts`` [T, k], when given,
    replace the router's own choice (``benchmark/reference/olmoe.forward``
    says why); the weights are still this router's probabilities of them."""
    logits = h @ p["router"]
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
        return w[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    y, _ = jax.lax.scan(lambda y, xs: (y + one_expert(*xs), None),
                        jnp.zeros_like(h),
                        (p["gate"], p["up"], p["down"],
                         dense[:, lo:lo + held].T))
    shared = (jax.nn.silu(h @ p["shared_gate"]) * (h @ p["shared_up"])) \
        @ p["shared_down"]
    if shared_gate:
        shared = jax.nn.sigmoid(h @ p["shared_expert_gate"]) * shared
    balance = E * jnp.sum(jnp.mean(chosen, axis=0) * jnp.mean(probs, axis=0))
    return y + shared, balance, top_e, shared, own_e


def pinned(x, to):
    """The VALUE of ``to`` with the gradient of ``x``."""
    return x + jax.lax.stop_gradient(to.astype(F32) - x)


def forward(top, layers, ids, *, n_head, n_kv_head, head_dim, rotary_dim,
            theta, eps, hk, dk, hv, dv, k, expert_lo=0, balance_coeff=0.001,
            norm_topk_prob=True, decay_gate=True, use_beta=True,
            output_gate=True, shared_gate=True, experts=None, streams=None):
    """(total loss, detail): detail holds the cross-entropy, the balance
    loss (unweighted, summed over layers) and per layer the experts used,
    the router's own choice (the same unless ``experts`` pins them), the
    residual stream after the mixer and the two branches' outputs. ``decay_gate``, ``use_beta``,
    ``rotary_dim`` (= head_dim: RoPE over the whole head), ``output_gate``,
    ``shared_gate`` and ``norm_topk_prob`` exist so that the tests can show
    each omission failing the check.

    Two pins, both None for this model's own pass (the loss, the routing).
    ``experts``: per layer [T, k], the experts a token is sent to, as in
    ``benchmark/reference/olmoe.forward``. ``streams``: per layer (the
    residual stream after the mixer, after the expert branch) of ANOTHER
    run of the same weights and batch; each layer then starts from that
    run's values (``pinned``: its values, this model's gradients). At
    initialisation the branches ARE the residual stream (a DeltaNet mixer's
    output is normalised to unit scale, 17 x the embedding's), so a bf16
    run and this one drift apart layer over layer — 0.9 %, 4.4 %, 7.6 %,
    7.9 % of the mixer's output over the four layers on the chip (PERF.md
    Findings PR 31) — and every layer's output and every gradient leaf
    would be compared through that drift. Pinned, each layer answers for
    its own arithmetic on the same input, forward and backward."""
    B, S = ids.shape
    x = top["embed"][ids]
    balance = jnp.zeros((), F32)
    per_layer = []

    def a_sequence_at_a_time(mixer, h, p):
        return jax.lax.map(
            jax.checkpoint(lambda one: mixer(one[None], p)[0]), h)

    for i, p in enumerate(layers):
        h = norm(x, p["input_norm"], eps)
        if "in_qkvz" in p:
            mixed = a_sequence_at_a_time(lambda h, p: delta_net(
                h, p, hk=hk, dk=dk, hv=hv, dv=dv, eps=eps,
                decay_gate=decay_gate, use_beta=use_beta), h, p)
        else:
            mixed = a_sequence_at_a_time(lambda h, p: attention(
                h, p, n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
                rotary_dim=rotary_dim, theta=theta, eps=eps,
                output_gate=output_gate), h, p)
        x = x + mixed
        if streams is not None:
            x = pinned(x, streams[i][0])
        x_mid = x
        h = norm(x, p["post_attn_norm"], eps).reshape(B * S, -1)
        out, bal, top_e, _, own_e = jax.checkpoint(
            lambda h, p, e: moe(h, p, k, expert_lo, norm_topk_prob,
                                shared_gate, e))(
            h, p, None if experts is None else experts[i])
        out = out.reshape(x.shape)
        x = x + out
        if streams is not None:
            x = pinned(x, streams[i][1])
        balance = balance + bal
        per_layer.append({"top_e": top_e, "own_top_e": own_e, "x_mid": x_mid,
                          "mixer_out": mixed, "ffn_out": out})
    # olmoe's head applies a plain-weight norm: hand it 1 + w
    ce = head_nll_sum(x, 1.0 + top["norm"], top["lm_head"], ids, eps) \
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
