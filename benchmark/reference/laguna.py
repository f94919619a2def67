"""Laguna-XS.2 as published, in plain float32 ``jax.numpy``: the yardstick.

Written from the published description of poolside/Laguna-XS.2 (its
``config.json``; HF's ``_compute_yarn_parameters`` for the YaRN frequencies;
ISSUE 33's layer equations) and independent of
``deepspeed_tpu/models/laguna.py``, ``deepspeed_tpu/ops`` and
``deepspeed_tpu/moe``: no kernel, no scan over layers, no sort, no grouped
matmul, no sharding. Every matmul runs under
``jax.default_matmul_precision("highest")``.

    x += Attn_l(norm(x));  x += FFN_l(norm(x));  final norm; an untied head.
    norm:  x / sqrt(mean(x^2) + eps) * w          (plain weight)
    Attn_l: H_l query heads (the width of the layer's own ``q`` over
          head_dim), ``n_kv_head`` KV heads, each serving H_l / n_kv_head
          consecutive query heads. RoPE (rotate-half) by ``layer_types[l]``:
          a FULL layer rotates the first ``rotary_dim`` of each head with
          YaRN frequencies — theta^(-2i/d) blended with that / factor by the
          linear ramp between the two correction dims, cos and sin times
          ``attention_factor``; a SLIDING layer rotates the whole head, plain.
          Scores q.k / sqrt(head_dim); key j is visible to query i iff
          0 <= i - j, and in a SLIDING layer also i - j < window. Softmax,
          o = P v. Gate: g = sigmoid(h W_g), one scalar a head a token, on
          the head's output. out = o W_o.
    FFN_l: where the layer carries ``mlp_gate`` (dense):
          (silu(h W_gate) * h W_up) W_down. Else (sparse): float32 router
          p = softmax(h W_r) over ALL ``E`` experts, the k largest,
          w = routed_scale * p_top / sum(p_top); the layer HOLDS experts
          [lo, lo + held) (``held`` is the leading size of its expert
          weights) and sums w_e * SwiGLU_e(h) over those alone — every held
          expert applied to every token and masked by the weights, nothing
          routed — plus sigmoid(h w_sg) * SwiGLU_shared(h).
    loss: next-token cross-entropy over the held slice of the vocabulary +
          ``balance_coeff`` * E * sum_e f_e P_e over all E, summed over the
          sparse layers.

Departures from the published model, each the configuration file's
``assumed`` or ``reduced``: the gate is per head on the block's normed input;
no QK-norm; a softmax router with the top-k renormalised; the shared expert
under a sigmoid gate; plain RMSNorm; the balance coefficient 0.001 and no
z-loss; a share of the experts and of the vocabulary held.

For MEMORY only (same arithmetic): attention one KV head's group of query
heads at a time and, within it, in blocks of query rows against ALL keys
under a mask (each recomputed in the backward pass), the experts in a scan,
the head in chunks of tokens, each layer's two branches checkpointed whole.

Weights (float32): top = {"embed": [V, H], "norm": [H], "lm_head": [V, H]};
a layer has "input_norm", "post_attn_norm" [H], "q" [H, H_l D], "k", "v"
[H, Hkv D], "g" [H, H_l], "o" [H_l D, H] and either (dense) "mlp_gate",
"mlp_up" [H, I], "mlp_down" [I, H] or (sparse) "router" [H, E], "gate", "up"
[held, H, F], "down" [held, F, H], "shared_gate", "shared_up" [H, Fs],
"shared_down" [Fs, H], "shared_expert_gate" [H, 1].
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.olmoe import (grad_norm, head_nll_sum,  # noqa: F401
                                       rotate_half)
from benchmark.reference.qwen3_next import pinned

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """The ``dim // 2`` inverse frequencies of YaRN, from its formula."""
    def correction_dim(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1.0 - ramp))
    return jnp.asarray(out, F32)


def rope(x, rope_params, yarn=True):
    """x [B, heads, S, D]: rotate-half RoPE at positions 0..S-1 on the first
    ``partial_rotary_factor`` of D with one layer type's published
    parameters (a dict); ``yarn`` False ignores a YaRN set's scaling."""
    S, D = x.shape[-2], x.shape[-1]
    p = dict(rope_params)
    dim = int(D * p.get("partial_rotary_factor", 1.0))
    theta = float(p["rope_theta"])
    scale = 1.0
    if p.get("rope_type", "default") == "yarn" and yarn:
        inv = yarn_inv_freq(dim, theta, float(p["factor"]),
                            p["original_max_position_embeddings"],
                            float(p.get("beta_fast", 32.0)),
                            float(p.get("beta_slow", 1.0)))
        scale = p.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(float(p["factor"])) + 1.0
    else:
        inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    rot, rest = x[..., :dim], x[..., dim:]
    rot = rot * (jnp.cos(ang) * scale) + rotate_half(rot) * (jnp.sin(ang)
                                                            * scale)
    return jnp.concatenate([rot, rest], axis=-1)


def attention(x, p, *, n_kv_head, head_dim, rope_params, window=None,
              output_gate=True, yarn=True, q_block=256):
    B, S, _ = x.shape
    D = head_dim
    n_head = p["q"].shape[1] // D
    rep = n_head // n_kv_head       # consecutive query heads a KV head serves
    q = (x @ p["q"]).reshape(B, S, n_kv_head, rep, D)
    k = (x @ p["k"]).reshape(B, S, n_kv_head, 1, D)
    v = (x @ p["v"]).reshape(B, S, n_kv_head, 1, D)
    step = min(q_block, S)

    # for memory only: one KV head's group of query heads at a time, and
    # within it blocks of query rows against ALL keys, each recomputed in
    # the backward pass
    @jax.checkpoint
    def group(q, k, v):                                 # [B, S, heads, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        q, k = (rope(t, rope_params, yarn) for t in (q, k))

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
    if output_gate:
        ctx = ctx * jax.nn.sigmoid(x @ p["g"])[..., None]
    return ctx.reshape(B, S, n_head * D) @ p["o"]


def dense_mlp(h, p):
    return (jax.nn.silu(h @ p["mlp_gate"]) * (h @ p["mlp_up"])) \
        @ p["mlp_down"]


def moe(h, p, k, lo, routed_scale=1.0, norm_topk_prob=True, shared_gate=True,
        experts=None):
    """(output [T, H], balance loss, experts [T, k], this router's own
    choice [T, k]). ``experts`` [T, k], when given, replace the router's own
    choice (``benchmark/reference/olmoe.forward`` says why); the weights are
    still this router's probabilities of them."""
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    own_e = top_e
    if experts is not None:
        top_e = experts
        top_w = jnp.take_along_axis(probs, experts, axis=1)
    if norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = routed_scale * top_w
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
    return y + shared, balance, top_e, own_e


def forward(top, layers, ids, *, layer_types, rope_parameters, n_kv_head,
            head_dim, window, eps, k, routed_scale, expert_lo=0,
            balance_coeff=0.001, norm_topk_prob=True, output_gate=True,
            shared_gate=True, yarn=True, experts=None, streams=None):
    """(total loss, detail): detail holds the cross-entropy, the balance
    loss (unweighted, summed over the sparse layers) and per layer the
    experts used and the router's own choice (None for a dense layer), the
    residual stream after the mixer and the two branches' outputs.
    ``window`` None (every layer full causal), ``output_gate``,
    ``shared_gate``, ``yarn``, ``norm_topk_prob`` and ``routed_scale`` exist
    so that the tests can show each omission failing the check.

    ``layer_types`` and ``rope_parameters`` are the published lists (the
    latter as a dict, or its items, of {layer type: parameter set}).
    ``experts`` / ``streams``: the two pins of
    ``benchmark/reference/qwen3_next.forward`` — per layer the experts a
    token is sent to (None for a dense layer), and per layer (the residual
    stream after the mixer, after the FFN) of ANOTHER run of the same
    weights and batch, whose values each layer then starts from with this
    model's gradients."""
    B, S = ids.shape
    rope_parameters = dict(rope_parameters)
    x = top["embed"][ids]
    balance = jnp.zeros((), F32)
    per_layer = []
    for i, (p, kind) in enumerate(zip(layers, layer_types)):
        h = norm(x, p["input_norm"], eps)
        mixed = jax.checkpoint(lambda h, p, kind=kind: attention(
            h, p, n_kv_head=n_kv_head, head_dim=head_dim,
            rope_params=rope_parameters[kind],
            window=window if kind == SLIDING else None,
            output_gate=output_gate, yarn=yarn))(h, p)
        x = x + mixed
        if streams is not None:
            x = pinned(x, streams[i][0])
        x_mid = x
        h = norm(x, p["post_attn_norm"], eps).reshape(B * S, -1)
        if "mlp_gate" in p:
            out, top_e, own_e = jax.checkpoint(dense_mlp)(h, p), None, None
        else:
            out, bal, top_e, own_e = jax.checkpoint(
                lambda h, p, e: moe(h, p, k, expert_lo, routed_scale,
                                    norm_topk_prob, shared_gate, e))(
                h, p, None if experts is None else experts[i])
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
