"""Kanana-2-30B-A3B (``model_type: deepseek_v3``) as published, in plain
float32 ``jax.numpy``: the yardstick.

Written from the published ``config.json`` of
kakaocorp/kanana-2-30b-a3b-instruct-2601, the DeepSeek-V2 / V3 papers' layer
equations (arXiv 2405.04434 section 2.1, 2412.19437 section 2.1) and
ISSUE 47's, and independent of ``deepspeed_tpu/models/deepseek_v3.py``,
``deepspeed_tpu/ops`` and ``deepspeed_tpu/moe``: no kernel, no scan over
layers, no sort, no grouped matmul, no sharding. Every matmul runs under
``jax.default_matmul_precision("highest")``.

    x += Attn(norm(x));  x += FFN_l(norm(x));  final norm; an untied head.
    norm:  x / sqrt(mean(x^2) + eps) * w          (plain weight)
    Attn: H heads. q = h W_q, a head's [q_nope (nope) ; q_rope (rope_dim)].
          [c ; k_r] = h W_kva (latent of R = the norm's width, and rope_dim
          more); c <- norm(c). [k_nope_h ; v_h] for every head = c W_kvb.
          RoPE at positions 0..S-1 on q_rope of every head and on k_r — ONE
          vector a token, the same for all heads — the pair (2i, 2i+1)
          turned by pos * theta^(-2i / rope_dim), IN PLACE (the published
          ``rope_interleave`` layout; no scaling, no mscale). A head's keys
          are materialised as [k_nope_h ; k_r]: scores q_h . k_h /
          sqrt(nope + rope_dim), key j visible to query i iff j <= i,
          softmax, o_h = P_h v_h (v_dim wide). out = concat_h(o_h) W_o.
    FFN_l: where the layer carries ``mlp_gate`` (dense):
          (silu(h W_gate) * h W_up) W_down. Else: s = sigmoid(h W_r) over
          ALL E experts; the k experts with the largest s + bias; w = s at
          them (WITHOUT the bias) / (their sum + 1e-20) * routed_scale; the
          layer HOLDS experts [lo, lo + held) (``held`` is the leading size
          of its expert weights) and sums w_e * SwiGLU_e(h) over those alone
          — every held expert applied to every token and masked by the
          weights, nothing routed — plus SwiGLU_shared(h), ungated.
    loss: next-token cross-entropy over the held slice of the vocabulary.

Departures from the published code, each the configuration file's
``assumed`` or ``reduced``: HF's implementation first PERMUTES a rotated
vector's columns to the half-split layout and rotates halves — here the
pairs are turned where they lie, which leaves every q . k as it is (the
same permutation on both sides); no multi-token-prediction module and no
auxiliary loss; a share of the experts and of the vocabulary held; the two
shared experts as one SwiGLU of their summed width (the published module is
one MLP of that width).

For MEMORY only (same arithmetic): attention ``head_group`` heads at a time
and, within them, in blocks of query rows against ALL keys under a mask
(each recomputed in the backward pass), the experts in a scan, the head in
chunks of tokens, each layer's two branches checkpointed whole.

Weights (float32): top = {"embed": [V, H], "norm": [H], "lm_head": [V, H]};
a layer has "input_norm", "post_attn_norm" [H], "q" [H, heads (nope +
rope_dim)], "kv_a" [H, R + rope_dim], "kv_a_norm" [R], "kv_b" [R, heads
(nope + v_dim)], "o" [heads v_dim, H] and either (dense) "mlp_gate", "mlp_up"
[H, I], "mlp_down" [I, H] or (sparse) "router" [H, E], "bias" [E], "gate",
"up" [held, H, F], "down" [held, F, H], "shared_gate", "shared_up" [H, Fs],
"shared_down" [Fs, H].
"""

import jax
import jax.numpy as jnp

from benchmark.reference.laguna import dense_mlp, norm
from benchmark.reference.olmoe import grad_norm, head_nll_sum  # noqa: F401
from benchmark.reference.qwen3_next import pinned

F32 = jnp.float32


def rope_in_place(x, theta):
    """x [..., S, d]: the pair (2i, 2i+1) of the last axis turned by
    ``pos x theta^(-2i/d)`` at positions 0..S-1; the columns stay where
    they are."""
    S, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]     # [S, d / 2]
    cos, sin = (jnp.repeat(f(ang), 2, axis=-1) for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    partner = jnp.stack([-odd, even], axis=-1).reshape(x.shape)
    return x * cos + partner * sin


def attention(x, p, *, n_head, nope, rope_dim, v_dim, theta, eps,
              latent_norm=True, rotate=True, shared_rope_key=True,
              scale_dim=None, q_block=256, head_group=8):
    """The latent-attention branch. ``latent_norm``, ``rotate``,
    ``shared_rope_key`` (False: every head but the first sees a zero rotated
    key — what a key part that is not broadcast would read) and
    ``scale_dim`` (the width under the score's square root, default nope +
    rope_dim) exist so that the tests can show each omission failing the
    check."""
    B, S, _ = x.shape
    H, R = n_head, p["kv_a_norm"].shape[0]
    down = x @ p["kv_a"]
    c, k_r = down[..., :R], down[..., R:]
    if latent_norm:
        c = norm(c, p["kv_a_norm"], eps)
    if rotate:
        k_r = rope_in_place(k_r, theta)             # [B, S, r]: ONE a token
    scale = float(scale_dim or nope + rope_dim) ** -0.5
    step, G = min(q_block, S), min(head_group, H)

    # for memory only: ``head_group`` heads at a time — their columns of W_q
    # and W_kvb, so that no array holds all heads' q, k and v at once — and
    # within them blocks of query rows against ALL keys, each recomputed in
    # the backward pass
    @jax.checkpoint
    def group(x, c, k_r, w_q, w_kvb, sees_rope_key):
        q = jnp.einsum("bsh,hgd->bgsd", x, w_q)            # [B, G, S, .]
        kv = jnp.einsum("bsr,rgd->bgsd", c, w_kvb)
        q_rope = q[..., nope:]
        if rotate:
            q_rope = rope_in_place(q_rope, theta)
        k_rope = jnp.broadcast_to(k_r[:, None], (B, G, S, rope_dim)) \
            * sees_rope_key[None, :, None, None]
        q = jnp.concatenate([q[..., :nope], q_rope], -1)
        k = jnp.concatenate([kv[..., :nope], k_rope], -1)  # [k_nope ; k_r]
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

    def by_group(w, width):                         # [., H width] -> groups
        return w.reshape(w.shape[0], H // G, G, width).transpose(1, 0, 2, 3)

    sees = jnp.ones((H,), F32) if shared_rope_key \
        else (jnp.arange(H) == 0).astype(F32)
    ctx = jax.lax.map(
        lambda xs: group(x, c, k_r, *xs),
        (by_group(p["q"], nope + rope_dim), by_group(p["kv_b"], nope + v_dim),
         sees.reshape(H // G, G)))                  # [H / G, B, G, S, v]
    ctx = ctx.transpose(1, 3, 0, 2, 4).reshape(B, S, H * v_dim)
    return ctx @ p["o"]


def experts(h, p, k, lo, *, routed_scale=2.448, norm_topk_prob=True,
            score="sigmoid", use_choice_bias=True, bias_in_weights=False,
            act=jax.nn.silu, gated=True, shared="plain", chosen=None):
    """(output [T, H], experts used [T, k], this router's own choice) of the
    expert branch's input ``h`` [T, H]. ``chosen`` [T, k], when given,
    replace the router's own choice (``benchmark/reference/olmoe.forward``
    says why); the weights are still this router's scores of them. The
    other keywords are the tests' omissions: ``shared`` "plain" |
    "missing"; ``gated`` False: an expert is ``down(act(up(x)))``."""
    logits = h @ p["router"]
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    pick = s + p["bias"] if use_choice_bias else s
    _, own_e = jax.lax.top_k(pick, k)
    top_e = own_e if chosen is None else chosen
    top_w = jnp.take_along_axis(pick if bias_in_weights else s, top_e, axis=1)
    if norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * routed_scale
    T, E = s.shape
    dense = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], top_e].add(top_w)
    held = p["up"].shape[0]

    def unit(gate, up, down):
        return ((act(h @ gate) * (h @ up)) if gated else act(h @ up)) @ down

    @jax.checkpoint
    def one_expert(gate, up, down, w):
        return w[:, None] * unit(gate, up, down)

    y, _ = jax.lax.scan(lambda y, xs: (y + one_expert(*xs), None),
                        jnp.zeros_like(h),
                        (p["gate"], p["up"], p["down"],
                         dense[:, lo:lo + held].T))
    if shared != "missing":
        y = y + unit(p["shared_gate"], p["shared_up"], p["shared_down"])
    return y, top_e, own_e


def forward(top, layers, ids, *, n_head, nope, rope_dim, v_dim, theta, eps,
            k, expert_lo=0, routed_scale=2.448, norm_topk_prob=True,
            attn_over=None, experts_over=None, chosen=None, streams=None):
    """(loss, detail): detail holds the cross-entropy and per layer the
    experts used and the router's own choice (None for a dense layer), the
    residual stream after the mixer and the two branches' outputs.

    ``attn_over`` and ``experts_over`` (keyword switches of ``attention`` /
    ``experts``) exist so that the tests can show each omission failing the
    check. ``chosen`` / ``streams``: the two pins of
    ``benchmark/reference/qwen3_next.forward`` — per layer the experts a
    token is sent to (None for a dense layer), and per layer (the residual
    stream after the mixer, after the FFN) of ANOTHER run of the same
    weights and batch, whose values each layer then starts from with this
    model's gradients."""
    B, S = ids.shape

    # for memory only: a layer recomputed whole in the backward pass (its
    # two branches again inside it), so that what a layer keeps is its input
    def layer(x, p, experts_pin, stream_pin):
        h = norm(x, p["input_norm"], eps)
        mixed = jax.checkpoint(lambda h, p: attention(
            h, p, n_head=n_head, nope=nope, rope_dim=rope_dim, v_dim=v_dim,
            theta=theta, eps=eps, **(attn_over or {})))(h, p)
        x = x + mixed
        if stream_pin is not None:
            x = pinned(x, stream_pin[0])
        x_mid = x
        h = norm(x, p["post_attn_norm"], eps).reshape(B * S, -1)
        if "mlp_gate" in p:
            out, top_e, own_e = jax.checkpoint(dense_mlp)(h, p), None, None
        else:
            out, top_e, own_e = jax.checkpoint(
                lambda h, p, e: experts(
                    h, p, k, expert_lo, routed_scale=routed_scale,
                    norm_topk_prob=norm_topk_prob, chosen=e,
                    **(experts_over or {})))(h, p, experts_pin)
        out = out.reshape(x.shape)
        x = x + out
        if stream_pin is not None:
            x = pinned(x, stream_pin[1])
        return x, {"top_e": top_e, "own_top_e": own_e, "x_mid": x_mid,
                   "mixer_out": mixed, "ffn_out": out}

    x = top["embed"][ids]
    per_layer = []
    for i, p in enumerate(layers):
        x, row = jax.checkpoint(layer)(
            x, p, None if chosen is None else chosen[i],
            None if streams is None else streams[i])
        per_layer.append(row)
    ce = head_nll_sum(x, top["norm"], top["lm_head"], ids, eps) \
        / (B * (S - 1))
    return ce, {"ce": ce, "layers": per_layer}


def loss(weights, ids, view=lambda w: w, **sizes):
    """(loss, detail) of ``forward`` at full matmul precision; ``view``
    turns the caller's ``weights`` into ``(top, layers)``."""
    with jax.default_matmul_precision("highest"):
        return forward(*view(weights), ids, **sizes)


def loss_and_grads(weights, ids, view=lambda w: w, **sizes):
    """((loss, detail), gradients shaped like ``weights``)."""
    return jax.value_and_grad(
        lambda w: loss(w, ids, view, **sizes), has_aux=True)(weights)
