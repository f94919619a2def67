"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``) as published,
in plain float32 ``jax.numpy``: the yardstick.

Written from the published ``config.json`` and ISSUE 40's layer equations,
and independent of ``deepspeed_tpu/``: no kernel, no chunked scan, no sort,
no grouped matmul, no sharding. Every matmul runs under
``jax.default_matmul_precision("highest")``.

    every layer l, x the residual stream [B, S, H]:  x += f_l(norm(x; w_l))
    with f_l read from the pattern string, one character a layer:

    M   [z | xBC | dt] = h W_in      (d_inner | d_inner + 2 G N | heads)
        xBC = silu(conv(xBC) + b)    causal, depthwise, ``conv_kernel`` taps
        x, B, C = xBC                x [heads, P], B and C [G, N]: a group's
                                     B_t, C_t serve its heads / G heads
        dt  = softplus(dt + dt_bias);  A = -exp(A_log), one scalar a head
        h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t        TOKEN BY TOKEN,
        y_t = h_t C_t + D x_t                               state [P, N]
        y   = groupnorm(y * silu(z)) W_out   the gate BEFORE the norm, the
              norm (RMS, weight w) over groups of d_inner / G channels
    *   q, k, v = h W_q, h W_k, h W_v (no bias, NO rotation);
        a = softmax(q k^T / sqrt(head_dim) + causal mask) v, query head n
        reads KV head n // (n_head / n_kv_head);  a W_o
    E   s = sigmoid(h W_r) over ALL E experts; the k experts with the
        largest s + bias; g = s at them (WITHOUT the bias), / their sum
        (``norm_topk_prob``), x ``routed_scale``;
        y = sum over the chosen experts HELD here of
            g_e * relu(h W_u^e)^2 W_d^e      (no gate: two matrices)
          + relu(h W_us)^2 W_ds              the shared expert, ungated
    norm: x / sqrt(mean(x^2) + eps) * w;  final norm; an untied head.
    loss: next-token cross-entropy over the held slice of the vocabulary;
    no auxiliary term (the config has none).

An ``E`` layer HOLDS experts [lo, lo + held) (``held`` is the leading size
of its expert weights): every held expert is applied to every token and
masked by the weights, nothing is routed, and what the absent experts would
have added is left out; the shared expert is whole.

For MEMORY only (same arithmetic): the recurrence runs in SEGMENTS of
``segment`` tokens, each recomputed in the backward pass from the state at
its start (a backward pass through 16,384 kept states would be 34 GB a
layer), and an ``M`` branch in three stages (projection and convolution,
the recurrence, gate and norm) each recomputed alone; attention as ``benchmark/reference/smallthinker.attention`` has it;
the experts in a scan; the head in chunks of tokens; each layer's branch
checkpointed whole.

Weights (float32): top = {"embed": [V, H], "norm": [H], "lm_head": [V, H]};
a layer has "norm" [H] and, by kind, M: "in_proj" [H, 2 d_inner + 2 G N +
heads], "conv" [taps, d_inner + 2 G N], "conv_bias", "A_log", "dt_bias",
"D" [heads], "ssm_norm" [d_inner], "out_proj" [d_inner, H]; *: "q", "k",
"v", "o"; E: "router" [H, E], "bias" [E], "up" [held, H, F], "down"
[held, F, H], "shared_up" [H, Fs], "shared_down" [Fs, H].
"""

import jax
import jax.numpy as jnp

from benchmark.reference.olmoe import grad_norm, head_nll_sum  # noqa: F401
from benchmark.reference.qwen3_next import pinned
from benchmark.reference.smallthinker import attention, norm

F32 = jnp.float32


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def conv(x, taps, bias):
    """[B, S, C] through a causal depthwise convolution, ``taps`` [W, C]:
    tap W - 1 reads the token itself, tap 0 the one W - 1 before it."""
    W, S = taps.shape[0], x.shape[1]
    y = sum(jnp.pad(x, ((0, 0), (W - 1 - j, 0), (0, 0)))[:, :S] * taps[j]
            for j in range(W))
    return y if bias is None else y + bias


def recurrence(x, dt, A, Bm, Cm, D, segment=128):
    """y [B, S, heads, P] of the state-space recurrence, token by token
    from a zero state. x [B, S, heads, P]; dt [B, S, heads]; A, D [heads]
    (D None: no skip); Bm, Cm [B, S, G, N]: group g's B_t, C_t serve heads
    [g * heads / G, (g + 1) * heads / G) — broadcast inside a step, never
    repeated in memory."""
    B, S, heads, P = x.shape
    G, N = Bm.shape[2:]
    R = heads // G
    A = A.reshape(G, R)

    def token(h, xs):
        x_t, dt_t, B_t, C_t = xs            # [B, G R P], [B, G R], [B, G, N]
        x_t, dt_t = x_t.reshape(B, G, R, P), dt_t.reshape(B, G, R)
        h = h * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, None, :]
        y = jnp.sum(h * C_t[:, :, None, None, :], axis=-1)
        return h, y.reshape(B, heads * P)

    @jax.checkpoint
    def run(h, xs):
        return jax.lax.scan(token, h, xs)

    pad = (-S) % segment
    xs = tuple(jnp.moveaxis(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (
        t.ndim - 2)), 1, 0) for t in (x.reshape(B, S, heads * P), dt, Bm, Cm))
    xs = tuple(t.reshape(-1, segment, *t.shape[1:]) for t in xs)
    _, y = jax.lax.scan(run, jnp.zeros((B, G, R, P, N), F32), xs)
    y = jnp.moveaxis(y.reshape(-1, *y.shape[2:]), 0, 1)[:, :S]
    y = y.reshape(B, S, heads, P)
    return y if D is None else y + D[:, None] * x


def mamba(h, p, *, heads, head_dim, n_groups, state, eps, use_D=True,
          use_dt_bias=True, use_softplus=True, gate_before_norm=True,
          norm_groups=None, use_conv_bias=True):
    """The ``M`` branch, in three stages each recomputed alone in the
    backward pass (memory only). The keyword switches are the tests'
    omissions; ``norm_groups`` None: ``n_groups``."""
    B, S, _ = h.shape
    d_inner, GN = heads * head_dim, n_groups * state

    @jax.checkpoint
    def project(h, p):
        zxbcdt = h @ p["in_proj"]
        xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * GN]
        xBC = jax.nn.silu(conv(xBC, p["conv"],
                               p["conv_bias"] if use_conv_bias else None))
        dt = zxbcdt[..., 2 * d_inner + 2 * GN:]
        if use_dt_bias:
            dt = dt + p["dt_bias"]
        if use_softplus:
            dt = jax.nn.softplus(dt)
        return zxbcdt[..., :d_inner], xBC, dt

    @jax.checkpoint
    def scan(xBC, dt, p):
        x = xBC[..., :d_inner].reshape(B, S, heads, head_dim)
        Bm, Cm = (t.reshape(B, S, n_groups, state) for t in (
            xBC[..., d_inner:d_inner + GN], xBC[..., d_inner + GN:]))
        return recurrence(x, dt, -jnp.exp(p["A_log"]), Bm, Cm,
                          p["D"] if use_D else None).reshape(B, S, d_inner)

    groups = n_groups if norm_groups is None else norm_groups

    def grouped(t, w):
        t = t.reshape(B, S, groups, d_inner // groups)
        t = t * jax.lax.rsqrt(jnp.mean(jnp.square(t), axis=-1,
                                       keepdims=True) + eps)
        return t.reshape(B, S, d_inner) * w

    @jax.checkpoint
    def gate_and_project(y, z, p):
        y = grouped(y * jax.nn.silu(z), p["ssm_norm"]) if gate_before_norm \
            else grouped(y, p["ssm_norm"]) * jax.nn.silu(z)
        return y @ p["out_proj"]

    z, xBC, dt = project(h, p)
    return gate_and_project(scan(xBC, dt, p), z, p)


def experts(h, p, k, lo, *, routed_scale=2.5, norm_topk_prob=True,
            score="sigmoid", use_choice_bias=True, bias_in_weights=False,
            act=relu2, gated=False, shared="plain", chosen=None):
    """(output [T, H], experts used [T, k], this router's own choice) of the
    ``E`` branch's input ``h`` [T, H]. ``chosen`` [T, k], when given,
    replace the router's own choice (``benchmark/reference/olmoe.forward``
    says why); the weights are still this router's scores of them. The
    other keywords are the tests' omissions: ``shared`` "plain" | "gated"
    (under a sigmoid gate read from the shared expert's first column) |
    "missing"; ``gated``: an expert's ``act(u) * u``."""
    logits = h @ p["router"]
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    pick = s + p["bias"] if use_choice_bias else s
    _, own_e = jax.lax.top_k(pick, k)
    top_e = own_e if chosen is None else chosen
    top_w = jnp.take_along_axis(pick if bias_in_weights else s, top_e, axis=1)
    if norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * routed_scale
    T, E = s.shape
    dense = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], top_e].add(top_w)
    held = p["up"].shape[0]

    def unit(up, down):
        u = h @ up
        return (act(u) * u if gated else act(u)) @ down

    @jax.checkpoint
    def one_expert(up, down, w):
        return w[:, None] * unit(up, down)

    y, _ = jax.lax.scan(lambda y, xs: (y + one_expert(*xs), None),
                        jnp.zeros_like(h),
                        (p["up"], p["down"], dense[:, lo:lo + held].T))
    if shared != "missing":
        ys = unit(p["shared_up"], p["shared_down"])
        if shared == "gated":
            ys = ys * jax.nn.sigmoid(h @ p["shared_up"][:, :1])
        y = y + ys
    return y, top_e, own_e


def forward(top, layers, ids, *, pattern, n_kv_head, head_dim, eps, heads,
            mamba_head_dim, n_groups, state, k, expert_lo=0, routed_scale=2.5,
            norm_topk_prob=True, theta=None, mamba_over=None,
            experts_over=None, chosen=None, streams=None):
    """(loss, detail): detail holds the cross-entropy and per layer its
    branch's output and, for an ``E`` layer, the experts used and the
    router's own choice.

    ``theta`` (a rotation of q and k, which the model does NOT apply),
    ``mamba_over`` and ``experts_over`` (keyword switches of ``mamba`` /
    ``experts``) exist so that the tests can show each omission failing the
    check. ``chosen`` / ``streams``: the two pins of
    ``benchmark/reference/qwen3_next.forward`` — per ``E`` layer (None
    elsewhere) the experts a token is sent to, and per layer the residual
    stream AFTER the layer of another run of the same weights and batch,
    whose values the next layer then starts from with this model's
    gradients."""
    B, S = ids.shape
    x = top["embed"][ids]
    per_layer = []
    for i, (kind, p) in enumerate(zip(pattern, layers)):
        h = norm(x, p["norm"], eps)
        row = {}
        if kind == "M":
            out = jax.checkpoint(lambda h, p: mamba(
                h, p, heads=heads, head_dim=mamba_head_dim,
                n_groups=n_groups, state=state, eps=eps,
                **(mamba_over or {})))(h, p)
        elif kind == "*":
            out = jax.checkpoint(lambda h, p: attention(
                h, p, n_kv_head=n_kv_head, head_dim=head_dim,
                theta=theta))(h, p)
        else:
            out, row["top_e"], row["own_top_e"] = jax.checkpoint(
                lambda h, p, e: experts(
                    h, p, k, expert_lo, routed_scale=routed_scale,
                    norm_topk_prob=norm_topk_prob, chosen=e,
                    **(experts_over or {})))(
                h.reshape(B * S, -1), p, None if chosen is None else chosen[i])
            out = out.reshape(x.shape)
        x = x + out
        if streams is not None:
            x = pinned(x, streams[i])
        per_layer.append(dict(row, branch_out=out))
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
