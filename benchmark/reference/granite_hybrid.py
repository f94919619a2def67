"""ibm-granite/granite-4.0-h-micro (``model_type: granitemoehybrid``) as
published, in plain float32 ``jax.numpy``: the yardstick.

Written from the published ``config.json`` and ISSUE 50's layer equations,
and independent of ``deepspeed_tpu/`` and of the other references: no
kernel, no chunked scan, no sharding. Every matmul runs under
``jax.default_matmul_precision("highest")``.

    x = embedding_multiplier * E[ids]              the residual stream
    every layer:  x += residual_multiplier * mixer(norm(x; w_in))
                  x += residual_multiplier * mlp(norm(x; w_post))
    mlp(u) = (silu(g) * p) W_out,   [g | p] = u W_in     (one input matrix)

    mixer "mamba":
        [z | xBC | dt] = u W_in      (d_inner | d_inner + 2 G N | heads)
        xBC = silu(conv(xBC) + b)    causal, depthwise, ``conv`` taps
        x, B, C = xBC                x [heads, P]; B, C [G, N], G = 1 as
                                     published: ONE B_t, C_t for all heads
        dt  = softplus(dt + dt_bias) (no clamp); A = -exp(A_log) a head
        S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t        TOKEN BY TOKEN,
        y_t = S_t C_t + D x_t                               state [P, N]
        y   = rmsnorm(y * silu(z); w) W_out  the gate BEFORE the norm, the
              norm over groups of d_inner / G channels: all of them
    mixer "attention":
        q, k, v = u W_q, u W_k, u W_v (no bias, NO rotation)
        a = softmax(q k^T * attention_multiplier + causal mask) v, query
        head n reads KV head n // (n_head / n_kv_head);  a W_o

    norm: x / sqrt(mean(x^2) + eps) * w
    logits = norm(x_L; w_f) E^T / logits_scaling      (the head IS E)
    loss: next-token cross-entropy, mean over tokens, over the held slice
    of the vocabulary.

For MEMORY only (same arithmetic): the recurrence runs in SEGMENTS of
``segment`` tokens, each recomputed in the backward pass from the state at
its start (16,384 kept states of 64 x 64 x 128 would be 34 GB a layer) —
every token still its own step: no chunked form, no matmul over a segment;
a Mamba branch in three stages each recomputed alone; attention a KV head's
group of query heads at a time in blocks of query rows against all keys,
so that S x S scores never stand whole; the MLP and the head in chunks of
tokens.

Weights (float32): top = {"embed": [V, H], "norm": [H]}; a layer has
"in_norm", "post_norm" [H], "mlp_in" [H, 2 F], "mlp_out" [F, H] and, by
kind, mamba: "in_proj" [H, 2 d_inner + 2 G N + heads], "conv" [taps,
d_inner + 2 G N], "conv_bias", "A_log", "dt_bias", "D" [heads], "ssm_norm"
[d_inner], "out_proj" [d_inner, H]; attention: "q", "k", "v", "o".

``forward`` is the model's own pass. ``pinned_backward`` is the gradient
of the same loss with every branch started from ANOTHER run's residual
stream (its values, this model's derivatives), walked a branch at a time
from the head down so that no more than one branch's activations and one
layer's gradients are alive at once.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def conv(x, taps, bias):
    """[B, S, C] through a causal depthwise convolution, ``taps`` [W, C]:
    tap W - 1 reads the token itself, tap 0 the one W - 1 before it."""
    W, S = taps.shape[0], x.shape[1]
    y = sum(jnp.pad(x, ((0, 0), (W - 1 - j, 0), (0, 0)))[:, :S] * taps[j]
            for j in range(W))
    return y if bias is None else y + bias


def recurrence(x, dt, A, Bm, Cm, D, segment=128, state_dtype=F32):
    """y [B, S, heads, P] of the state-space recurrence, token by token
    from a zero state. x [B, S, heads, P]; dt [B, S, heads]; A, D [heads]
    (D None: no skip); Bm, Cm [B, S, G, N]: group g's B_t, C_t serve heads
    [g * heads / G, (g + 1) * heads / G). ``state_dtype``: what the state
    is rounded to after every token (the tests' omission)."""
    B, S, heads, P = x.shape
    G, N = Bm.shape[2:]
    R = heads // G
    A = A.reshape(G, R)

    def token(h, xs):
        x_t, dt_t, B_t, C_t = xs            # [B, G R P], [B, G R], [B, G, N]
        x_t, dt_t = x_t.reshape(B, G, R, P), dt_t.reshape(B, G, R)
        h = h * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, None, :]
        h = h.astype(state_dtype).astype(F32)
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


def mamba(u, p, *, heads, head_dim, n_groups, state, eps, use_D=True,
          use_dt_bias=True, use_conv_bias=True, gate_before_norm=True,
          norm_groups=None, state_dtype=F32):
    """The Mamba-2 mixer, in three stages each recomputed alone in the
    backward pass (memory only). The keyword switches are the tests'
    omissions; ``norm_groups`` None: ``n_groups``."""
    B, S, _ = u.shape
    d_inner, GN = heads * head_dim, n_groups * state

    @jax.checkpoint
    def project(u, p):
        zxbcdt = u @ p["in_proj"]
        xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * GN]
        xBC = jax.nn.silu(conv(xBC, p["conv"],
                               p["conv_bias"] if use_conv_bias else None))
        dt = zxbcdt[..., 2 * d_inner + 2 * GN:]
        if use_dt_bias:
            dt = dt + p["dt_bias"]
        return zxbcdt[..., :d_inner], xBC, jax.nn.softplus(dt)

    @jax.checkpoint
    def scan(xBC, dt, p):
        x = xBC[..., :d_inner].reshape(B, S, heads, head_dim)
        Bm, Cm = (t.reshape(B, S, n_groups, state) for t in (
            xBC[..., d_inner:d_inner + GN], xBC[..., d_inner + GN:]))
        return recurrence(x, dt, -jnp.exp(p["A_log"]), Bm, Cm,
                          p["D"] if use_D else None,
                          state_dtype=state_dtype).reshape(B, S, d_inner)

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

    z, xBC, dt = project(u, p)
    return gate_and_project(scan(xBC, dt, p), z, p)


def attention(u, p, *, n_kv_head, head_dim, scale, q_block=256):
    """Causal softmax attention without rotation, the scores times
    ``scale``."""
    B, S, _ = u.shape
    D = head_dim
    n_head = p["q"].shape[1] // D
    rep = n_head // n_kv_head       # consecutive query heads a KV head serves
    q = (u @ p["q"]).reshape(B, S, n_kv_head, rep, D)
    k = (u @ p["k"]).reshape(B, S, n_kv_head, 1, D)
    v = (u @ p["v"]).reshape(B, S, n_kv_head, 1, D)
    step = min(q_block, S)

    @jax.checkpoint
    def group(q, k, v):                                 # [B, S, heads, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

        @jax.checkpoint
        def rows(q_blk, start):
            scores = q_blk @ k.transpose(0, 1, 3, 2) * scale
            seen = (start + jnp.arange(q_blk.shape[2]))[:, None] \
                >= jnp.arange(S)[None, :]
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
    ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(B, S, n_head * D)
    return ctx @ p["o"]


def mlp(u, p, chunk=4096):
    """``(silu(g) * p) W_out`` with ``[g | p] = u W_in``, a chunk of tokens
    at a time (memory only)."""
    B, S, H = u.shape
    F = p["mlp_out"].shape[0]
    rows = u.reshape(B * S, H)
    pad = (-rows.shape[0]) % chunk

    @jax.checkpoint
    def part(r):
        gp = r @ p["mlp_in"]
        return (jax.nn.silu(gp[:, :F]) * gp[:, F:]) @ p["mlp_out"]

    out = jax.lax.map(part, jnp.pad(rows, ((0, pad), (0, 0))).reshape(
        -1, chunk, H))
    return out.reshape(-1, H)[:B * S].reshape(B, S, H)


def head_loss(x, top, ids, *, eps, logits_scaling=8.0, chunk=2048):
    """Mean over positions of -log p(next token); a sequence's last
    position has no target. Chunks of tokens one after the other, each
    recomputed in backward: the [tokens, vocabulary] logits never exist."""
    B, S, H = x.shape
    xs = norm(x[:, :-1], top["norm"], eps).reshape(-1, H)
    tgt = ids[:, 1:].reshape(-1)
    pad = (-xs.shape[0]) % chunk
    xs = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, chunk, H)
    live = jnp.pad(jnp.ones_like(tgt, F32), (0, pad)).reshape(-1, chunk)
    tgt = jnp.pad(tgt, (0, pad)).reshape(-1, chunk)

    @jax.checkpoint
    def part(xc, tc, mc):
        logp = jax.nn.log_softmax(xc @ top["embed"].T / logits_scaling,
                                  axis=-1)
        picked = jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * mc)

    total, _ = jax.lax.scan(lambda acc, c: (acc + part(*c), None),
                            jnp.zeros((), F32), (xs, tgt, live))
    return total / (B * (S - 1))


def branches(kind, *, n_kv_head, head_dim, eps, heads, mamba_head_dim,
             n_groups, state, attention_multiplier=0.015625,
             mamba_over=None, **_):
    """(mixer, mlp): each ``f(x, p) -> the branch's output`` on the stream
    ``x`` it starts from, before the residual multiplier, recomputed whole
    in the backward pass. ``mamba_over``: keyword switches of ``mamba``, a
    dict or its items."""
    def mixer(x, p):
        u = norm(x, p["in_norm"], eps)
        if kind == MAMBA:
            return mamba(u, p, heads=heads, head_dim=mamba_head_dim,
                         n_groups=n_groups, state=state, eps=eps,
                         **dict(mamba_over or {}))
        return attention(u, p, n_kv_head=n_kv_head, head_dim=head_dim,
                         scale=attention_multiplier)

    def feed_forward(x, p):
        return mlp(norm(x, p["post_norm"], eps), p)

    return jax.checkpoint(mixer), jax.checkpoint(feed_forward)


def embed(top, ids, embedding_multiplier=12.0):
    return embedding_multiplier * top["embed"][ids]


def forward(top, layers, ids, *, layer_types, embedding_multiplier=12.0,
            residual_multiplier=0.22, logits_scaling=8.0, look=None, **sizes):
    """(loss, detail): detail holds per layer the stream it starts from and
    its two branches' outputs — or what ``look(i, those)`` makes of them,
    which the next layer then waits for (memory only: 400 MB a layer are
    let go before the next is computed). The multipliers,
    ``attention_multiplier`` and ``mamba_over`` (keyword switches of
    ``mamba``) among ``sizes`` are arguments so that the tests can show
    each omission failing the check."""
    x = embed(top, ids, embedding_multiplier)
    per_layer = []
    for i, (kind, p) in enumerate(zip(layer_types, layers)):
        mixer, feed_forward = branches(kind, **sizes)
        row = {"x_in": x}
        row["mixer_out"] = mixer(x, p)
        x = x + residual_multiplier * row["mixer_out"]
        row["mlp_out"] = feed_forward(x, p)
        x = x + residual_multiplier * row["mlp_out"]
        if look is not None:
            x, row = jax.lax.optimization_barrier((x, look(i, row)))
        per_layer.append(row)
    ce = head_loss(x, top, ids, eps=sizes["eps"],
                   logits_scaling=logits_scaling)
    return ce, {"ce": ce, "layers": per_layer, "x_out": x}


def loss(weights, ids, view=lambda w: w, **sizes):
    """(loss, detail) of ``forward`` at full matmul precision; ``view``
    turns the caller's ``weights`` into ``(top, layers)``."""
    with jax.default_matmul_precision("highest"):
        return forward(*view(weights), ids, **sizes)


def loss_and_grads(weights, ids, view=lambda w: w, **sizes):
    """((loss, detail), gradients shaped like ``weights``)."""
    return jax.value_and_grad(
        lambda w: loss(w, ids, view, **sizes), has_aux=True)(weights)


def logits(weights, ids, view=lambda w: w, **sizes):
    """[B, S, V] (small sizes only: the tests)."""
    with jax.default_matmul_precision("highest"):
        top, layers = view(weights)
        _, detail = forward(top, layers, ids, **sizes)
        return norm(detail["x_out"], top["norm"], sizes["eps"]) \
            @ top["embed"].T / sizes.get("logits_scaling", 8.0)


def grad_norm(grads):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))


def pinned_backward(top, layers, ids, other, fold, *, layer_types,
                    embedding_multiplier=12.0, residual_multiplier=0.22,
                    logits_scaling=8.0, **sizes):
    """The gradients of the loss with every branch started from the residual
    stream of ANOTHER run of the same weights and batch — ``other``: per
    layer that run's {"x_in" (the stream the layer starts from),
    "mixer_out", "mlp_out" (its two branches, before the multiplier)} — its
    values, this model's derivatives, walked from the head down a branch at
    a time. A layer's mixer starts from the run's ``x_in``, its MLP from
    ``x_in + r mixer_out``, the head from the last layer's ``... + r
    mlp_out``. A layer's gradients and its two branches' outputs at those
    streams are handed to ``fold(i, kind, gradients, mixer_out, mlp_out)``
    as soon as they are whole and what it returns is kept in their place;
    the top's the same, ``fold(None, None, gradients, None, None)``.
    Returns (loss at the last stream, [what ``fold`` returned a layer],
    what it returned for the top)."""
    with jax.default_matmul_precision("highest"):
        r = residual_multiplier

        def after_mixer(row):
            return row["x_in"].astype(F32) + r * row["mixer_out"].astype(F32)

        last = after_mixer(other[-1]) + r * other[-1]["mlp_out"].astype(F32)
        ce, back = jax.vjp(lambda t, x: head_loss(
            x, t, ids, eps=sizes["eps"], logits_scaling=logits_scaling),
            top, last)
        g_top, c = back(jnp.ones((), F32))
        folded = [None] * len(layers)
        for i in reversed(range(len(layers))):
            kind, p = layer_types[i], layers[i]
            mixer, feed_forward = branches(kind, **sizes)
            # for memory only: a branch's forward pass waits for the
            # cotangent that its backward pass needs (nothing else orders
            # the twenty forward passes, and each leaves 134 MB behind)
            row, c = jax.lax.optimization_barrier((other[i], c))
            mlp_out, back = jax.vjp(feed_forward, after_mixer(row), p)
            dx, g_mlp = back(r * c)
            row, c = jax.lax.optimization_barrier((row, c + dx))
            mixer_out, back = jax.vjp(mixer, row["x_in"].astype(F32), p)
            dx, g_mixer = back(r * c)
            c = c + dx
            # each branch's gradient of the other's leaves is zero
            grads = jax.tree_util.tree_map(jnp.add, g_mlp, g_mixer)
            # ... and the layer below waits for what ``fold`` makes of this
            # one's gradients
            c, folded[i] = jax.lax.optimization_barrier(
                (c, fold(i, kind, grads, mixer_out, mlp_out)))
        # the embedding is read twice: as the head and, times the
        # multiplier, as the rows of the stream's start
        g_top = dict(g_top, embed=g_top["embed"] + jnp.zeros_like(
            top["embed"]).at[ids].add(embedding_multiplier * c))
        return ce, folded, fold(None, None, g_top, None, None)
