"""allenai/Olmo-Hybrid-7B (``model_type: olmo_hybrid``) as published, in plain
float32 ``jax.numpy``: the yardstick.

Written from the published ``config.json`` and ISSUE 68's layer equations,
and independent of ``deepspeed_tpu/`` and of the other references: no
kernel, no chunked scan, no sharding. Every matmul runs under
``jax.default_matmul_precision("highest")``.

    x = E[ids]                                      the residual stream
    every layer:  x += norm(mixer(x); w_attn)       OLMo 2 / 3's reordered
                  x += norm(mlp(x); w_ffn)          norms: on the OUTPUT
    mlp(u) = (silu(u W_gate) * (u W_up)) W_down

    mixer "linear_attention" (Gated DeltaNet, H heads of Dk x Dv):
        [q | k | v | z] = u W_qkvz      (H Dk | H Dk | H Dv | H Dv)
        [b | a]         = u W_ba        (H | H)
        q, k, v = silu(conv([q | k | v]))    causal, depthwise, no bias
        q = l2norm_head(q) * Dk^-1/2,  k = l2norm_head(k)      (eps 1e-6)
        beta = 2 sigmoid(b)             in (0, 2): linear_allow_neg_eigval
        g    = -exp(A_log) * softplus(a + dt_bias)
        per head, S in R^(Dk x Dv) from zero, TOKEN BY TOKEN:
            S <- exp(g_t) S
            S <- S + k_t (beta_t (v_t - S^T k_t))^T
            o_t = S^T q_t
        y = (rmsnorm_Dv(o) * w_gdn * silu(z)) W_out    one w for all heads
    mixer "full_attention":
        q = norm(u W_q; w_q), k = norm(u W_k; w_k)   over the WHOLE
        projection, v = u W_v; H heads of D, each query head its own KV
        head; NO rotation; a = softmax(q k^T D^-1/2 + causal mask) v; a W_o

    norm(x; w) = x / sqrt(mean(x^2) + eps) * w
    logits = norm(x_L; w_f) W_head^T        (the head is its own matrix)
    loss: next-token cross-entropy, mean over tokens, over the held slice
    of the vocabulary.

For MEMORY only (same arithmetic): the recurrence runs in SEGMENTS of
``segment`` tokens, each recomputed in the backward pass from the state at
its start — every token still its own step: no chunked form, no matmul over
a segment; attention in blocks of query rows against all keys, so that S x S
scores never stand whole; the MLP and the head in chunks of tokens.

``CONTROLS`` names the faults of the mathematics this file can be asked for
(``fault=``): what ``benchmark.tools.reference_controls`` judges the honest
system against, each of which has to read NOT correct.

Weights (float32): top = {"embed", "lm_head": [V, H], "norm": [H]}; a layer
has "attn_norm", "ffn_norm" [H], "gate", "up" [H, F], "down" [F, H] and, by
kind, linear_attention: "in_qkvz" [H, 2 H Dk + 2 H Dv], "in_ba" [H, 2 H],
"conv" [taps, 2 H Dk + H Dv], "A_log", "dt_bias" [heads], "gdn_norm" [Dv],
"out" [H Dv, H]; full_attention: "q", "k", "v", "o" [H, H], "q_norm",
"k_norm" [H].

``forward`` is the model's own pass. ``pinned_backward`` is the gradient of
the same loss with every branch started from ANOTHER run's residual stream
(its values, this model's derivatives), walked a branch at a time from the
head down so that no more than one branch's activations and one layer's
gradients are alive at once.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
CONTROLS = ("beta_sigmoid", "norms_on_inputs", "qk_norm_per_head",
            "rotation_added", "q_scale_left_out", "output_gate_dropped")


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def conv(x, taps):
    """[B, S, C] through a causal depthwise convolution, ``taps`` [W, C]:
    tap W - 1 reads the token itself, tap 0 the one W - 1 before it."""
    W, S = taps.shape[0], x.shape[1]
    return sum(jnp.pad(x, ((0, 0), (W - 1 - j, 0), (0, 0)))[:, :S] * taps[j]
               for j in range(W))


def delta_rule(q, k, v, g, beta, segment=64):
    """o [B, S, H, Dv] of the gated delta rule, token by token from a zero
    state. q, k [B, S, H, Dk]; v [B, S, H, Dv]; g, beta [B, S, H]."""
    B, S, H, Dk = q.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs                        # [B, H, ...]
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., :, None] \
            * (b_t[..., None] * (v_t - read))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def run(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = (-S) % segment
    xs = tuple(jnp.moveaxis(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (
        t.ndim - 2)), 1, 0) for t in (q, k, v, g, beta))
    xs = tuple(t.reshape(-1, segment, *t.shape[1:]) for t in xs)
    _, o = jax.lax.scan(run, jnp.zeros((B, H, Dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(o.reshape(-1, *o.shape[2:]), 0, 1)[:, :S]


def delta_net(u, p, *, heads, dk, dv, eps, fault=None):
    """The Gated DeltaNet mixer, in three stages each recomputed alone in
    the backward pass (memory only)."""
    B, S, _ = u.shape
    key, val = heads * dk, heads * dv

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6)

    @jax.checkpoint
    def project(u, p):
        qkvz, ba = u @ p["in_qkvz"], u @ p["in_ba"]
        qkv = jax.nn.silu(conv(qkvz[..., :2 * key + val], p["conv"]))
        q = l2(qkv[..., :key].reshape(B, S, heads, dk))
        if fault != "q_scale_left_out":
            q = q * dk ** -0.5
        k = l2(qkv[..., key:2 * key].reshape(B, S, heads, dk))
        v = qkv[..., 2 * key:].reshape(B, S, heads, dv)
        beta = jax.nn.sigmoid(ba[..., :heads])
        if fault != "beta_sigmoid":
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            ba[..., heads:] + p["dt_bias"])
        return q, k, v, g, beta, qkvz[..., 2 * key + val:]

    @jax.checkpoint
    def gate_and_project(o, z, p):
        o = norm(o, p["gdn_norm"], eps)
        if fault != "output_gate_dropped":
            o = o * jax.nn.silu(z.reshape(B, S, heads, dv))
        return o.reshape(B, S, val) @ p["out"]

    q, k, v, g, beta, z = project(u, p)
    return gate_and_project(jax.checkpoint(delta_rule)(q, k, v, g, beta),
                            z, p)


def rotate(x, theta=500000.0):
    """Rotate-half RoPE on [B, heads, S, D] (the ``rotation_added`` fault
    only: the published model rotates nothing)."""
    S, D = x.shape[-2:]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def attention(u, p, *, n_head, eps, fault=None, q_block=256):
    """Causal softmax attention, every query head its own KV head, the QK
    norm over the whole projection, no rotation."""
    B, S, H = u.shape
    D = H // n_head
    q, k, v = u @ p["q"], u @ p["k"], u @ p["v"]
    if fault == "qk_norm_per_head":
        q, k = (norm(t.reshape(B, S, n_head, D), w.reshape(n_head, D),
                     eps).reshape(B, S, H)
                for t, w in ((q, p["q_norm"]), (k, p["k_norm"])))
    else:
        q, k = norm(q, p["q_norm"], eps), norm(k, p["k_norm"], eps)
    q, k, v = (t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
               for t in (q, k, v))
    if fault == "rotation_added":
        q, k = rotate(q), rotate(k)
    step = min(q_block, S)

    @jax.checkpoint
    def rows(q_blk, start):
        scores = q_blk @ k.transpose(0, 1, 3, 2) * D ** -0.5
        seen = (start + jnp.arange(q_blk.shape[2]))[:, None] \
            >= jnp.arange(S)[None, :]
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                              axis=-1) @ v

    blocks = q.reshape(B, n_head, S // step, step, D).transpose(
        2, 0, 1, 3, 4)
    ctx = jax.lax.map(lambda xs: rows(*xs), (blocks, jnp.arange(0, S, step)))
    ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(B, n_head, S, D)
    return ctx.transpose(0, 2, 1, 3).reshape(B, S, H) @ p["o"]


def mlp(u, p, chunk=2048):
    """``(silu(u W_gate) * (u W_up)) W_down``, a chunk of tokens at a time
    (memory only)."""
    B, S, H = u.shape
    rows = u.reshape(B * S, H)
    pad = (-rows.shape[0]) % chunk

    @jax.checkpoint
    def part(r):
        return (jax.nn.silu(r @ p["gate"]) * (r @ p["up"])) @ p["down"]

    out = jax.lax.map(part, jnp.pad(rows, ((0, pad), (0, 0))).reshape(
        -1, chunk, H))
    return out.reshape(-1, H)[:B * S].reshape(B, S, H)


def head_loss(x, top, ids, *, eps, chunk=2048):
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
        logp = jax.nn.log_softmax(xc @ top["lm_head"].T, axis=-1)
        picked = jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * mc)

    total, _ = jax.lax.scan(lambda acc, c: (acc + part(*c), None),
                            jnp.zeros((), F32), (xs, tgt, live))
    return total / (B * (S - 1))


def branches(kind, *, n_head, heads, dk, dv, eps, fault=None, **_):
    """(mixer, mlp): each ``f(x, p) -> the branch as it is added to the
    stream`` on the stream ``x`` it starts from, its output norm included,
    recomputed whole in the backward pass."""
    pre = fault == "norms_on_inputs"

    def mixer(x, p):
        u = norm(x, p["attn_norm"], eps) if pre else x
        y = delta_net(u, p, heads=heads, dk=dk, dv=dv, eps=eps, fault=fault) \
            if kind == LINEAR else attention(u, p, n_head=n_head, eps=eps,
                                             fault=fault)
        return y if pre else norm(y, p["attn_norm"], eps)

    def feed_forward(x, p):
        if pre:
            return mlp(norm(x, p["ffn_norm"], eps), p)
        return norm(mlp(x, p), p["ffn_norm"], eps)

    return jax.checkpoint(mixer), jax.checkpoint(feed_forward)


def forward(top, layers, ids, *, layer_types, look=None, **sizes):
    """(loss, detail): detail holds per layer the stream it starts from and
    its two branches as added — or what ``look(i, those)`` makes of them,
    which the next layer then waits for (memory only). ``fault`` among
    ``sizes``: one of ``CONTROLS``."""
    x = top["embed"][ids]
    per_layer = []
    for i, (kind, p) in enumerate(zip(layer_types, layers)):
        mixer, feed_forward = branches(kind, **sizes)
        row = {"x_in": x}
        row["mixer_out"] = mixer(x, p)
        x = x + row["mixer_out"]
        row["mlp_out"] = feed_forward(x, p)
        x = x + row["mlp_out"]
        if look is not None:
            x, row = jax.lax.optimization_barrier((x, look(i, row)))
        per_layer.append(row)
    ce = head_loss(x, top, ids, eps=sizes["eps"])
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


def grad_norm(grads):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))


def pinned_backward(top, layers, ids, other, fold, *, layer_types, **sizes):
    """The gradients of the loss with every branch started from the residual
    stream of ANOTHER run of the same weights and batch — ``other``: per
    layer that run's {"x_in" (the stream the layer starts from),
    "mixer_out", "mlp_out" (its two branches as added)} — its values, this
    model's derivatives, walked from the head down a branch at a time. A
    layer's mixer starts from the run's ``x_in``, its MLP from ``x_in +
    mixer_out``, the head from the last layer's ``... + mlp_out``. A
    layer's gradients and its two branches' outputs at those streams are
    handed to ``fold(i, kind, gradients, mixer_out, mlp_out)`` as soon as
    they are whole and what it returns is kept in their place; the top's the
    same, ``fold(None, None, gradients, None, None)``. Returns (loss at the
    last stream, [what ``fold`` returned a layer], what it returned for the
    top)."""
    with jax.default_matmul_precision("highest"):
        def after_mixer(row):
            return row["x_in"].astype(F32) + row["mixer_out"].astype(F32)

        last = after_mixer(other[-1]) + other[-1]["mlp_out"].astype(F32)
        ce, back = jax.vjp(
            lambda t, x: head_loss(x, t, ids, eps=sizes["eps"]), top, last)
        g_top, c = back(jnp.ones((), F32))
        folded = [None] * len(layers)
        for i in reversed(range(len(layers))):
            kind, p = layer_types[i], layers[i]
            mixer, feed_forward = branches(kind, **sizes)
            # for memory only: a branch's forward pass waits for the
            # cotangent that its backward pass needs
            row, c = jax.lax.optimization_barrier((other[i], c))
            mlp_out, back = jax.vjp(feed_forward, after_mixer(row), p)
            dx, g_mlp = back(c)
            row, c = jax.lax.optimization_barrier((row, c + dx))
            mixer_out, back = jax.vjp(mixer, row["x_in"].astype(F32), p)
            dx, g_mixer = back(c)
            c = c + dx
            # each branch's gradient of the other's leaves is zero
            grads = jax.tree_util.tree_map(jnp.add, g_mlp, g_mixer)
            # ... and the layer below waits for what ``fold`` makes of this
            # one's gradients
            c, folded[i] = jax.lax.optimization_barrier(
                (c, fold(i, kind, grads, mixer_out, mlp_out)))
        g_top = dict(g_top, embed=jnp.zeros_like(top["embed"]).at[ids].add(c))
        return ce, folded, fold(None, None, g_top, None, None)
