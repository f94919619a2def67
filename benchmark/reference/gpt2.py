"""GPT-2 as published, in plain float32 ``jax.numpy``: the yardstick.

Forward, next-token loss and the norm of the loss's gradient, written from
the GPT-2 description (pre-LN blocks, learned positions, tied output head,
tanh-GELU) and independent of ``deepspeed_tpu/models/gpt2.py``: no kernel,
no KV cache, no remat, no sharding. Every matmul runs under
``jax.default_matmul_precision("highest")`` — on a TPU a float32 matmul is
otherwise done in bf16 passes.

Departures from the published model, both also made by the system under
test and written in the configuration files: the vocabulary is padded to a
multiple of 128 (the extra rows are ordinary rows of ``wte``) and dropout
is off.

Weights arrive one layer at a time, so that a caller whose weights live
sharded, or in bf16, or beside a training engine's state, never has to hold
a second float32 copy of the model:

    top = {"wte": [V, E], "wpe": [P, E], "ln_f": (g, b)}
    layer(i) = {"ln_1": (g, b), "c_attn": (W [E, 3E], b), "c_proj": (W, b),
                "ln_2": (g, b), "c_fc": (W [E, 4E], b), "mlp_proj": (W, b)}

``logits`` takes them as ``top`` and ``layer(i)``; ``loss_and_grad_norm``,
which may deal the batch's sequences onto several chips, as ``top(device)``
and ``layer(i, device)``.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def layer_norm(x, gb, eps):
    g, b = gb
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(x, p, n_head):
    B, S, E = x.shape
    D = E // n_head
    qkv = x @ p["c_attn"][0] + p["c_attn"][1]
    q, k, v = (t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = (q @ k.transpose(0, 1, 3, 2)) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jax.nn.softmax(scores, axis=-1) @ v
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, E)
    return ctx @ p["c_proj"][0] + p["c_proj"][1]


@_highest
def block(x, p, n_head, eps):
    x = x + attention(layer_norm(x, p["ln_1"], eps), p, n_head)
    h = gelu_new(layer_norm(x, p["ln_2"], eps) @ p["c_fc"][0] + p["c_fc"][1])
    return x + h @ p["mlp_proj"][0] + p["mlp_proj"][1]


def embed(ids, wte, wpe):
    return wte[ids] + wpe[: ids.shape[-1]]


@_highest
def head_logits(x, ln_f, wte, eps):
    return layer_norm(x, ln_f, eps) @ wte.T


@_highest
def head_nll_sum(x, ln_f, wte, ids, eps):
    """Sum over positions of -log p(next token): logits at position t
    against the token at t+1 (the last position has no target)."""
    logits = layer_norm(x[:, :-1], ln_f, eps) @ wte.T
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(tgt)


_block = jax.jit(block, static_argnums=(2, 3))
_head_logits = jax.jit(head_logits, static_argnums=(3,))


def logits(top, layer, n_layer, n_head, eps, ids, positions):
    """Logits [len(positions), V] of one sequence ``ids`` [S] at the given
    positions, from a full forward pass over the whole sequence."""
    x = embed(ids[None], top["wte"], top["wpe"])
    for i in range(n_layer):
        x = _block(x, layer(i), n_head, eps)
    return _head_logits(x[0, jnp.asarray(positions)], top["ln_f"],
                        top["wte"], eps)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block_vjp(x, p, n_head, eps, g):
    _, vjp = jax.vjp(lambda x_, p_: block(x_, p_, n_head, eps), x, p)
    return vjp(g)


@functools.partial(jax.jit, static_argnums=(4,))
def _head_vjp(x, ln_f, wte, ids, eps):
    nll, vjp = jax.vjp(
        lambda x_, l_, w_: head_nll_sum(x_, l_, w_, ids, eps), x, ln_f, wte)
    return nll, vjp(jnp.ones((), F32))


def _sq(tree):
    return sum(jnp.sum(jnp.square(leaf))
               for leaf in jax.tree_util.tree_leaves(tree))


def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def loss_and_grad_norm(top, layer, n_layer, n_head, eps, ids, devices,
                       group=2):
    """(mean next-token loss, global L2 norm of its gradient) over the batch
    ``ids`` [B, S]. ``top(device)`` and ``layer(i, device)`` give the weights
    on a device. The batch is cut into groups of ``group`` sequences, dealt
    round robin onto ``devices`` (same arithmetic, only placed on more than
    one chip), and backward runs layer by layer, so beside the saved layer
    inputs only one layer's gradient is alive. Gradients are summed on
    ``devices[0]``; the tied ``wte`` takes its head and its embedding
    gradient summed before the norm."""
    B, S = ids.shape
    n_targets = B * (S - 1)
    home = devices[0]
    where = [devices[(i // group) % len(devices)] for i in range(0, B, group)]
    groups = [jax.device_put(ids[i:i + group], d)
              for i, d in zip(range(0, B, group), where)]

    def gather(tree):
        return jax.device_put(tree, home)

    acts = [[embed(g, top(d)["wte"], top(d)["wpe"])]
            for g, d in zip(groups, where)]          # [group][layer] inputs
    for i in range(n_layer):
        for xs, d in zip(acts, where):
            xs.append(_block(xs[-1], layer(i, d), n_head, eps))

    nll, g_ln_f, g_wte, cots = jnp.zeros((), F32), None, None, []
    for g, d, xs in zip(groups, where, acts):
        n, (gx, gl, gw) = _head_vjp(xs.pop(), top(d)["ln_f"], top(d)["wte"],
                                    g, eps)
        nll = nll + gather(n)
        g_ln_f = gather(gl) if g_ln_f is None else _add(g_ln_f, gather(gl))
        g_wte = gather(gw) if g_wte is None else g_wte + gather(gw)
        cots.append(gx)
    sq = _sq(g_ln_f)

    for i in reversed(range(n_layer)):
        g_p = None
        for j, (xs, d) in enumerate(zip(acts, where)):
            cots[j], gp = _block_vjp(xs.pop(), layer(i, d), n_head, eps,
                                     cots[j])
            g_p = gather(gp) if g_p is None else _add(g_p, gather(gp))
        sq = sq + _sq(g_p)

    g_wpe = jnp.zeros_like(top(home)["wpe"]).at[:S].add(
        sum(gather(c.sum(axis=0)) for c in cots))
    for g, c in zip(groups, cots):
        g_wte = g_wte.at[gather(g).reshape(-1)].add(
            gather(c).reshape(-1, c.shape[-1]))
    sq = sq + _sq(g_wpe) + _sq(g_wte)
    return nll / n_targets, jnp.sqrt(sq) / n_targets
