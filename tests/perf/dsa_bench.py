"""The learned-sparse-attention kernels alone on the chip
(``ops/pallas/learned_sparse_attention.py``) at the Keye-VL-2.0 cell's shape
— 32 query / 4 KV heads x 16,384 rows x head_dim 128, an indexer of 16 heads
x 64, top-2,048, bf16 — one JSON line a stage: wall-clock ms over ``--iters``
fenced calls after a warm-up of the indexer's scores, the selection, the
masked forward, the KL pass (which leaves its gradient as the causal tiles),
the indexer's backward from those tiles, and the whole call forward and
forward + backward; first a line that holds the kernels to the dense
plain-XLA form at ``--check-seq`` rows (output, KL, the selected set, every
gradient). Not part of the benchmark: PERF.md's Findings quote it.

    chiprun -- python tests/perf/dsa_bench.py
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.ops.pallas import learned_sparse_attention as L  # noqa: E402


def operands(S, H, Hkv, D, J, Di, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    return (jax.random.normal(ks[0], (1, H, S, D), bf),
            jax.random.normal(ks[1], (1, Hkv, S, D), bf),
            jax.random.normal(ks[2], (1, Hkv, S, D), bf),
            jax.random.normal(ks[3], (1, J, S, Di), bf),
            jax.random.normal(ks[4], (1, S, Di), bf),
            jax.random.normal(ks[5], (1, S, J), jnp.float32) * 0.03)


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def check(S, topk, shape):
    args = operands(S, *shape, seed=1)
    scale = shape[2] ** -0.5

    def loss(fn, *a):
        o, kl, n, _ = fn(*a)
        w = jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape)
        return jnp.sum(o.astype(jnp.float32) * w) + 100 * jnp.mean(kl), \
            (o, kl, n)

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: loss(fn, *a), argnums=tuple(range(6)),
            has_aux=True))(*args)

    (_, (o0, kl0, n0)), g0 = grads(
        lambda *a: L.reference_learned_sparse_attention(*a, topk, scale))
    (_, (o1, kl1, n1)), g1 = grads(
        lambda *a: L.learned_sparse_attention(*a, topk, scale))

    def rel(a, b):
        a, b = (x.astype(jnp.float32) for x in (a, b))
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    return {"check_seq": S, "topk": topk, "o_rel": rel(o1, o0),
            "kl_rel": rel(kl1, kl0), "kl_mean": float(kl0.mean()),
            "selected": [int(n1.sum()), int(n0.sum()),
                         L.selected_pairs(S, topk)],
            "grad_rel": {n: rel(a, b) for n, a, b in zip(
                ("q", "k", "v", "iq", "ik", "iw"), g1, g0)}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--check-seq", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()
    shape = (32, 4, 128, 16, 64)
    if args.check_seq:
        print(json.dumps(check(args.check_seq, args.topk // 4, shape)),
              flush=True)
    S, topk = args.seq, args.topk
    q, k, v, iq, ik, iw = operands(S, *shape)
    H, Hkv, D, J, Di = shape
    tile = L._tile(False)
    block, chunk = L._plan(S, D, 2, False)
    scale = D ** -0.5
    it = jax.jit(lambda *a: L._index_scores(*a, tile, False))(iq, ik, iw)
    mt, lse_i, n = jax.jit(lambda x: L._select_call(x, topk, False))(it)
    flat = (q.reshape(H, S, D), k.reshape(Hkv, S, D), v.reshape(Hkv, S, D))
    o, lse = jax.jit(lambda *a: L._masked_attention(
        *a, scale, block, chunk, False, H, Hkv))(*flat, mt)
    _, gt = jax.jit(lambda *a: L._kl_call(
        *a, scale, tile, False, "bfloat16"))(it, lse_i, q, k, lse, mt)
    qw = (iq.astype(jnp.float32)
          * jnp.swapaxes(iw, 1, 2)[..., None]).astype(iq.dtype)
    g = jnp.full((1, 1, S), 1.0 / S, jnp.float32)
    print(json.dumps({"selected_share": float(n.sum()) / L.causal_pairs(S),
                      "expected": L.selected_pairs(S, topk)
                      / L.causal_pairs(S),
                      "tile_overcompute": L.tile_overcompute(S, topk, tile),
                      "plan": [tile, block, chunk],
                      "kl_grad_mb": gt.nbytes / 1e6}), flush=True)
    whole = lambda *a: L.learned_sparse_attention(*a, topk, scale)  # noqa: E731
    stages = {
        "indexer": (lambda *a: L._index_scores(*a, tile, False),
                    (iq, ik, iw)),
        "select": (lambda x: L._select_call(x, topk, False), (it,)),
        "fwd": (lambda *a: L._masked_attention(
            *a, scale, block, chunk, False, H, Hkv), flat + (mt,)),
        "kl": (lambda *a: L._kl_call(*a, scale, tile, False, "bfloat16"),
               (it, lse_i, q, k, lse, mt)),
        "indexer_bwd": (lambda *a: L._index_scores_bwd_call(*a, tile, False),
                        (iq, qw, ik, gt, g)),
        "call_fwd": (whole, (q, k, v, iq, ik, iw)),
        "call_fwd_bwd": (jax.grad(
            lambda *a: (lambda o, kl, *_: o.astype(jnp.float32).sum()
                        + kl.mean())(*whole(*a)), argnums=tuple(range(6))),
            (q, k, v, iq, ik, iw)),
    }
    for name, (fn, a) in stages.items():
        try:
            print(json.dumps({"stage": name,
                              "ms": timed(jax.jit(fn), a, args.iters)}),
                  flush=True)
        except Exception as e:  # boundary: report the compiler's words
            print(json.dumps({"stage": name,
                              "refused": str(e).splitlines()[0][:300]}),
                  flush=True)


if __name__ == "__main__":
    main()
