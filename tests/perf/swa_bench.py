"""Microbenchmark of the window kernels alone on the chip
(``ops/pallas/flash_attention.py``'s sliding-window family) at the
``laguna-train-1chip-s16384`` cell's shape — 1 x 64 query / 8 KV heads x
16,384 x head_dim 128, bf16, window 512 — forward and forward + backward,
over grid blocks and chunks, beside full causal attention of the same shape
(what a window layer would cost under a mask) and against the masked
float32 reference at a shorter sequence; since PR 34 also the causal chunked
kernels at the cell's full layers' shape (48 query / 8 KV heads) and what a
re-layout of a ``[BH, S, 1]`` log-sum-exp to 128 dense lanes and back costs
in XLA (the form PR 34 did not take). ``--tree`` times another checkout's
kernels (the parent's, unpacked in a git-ignored directory) with this
harness. Not part of the benchmark: PERF.md's Findings quote it.

    chiprun -- python tests/perf/swa_bench.py [--out NAME] [--tree DIR]
"""

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, next((os.path.abspath(a.split("=", 1)[1])
                         for a in sys.argv if a.startswith("--tree=")), HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.ops.attention import reference_attention  # noqa: E402
from deepspeed_tpu.ops.pallas.flash_attention import (  # noqa: E402
    flash_attention, window_tile_overcompute)

PEAK = 197e12                       # bf16 flops a second, one v5e
TILES = ((1024, 512, 1024), (1024, 1024, 1024), (512, 512, 1024),
         (512, 512, 512), (256, 256, 256), (256, 256, 512), (256, 256, 1024),
         (128, 128, 128), (128, 128, 256), (128, 128, 512), (256, 128, 256),
         (128, 256, 256), (512, 256, 512), (256, 512, 512))


def timed(fn, *args, reps=10):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return statistics.median(times) * 1e3


def inputs(H, Hkv, S, D, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, cot = (jax.random.normal(k, (1, H, S, D), jnp.float32).astype(dtype)
              for k in (ks[0], ks[3]))
    k, v = (jax.random.normal(k, (1, Hkv, S, D), jnp.float32).astype(dtype)
            for k in ks[1:3])
    return (q, k, v), cot


def programs(attend):
    fwd = jax.jit(attend)
    grads = jax.jit(lambda q, k, v, cot: jax.grad(
        lambda *a: jnp.sum(attend(*a).astype(jnp.float32)
                           * cot.astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v))
    return fwd, grads


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="swa_bench")
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--causal-heads", type=int, default=48)
    ap.add_argument("--tiles", choices=("all", "default"), default="all",
                    help="default: only the tiling the dispatch picks")
    ap.add_argument("--tree", default=HERE,
                    help="--tree=DIR: the checkout whose kernels are timed")
    args = ap.parse_args()
    H, Hkv, S, D, W = args.heads, 8, args.seq, 128, args.window
    (q, k, v), cot = inputs(H, Hkv, S, D)
    band = S * W - W * (W - 1) // 2
    product = 2 * H * band * D
    rows = []
    for bq, bk, chunk in TILES if args.tiles == "all" else ((512, 512, 512),):
        attend = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, window=W, block_q=bq, block_k=bk,
            chunk=chunk)
        try:
            fwd, grads = programs(attend)
            f_ms, g_ms = timed(fwd, q, k, v), timed(grads, q, k, v, cot)
        except Exception as e:  # boundary: report the compiler's words
            rows.append({"block_q": bq, "block_k": bk, "chunk": chunk,
                         "refused": str(e).splitlines()[:3]})
            print(json.dumps(rows[-1]), flush=True)
            continue
        b_ms = g_ms - f_ms              # grad runs forward then backward
        rows.append({
            "block_q": bq, "block_k": bk, "chunk": chunk,
            "overcompute": window_tile_overcompute(S, bq, bk, W),
            "fwd_ms": f_ms, "fwd_bwd_ms": g_ms,
            "fwd_roofline_pct": 100 * 2 * product / PEAK / (f_ms / 1e3),
            "bwd_roofline_pct": 100 * 4 * product / PEAK / (b_ms / 1e3)})
        print(json.dumps(rows[-1]), flush=True)
    fwd, grads = programs(lambda q, k, v: flash_attention(q, k, v,
                                                          causal=True))
    causal = {"causal_fwd_ms": timed(fwd, q, k, v, reps=5),
              "causal_fwd_bwd_ms": timed(grads, q, k, v, cot, reps=5)}
    # the cell's FULL layers: 48 query heads, causal, the chunked kernels
    Hc = args.causal_heads
    (qc, kc, vc), cotc = inputs(Hc, Hkv, S, D, seed=2)
    f_ms, g_ms = timed(fwd, qc, kc, vc, reps=5), timed(grads, qc, kc, vc,
                                                       cotc, reps=5)
    needed = 2 * Hc * (S * (S + 1) // 2) * D        # one product, causal
    causal.update({
        "causal48_heads": Hc, "causal48_fwd_ms": f_ms,
        "causal48_fwd_bwd_ms": g_ms,
        "causal48_fwd_roofline_pct": 100 * 2 * needed / PEAK / (f_ms / 1e3),
        "causal48_bwd_roofline_pct": 100 * 4 * needed / PEAK
        / ((g_ms - f_ms) / 1e3)})
    # the form not taken: a [BH, S, 1] statistic re-laid to dense lanes
    column = jnp.zeros((H, S, 1), jnp.float32)
    dense = jax.jit(lambda x: x.reshape(H, S // 128, 1, 128))
    back = jax.jit(lambda x: x.reshape(H, S, 1))
    causal.update({"lse_column_to_dense_ms": timed(dense, column),
                   "lse_dense_to_column_ms": timed(back, dense(column))})
    print(json.dumps(causal), flush=True)

    # accuracy at a length the [S, S] reference fits, bf16 in, f32 compared
    (q, k, v), cot = inputs(16, 2, 2048, D, seed=1)
    got = programs(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=W))[1](q, k, v, cot)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: jnp.sum(reference_attention(
            *a, causal=True, window=W) * f32(cot)), argnums=(0, 1, 2))(
            f32(q), f32(k), f32(v))
    rel = [float(jnp.linalg.norm(f32(a) - b) / jnp.linalg.norm(b))
           for a, b in zip(got, want)]
    out = {"tree": os.path.relpath(args.tree, HERE),
           "shape": [1, H, Hkv, S, D], "window": W, "tiles": rows,
           "device": jax.devices()[0].device_kind, **causal,
           "grad_rel_vs_f32_reference_dq_dk_dv": rel}
    print(json.dumps({"grad_rel_vs_f32_reference_dq_dk_dv": rel}))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out + ".json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
