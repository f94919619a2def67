"""Microbenchmark of the block-diffusion mask kernels alone on the chip
(``ops/pallas/block_diffusion_attention.py``) at the cell's shape — 32 query /
4 KV heads x 2 x 8,192 rows x head_dim 128, bf16, block length 4 — one JSON
line a (tile, chunk) plan: ms of the forward call and of forward + backward
(``jax.grad``: the backward kernel, delta and the sum of the dq partials),
wall clock over ``--iters`` fenced calls after a warm-up, and the share of
the bf16 peak the allowed pairs' flops make of each. A plan the compiler
refuses is a ``refused`` line. Not part of the benchmark: PERF.md's Findings
quote it.

    chiprun -- python tests/perf/bd_attention_bench.py \
        --plans 512x1024,512x2048,512x4096,512x8192,256x2048
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

from deepspeed_tpu.ops.pallas import block_diffusion_attention as bd  # noqa: E402

PEAK = 197e12                       # bf16 flops a second, one v5e


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--plans", default="512x2048")
    ap.add_argument("--L", type=int, default=8192)
    ap.add_argument("--block-length", type=int, default=4)
    ap.add_argument("--heads", default="32x4")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    H, Hkv = (int(x) for x in args.heads.split("x"))
    L, D = args.L, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, H, 2 * L, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, Hkv, 2 * L, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, Hkv, 2 * L, D), jnp.bfloat16)
    pairs = bd.allowed_pairs(L, args.block_length)
    flops = {"fwd": 4 * H * pairs * D, "fwd_bwd": 12 * H * pairs * D}
    for plan in args.plans.split(","):
        block, chunk = (int(x) for x in plan.split("x"))

        def attend(q, k, v):
            return bd.block_diffusion_attention(
                q, k, v, args.block_length, block=block, chunk=chunk)

        fns = {"fwd": jax.jit(attend),
               "fwd_bwd": jax.jit(jax.grad(
                   lambda *a: attend(*a).astype(jnp.float32).sum(),
                   argnums=(0, 1, 2)))}
        line = {"block": block, "chunk": chunk,
                "overcompute": bd.tile_overcompute(L, args.block_length,
                                                   block)}
        try:
            for name, fn in fns.items():
                jax.block_until_ready(fn(q, k, v))
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = fn(q, k, v)
                jax.block_until_ready(out)
                ms = 1e3 * (time.perf_counter() - t0) / args.iters
                line[name + "_ms"] = ms
                line[name + "_peak_share"] = 100 * flops[name] / PEAK / (
                    ms / 1e3)
        except Exception as e:  # boundary: report the compiler's words
            line["refused"] = str(e).splitlines()[0][:300]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
