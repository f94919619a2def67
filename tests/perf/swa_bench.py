"""Microbenchmark of the window kernels alone on the chip
(``ops/pallas/flash_attention.py``'s sliding-window family) at the two
window cells' shapes — ``laguna-train-1chip-s16384``'s 1 x 64 query / 8 KV
heads x 16,384 x head_dim 128, bf16, window 512, and
``smallthinker-train-1chip-s16384``'s 28 / 4 heads, window 4,096. Per shape
and per call of the three (forward, dq, dkv): ms, grid steps, us a grid step,
score tiles a step and the share of the band's roofline; with ``--sweep`` the
same under ``chunk=`` caps of the rows a grid step holds (PR 43: the band in
one step against 1, 2, 4 tiles a step) and, on Laguna's shape, 1, 2, 4 of a
group's 8 query heads a dkv step (by the kernels' budget) and over grid
blocks; with ``--causal`` full causal attention of the same shape (what a
window layer would cost under a mask), the causal chunked kernels at the
Laguna cell's full layers' shape (48 query / 8 KV heads) and what a re-layout
of a ``[BH, S, 1]`` log-sum-exp to 128 dense lanes and back costs in XLA (the
form PR 34 did not take); always the three gradients against the masked
float32 reference at a shorter sequence. ``--tree`` times another checkout's
kernels (the parent's, unpacked in a git-ignored directory) with this
harness. Not part of the benchmark: PERF.md's Findings quote it.

    chiprun -- python tests/perf/swa_bench.py [--out NAME] [--tree=DIR]
"""

import argparse
import importlib
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

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

PEAK = 197e12                       # bf16 flops a second, one v5e
S, D = 16384, 128                   # --seq: a rehearsal's shorter S
SHAPES = {"laguna": (64, 8, 512), "smallthinker": (28, 4, 4096)}
BLOCKS = ((1024, 512), (1024, 1024), (256, 256), (128, 128), (256, 128),
          (128, 256), (512, 256), (256, 512))


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


def programs(attend, argnums=(0, 1, 2)):
    fwd = jax.jit(attend)
    grads = jax.jit(lambda q, k, v, cot: jax.grad(
        lambda *a: jnp.sum(attend(*a).astype(jnp.float32)
                           * cot.astype(jnp.float32)),
        argnums=argnums)(q, k, v))
    return fwd, grads


def grid_steps(H, Hkv, W, bq, bk, chunk):
    """Grid steps of the (forward or dq, dkv) call and the score tiles each
    computes, from the kernels' own ``_band_plan``."""
    (_, steps_k), (_, steps_q, heads) = fa._band_plan(
        S, bq, bk, W, D * 2, H // Hkv, chunk or 0)
    steps_q /= heads
    over_k = sum((q0 + bq - 1) // bk - max(q0 - W + 1, 0) // bk + 1
                 for q0 in range(0, S, bq))
    over_q = sum(min((k0 + bk + W - 2) // bq + 1, S // bq) - k0 // bq
                 for k0 in range(0, S, bk))
    return (H * (S // bq) * steps_k, H * (S // bk) * steps_q,
            H * over_k, H * over_q)


def window_row(H, W, q, k, v, cot, bq=None, bk=None, chunk=None,
               band_bytes=None):
    """One tiling of one shape: the three calls apart. dq is the gradient
    by q alone less the forward, dkv the one by k and v (the VJP's other
    call is dead code there; the delta pass and the casts stay in).
    ``band_bytes``: the kernels' budget for one band operand, which decides
    how many of a group's query heads a dkv step takes (PR 43)."""
    attend = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=W, block_q=bq, block_k=bk, chunk=chunk)
    row = {"block_q": bq or min(S, 512), "block_k": bk or min(S, 512),
           "chunk": chunk}
    budget = getattr(fa, "_BAND_BYTES", None)
    if band_bytes:
        fa._BAND_BYTES, row["band_bytes"] = band_bytes, band_bytes
    try:
        fwd, g_q = programs(attend, (0,))
        _, g_kv = programs(attend, (1, 2))
        _, g_all = programs(attend)
        f_ms = timed(fwd, q, k, v)
        ms = {"fwd": f_ms, "dq": timed(g_q, q, k, v, cot) - f_ms,
              "dkv": timed(g_kv, q, k, v, cot) - f_ms}
        g_ms = timed(g_all, q, k, v, cot)
        steps_k, steps_q, tiles_k, tiles_q = grid_steps(
            H, k.shape[1], W, row["block_q"], row["block_k"], chunk)
    except Exception as e:  # boundary: report the compiler's words
        row["refused"] = str(e).splitlines()[:3]
        return row
    finally:
        if band_bytes:
            fa._BAND_BYTES = budget
    product = 2 * H * (S * W - W * (W - 1) // 2) * D
    row.update({
        "overcompute": window_tile_overcompute(S, row["block_q"],
                                               row["block_k"], W),
        "fwd_ms": f_ms, "fwd_bwd_ms": g_ms,
        "fwd_roofline_pct": 100 * 2 * product / PEAK / (f_ms / 1e3),
        "bwd_roofline_pct": 100 * 4 * product / PEAK / ((g_ms - f_ms) / 1e3)})
    for call, steps, tiles in (("fwd", steps_k, tiles_k),
                               ("dq", steps_k, tiles_k),
                               ("dkv", steps_q, tiles_q)):
        row[call] = {"ms": ms[call], "grid_steps": steps,
                     "us_a_step": 1e3 * ms[call] / steps,
                     "tiles_a_step": tiles / steps,
                     "us_a_tile": 1e3 * ms[call] / tiles}
    return row


def causal_rows(q, k, v, cot, H, Hkv, Hc):
    fwd, grads = programs(lambda q, k, v: flash_attention(q, k, v,
                                                          causal=True))
    causal = {"causal_fwd_ms": timed(fwd, q, k, v, reps=5),
              "causal_fwd_bwd_ms": timed(grads, q, k, v, cot, reps=5)}
    # the Laguna cell's FULL layers: 48 query heads, the chunked kernels
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
    return causal


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="swa_bench")
    ap.add_argument("--shapes", default="smallthinker,laguna")
    ap.add_argument("--sweep", action="store_true",
                    help="also chunk= caps of a step's rows, and blocks")
    ap.add_argument("--causal", action="store_true",
                    help="also the causal kernels at Laguna's shapes")
    ap.add_argument("--causal-heads", type=int, default=48)
    ap.add_argument("--tree", default=HERE,
                    help="--tree=DIR: the checkout whose kernels are timed")
    ap.add_argument("--seq", type=int, default=S)
    args = ap.parse_args()
    globals()["S"] = args.seq
    out = {"tree": os.path.relpath(args.tree, HERE),
           "device": jax.devices()[0].device_kind, "shapes": {}}
    for name in args.shapes.split(","):
        H, Hkv, W = SHAPES[name]
        (q, k, v), cot = inputs(H, Hkv, S, D)
        tilings = [(None, None, None)]
        if args.sweep:
            tilings += [(None, None, c) for c in (512, 1024, 2048)
                        if c < W + 512]
            if name == "laguna" and hasattr(fa, "_BAND_BYTES"):
                tilings += [(None, None, None, h * 1024 * D * 2)
                            for h in (1, 2, 4)]
                tilings += [(bq, bk, None) for bq, bk in BLOCKS]
        rows = []
        for tiling in tilings:
            rows.append(window_row(H, W, q, k, v, cot, *tiling))
            print(json.dumps({"shape": name, **rows[-1]}), flush=True)
        out["shapes"][name] = {"shape": [1, H, Hkv, S, D], "window": W,
                               "tiles": rows}
        if args.causal and name == "laguna":
            out.update(causal_rows(q, k, v, cot, H, Hkv, args.causal_heads))

    # accuracy at a length the [S, S] reference fits, bf16 in, f32 compared
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    for name in args.shapes.split(","):
        H, Hkv, W = SHAPES[name]
        (q, k, v), cot = inputs(H // 4, Hkv // 4, min(W + 1024, S), D,
                                seed=1)
        got = programs(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=W))[1](q, k, v, cot)
        with jax.default_matmul_precision("highest"):
            want = jax.grad(lambda *a: jnp.sum(reference_attention(
                *a, causal=True, window=W) * f32(cot)), argnums=(0, 1, 2))(
                f32(q), f32(k), f32(v))
        rel = [float(jnp.linalg.norm(f32(a) - b) / jnp.linalg.norm(b))
               for a, b in zip(got, want)]
        out["shapes"][name]["grad_rel_vs_f32_reference_dq_dk_dv"] = rel
        print(json.dumps({"shape": name,
                          "grad_rel_vs_f32_reference_dq_dk_dv": rel}))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out + ".json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
