"""Microbenchmark of the window kernels alone on the chip
(``ops/pallas/flash_attention.py``'s sliding-window family) at the two
window cells' shapes — ``laguna-train-1chip-s16384``'s 1 x 64 query / 8 KV
heads x 16,384 x head_dim 128, bf16, window 512, and
``smallthinker-train-1chip-s16384``'s 28 / 4 heads, window 4,096. Per shape
and per call of the two (forward; the single-pass backward, PR 53: the
gradient program less the forward, the delta pass in): ms, grid steps, us a
grid step, score tiles a step, us a tile and the share of the band's
roofline; with ``--sweep`` the same under ``chunk=`` caps of the rows a grid
step holds (the band in one step against 1, 2, 4 tiles a step), under 1, 2, 4
of a group's query heads a backward step (the plan's own count overridden)
and, on Laguna's shape, over grid blocks; with ``--causal`` full causal
attention of the same shape (what a window layer would cost under a mask),
the causal chunked kernels at the Laguna cell's full layers' shape (48 query
/ 8 KV heads) and what a re-layout of a ``[BH, S, 1]`` log-sum-exp to 128
dense lanes and back costs in XLA (the form PR 34 did not take); always the
three gradients against the masked float32 reference at a shorter sequence.
``--tree`` times another checkout's kernels (the parent's, unpacked in a
git-ignored directory: its dq and dkv calls together are its backward) with
this harness. ``--walk=keys`` times the walk PR 53 did NOT take in place of
the module's backward (``_swa_bwd_by_key_block`` below: by KEY block, the
K / V tile resident, a head's Q / dO band streamed, dq the ring; equal
blocks and a band in one step only), so that both crossings of the single
pass are measured by one harness. Not part of the benchmark: PERF.md's
Findings quote it.

    chiprun -- python tests/perf/swa_bench.py [--out NAME] [--tree=DIR]
"""

import argparse
import functools
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
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

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
    """Grid steps of the (forward, backward) calls and the score tiles each
    computes, from the kernels' own ``_band_plan``; a tree from before PR 53
    (no ``_band_ring``) has a dq and a dkv call for its backward."""
    band = fa._band_plan(S, bq, bk, W, D * 2, H // Hkv, chunk or 0)
    over_k = sum((q0 + bq - 1) // bk - max(q0 - W + 1, 0) // bk + 1
                 for q0 in range(0, S, bq))
    if hasattr(fa, "_band_ring"):
        (_, steps), (lag, _, heads) = band
        if fa._swa_bwd is _swa_bwd_by_key_block:    # a (key block, head) a step
            lag, heads = 0, 1
        return (H * (S // bq) * steps, H * (S // bq + lag) * steps / heads,
                H * over_k, H * over_k, heads)
    (_, steps_k), (_, steps_q, heads) = band
    over_q = sum(min((k0 + bk + W - 2) // bq + 1, S // bq) - k0 // bq
                 for k0 in range(0, S, bk))
    return (H * (S // bq) * steps_k,
            H * (S // bq) * steps_k + H * (S // bk) * steps_q / heads,
            H * over_k, H * (over_k + over_q), heads)


def window_row(H, W, q, k, v, cot, bq=None, bk=None, chunk=None, heads=None):
    """One tiling of one shape: the forward and the backward (the gradient
    program by q, k and v less the forward; the delta pass stays in).
    ``heads``: query heads a backward step holds, in place of the plan's own
    count (PR 53's kernel alone)."""
    attend = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=W, block_q=bq, block_k=bk, chunk=chunk)
    row = {"block_q": bq or min(S, 512), "block_k": bk or min(S, 512),
           "chunk": chunk}
    plan = fa._band_plan
    if heads:
        def forced(*a, **kw):
            walk, (lag, ring, _) = plan(*a, **kw)
            return walk, (lag, ring, heads)
        fa._band_plan = forced
    try:
        fwd, g_all = programs(attend)
        f_ms = timed(fwd, q, k, v)
        g_ms = timed(g_all, q, k, v, cot)
        steps_f, steps_b, tiles_f, tiles_b, row["heads_a_step"] = grid_steps(
            H, k.shape[1], W, row["block_q"], row["block_k"], chunk)
    except Exception as e:  # boundary: report the compiler's words
        row["refused"] = str(e).splitlines()[:3]
        return row
    finally:
        fa._band_plan = plan
    product = 2 * H * (S * W - W * (W - 1) // 2) * D
    row.update({
        "overcompute": window_tile_overcompute(S, row["block_q"],
                                               row["block_k"], W),
        "fwd_ms": f_ms, "fwd_bwd_ms": g_ms,
        "fwd_roofline_pct": 100 * 2 * product / PEAK / (f_ms / 1e3),
        "bwd_roofline_pct": 100 * 4 * product / PEAK / ((g_ms - f_ms) / 1e3)})
    for call, ms, steps, tiles in (("fwd", f_ms, steps_f, tiles_f),
                                   ("bwd", g_ms - f_ms, steps_b, tiles_b)):
        row[call] = {"ms": ms, "grid_steps": steps,
                     "us_a_step": 1e3 * ms / steps,
                     "tiles_a_step": tiles / steps,
                     "us_a_tile": 1e3 * ms / tiles}
    return row


def _by_key_block_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, dq_ring, dk_acc, dv_acc, *,
                         scale, window, block, per, rep, seq_len):
    """The mirror of ``_swa_bwd_kernel``: grid (KV head, key block, query
    head of the group). The key block's K and V tile stays over the group's
    heads, a head's band of Q, dO, lse and delta — the ``per`` query blocks
    from the key block's own on — is one operand block, dk and dv
    accumulate over the band (a carry) and the heads (scratch) and leave on
    the group's last head; dq is the crossing gradient: a float32 ring of
    the band's rows a HEAD, query block ``j`` whole once key block ``j`` —
    the last it sees — is done."""
    j, h = pl.program_id(1), pl.program_id(2)
    blocks = seq_len // block
    fold = fa._scale_folds(scale)
    k, v = k_ref[0], v_ref[0]
    k0 = j * block
    rel = -fa._rel_pos(block, block)                # query - key
    at = jnp.minimum(j, blocks - per)
    hi = jnp.minimum((k0 + block + window - 2) // block + 1, blocks)
    a = jnp.clip((k0 + 2 * block - 2) // block, j, hi)
    b = jnp.clip((k0 + window) // block, a, hi)
    ring = per * block

    @pl.when(j == 0)
    def _clear():
        dq_ring[h] = jnp.zeros((ring, dq_ring.shape[2]), jnp.float32)

    @pl.when(h == 0)
    def _start():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(jj, carry, masked):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(jj * block, block), block)
        q = q_ref[0, rows, :] * scale if fold else q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        lse = fa._stat_row(lse_ref, (0,), jj * block, block)
        delta = fa._stat_row(delta_ref, (0,), jj * block, block)
        mask = (fa._band_mask(rel, (at + jj) * block, k0, window)
                if masked else None)
        p, ds = fa._bwd_ds_block(k, v, lse, delta, q, do, mask,
                                 None if fold else scale)
        slot = pl.ds(pl.multiple_of(((at + jj) * block) % ring, block), block)
        dq_ring[h, slot, :] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (dk + jax.lax.dot(ds, q, preferred_element_type=jnp.float32),
                dv + jax.lax.dot(p, do, preferred_element_type=jnp.float32))

    dk, dv = fa._band_loop(tuple(x - at for x in (j, a, b, hi)), body,
                           (dk_acc[...], dv_acc[...]))
    dk_acc[...] = dk
    dv_acc[...] = dv
    slot = pl.ds(pl.multiple_of(k0 % ring, block), block)
    dq_ref[0] = (dq_ring[h, slot, :] * scale).astype(dq_ref.dtype)
    dq_ring[h, slot, :] = jnp.zeros((block, dq_ring.shape[2]), jnp.float32)

    @pl.when(h == rep - 1)
    def _leave():
        dk_ref[0] = (dk if fold else dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)


def _swa_bwd_by_key_block(q, k, v, o, lse, do, scale, window, block_q,
                          block_k, band, interpret, heads, kv_heads):
    """``fa._swa_bwd``'s signature and results from the other walk."""
    assert block_q == block_k and band[0][1] == 1, (block_q, block_k, band)
    BH, S, D = q.shape
    BHkv = k.shape[0]
    rep = BH // BHkv
    block, per = block_q, band[0][0]
    blocks = S // block
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)
    piece = lse.shape[-1]

    def band_at(b, j, h):
        return b * rep + h, jnp.minimum(j, blocks - per) * block

    queries = pl.BlockSpec(
        (pl.Element(1), pl.Element(per * block), pl.Element(D)),
        lambda *g: band_at(*g) + (0,))
    stats = pl.BlockSpec(
        (pl.Element(1), pl.Element(per * block // piece), pl.Element(1),
         pl.Element(piece)),
        lambda *g: (band_at(*g)[0], band_at(*g)[1] // piece, 0, 0))
    keys = pl.BlockSpec((1, block, D), lambda b, j, h: (b, j, 0))
    call = pl.pallas_call(
        functools.partial(_by_key_block_kernel, scale=scale, window=window,
                          block=block, per=per, rep=rep, seq_len=S),
        grid=(BHkv, blocks, rep),
        in_specs=[queries, keys, keys, queries, stats, stats],
        out_specs=[pl.BlockSpec((1, block, D),
                                lambda b, j, h: (b * rep + h, j, 0)),
                   keys, keys],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   jax.ShapeDtypeStruct((BHkv, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BHkv, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((rep, per * block, D), jnp.float32),
                        pltpu.VMEM((block, D), jnp.float32),
                        pltpu.VMEM((block, D), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=fa._BWD_VMEM_BYTES))
    with fa.annotate("swa_bwd"):
        return tuple(call(q, k, v, do, lse, delta))


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
    ap.add_argument("--walk", choices=("queries", "keys"), default="queries",
                    help="keys: the walk by key block in the module's place")
    args = ap.parse_args()
    globals()["S"] = args.seq
    if args.walk == "keys":
        fa._swa_bwd = _swa_bwd_by_key_block
    out = {"tree": os.path.relpath(args.tree, HERE), "walk": args.walk,
           "device": jax.devices()[0].device_kind, "shapes": {}}
    for name in args.shapes.split(","):
        H, Hkv, W = SHAPES[name]
        (q, k, v), cot = inputs(H, Hkv, S, D)
        tilings = [(None, None, None)]
        if args.sweep:
            tilings += [(None, None, c) for c in (512, 1024, 2048)
                        if c < W + 512]
            if hasattr(fa, "_band_ring"):
                tilings += [(None, None, None, h) for h in (1, 2, 4)
                            if (H // Hkv) % h == 0]
            if name == "laguna":
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
