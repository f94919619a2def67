"""Sweep of the grouped matmul's tiles on the chip at widths that are no
powers of two — SmallThinker's experts, (K, N) = (2560, 768) for gate / up
and (768, 2560) for down, M = the cell's slab of 49,152 rows of which a
uniform router fills 24,576 over 16 experts — each of the three products
alone (``gmm`` forward, ``gmm`` against the transposed weights for ``dlhs``,
``tgmm`` for ``drhs``), every time a DEVICE time from a profiler trace
(``tests/perf/rows_to_tokens_bench.device_ms``). A tile the compiler refuses
(scoped VMEM) is reported as such. Not part of the benchmark: the winner is
written beside ``ops/pallas/grouped_matmul.TILE_*``.

    chiprun -- python tests/perf/gmm_tile_bench.py [--out NAME]

``--set nemotron`` (PR 40): a width that no multiple of 128 divides —
Nemotron-3-Nano's experts, 1,856 = 29 x 64 against 2,688 = 21 x 128, a slab
of 12,288 rows of which 6,144 are filled over 8 experts — taken two ways:
PADDED with zeros to 1,920 and tiled by divisors of that, or as it is with
ONE whole-dimension block of 1,856 on that side. ``mxu_pct`` counts the
flops of the 1,856 in both.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402
from tests.perf.rows_to_tokens_bench import device_ms  # noqa: E402

M, G, ROWS = 49152, 16, 24576
# (rows, contraction, columns) candidates a product; the first of each list
# is what ``_clip`` gives the committed ``TILE_*`` at that shape
FWD = {(2560, 768): [(256, 1280, 768), (256, 512, 768), (256, 2560, 768),
                     (512, 1280, 768), (512, 640, 768), (256, 640, 768),
                     (512, 512, 768), (128, 2560, 768), (512, 1280, 384)],
       (768, 2560): [(256, 768, 640), (256, 768, 512), (256, 768, 1280),
                     (512, 768, 640), (512, 768, 1280), (256, 768, 2560),
                     (128, 768, 2560), (512, 384, 1280), (1024, 768, 640)]}
# dlhs = gmm(dout [M, N], rhs^T): contraction N, columns K
DLHS = {(2560, 768): [(512, 768, 640), (512, 768, 1280), (256, 768, 1280),
                      (256, 768, 2560), (512, 384, 1280), (1024, 768, 640),
                      (512, 768, 512)],
        (768, 2560): [(512, 640, 768), (512, 1280, 768), (256, 1280, 768),
                      (256, 2560, 768), (512, 512, 768), (1024, 640, 768),
                      (512, 2560, 768)]}
# drhs = tgmm(lhs^T [K, M], dout [M, N]): (rows of M a step, K, N)
DRHS = {(2560, 768): [(512, 640, 768), (512, 1280, 768), (1024, 640, 768),
                      (256, 1280, 768), (512, 512, 768), (1024, 1280, 768),
                      (512, 2560, 768), (512, 1280, 384)],
        (768, 2560): [(512, 768, 640), (512, 768, 1280), (1024, 768, 640),
                      (256, 768, 1280), (512, 768, 512), (1024, 768, 1280),
                      (512, 768, 2560), (512, 384, 1280)]}


# the second set: (K, N) as the arrays are handed over — 1,920 is the padded
# 1,856, 1,856 the width as it is (one whole-dimension block on that side)
NEMOTRON = dict(M=12288, G=8, ROWS=6144, real={1920: 1856})
NEMOTRON_FWD = {
    (2688, 1920): [(256, 896, 640), (256, 2688, 640), (256, 896, 1920),
                   (256, 2688, 384), (512, 896, 640), (256, 1344, 640)],
    (2688, 1856): [(256, 896, 1856), (256, 2688, 1856), (512, 896, 1856),
                   (256, 384, 1856)],
    (1920, 2688): [(256, 1920, 896), (256, 640, 896), (256, 1920, 384),
                   (512, 1920, 896), (256, 1920, 2688), (256, 960, 896)],
    (1856, 2688): [(256, 1856, 896), (256, 1856, 384), (512, 1856, 896),
                   (256, 1856, 2688)]}
NEMOTRON_DLHS = {       # dout [M, N] x rhs^T: contraction N, columns K
    (2688, 1920): [(512, 640, 896), (512, 1920, 896), (256, 1920, 896),
                   (512, 640, 384), (512, 960, 896)],
    (2688, 1856): [(512, 1856, 896), (256, 1856, 896), (512, 1856, 384)],
    (1920, 2688): [(512, 896, 640), (512, 896, 1920), (512, 384, 640),
                   (256, 896, 1920), (512, 1344, 640)],
    (1856, 2688): [(512, 896, 1856), (256, 896, 1856), (512, 384, 1856)]}
NEMOTRON_DRHS = {       # lhs^T [K, M] x dout [M, N]
    (2688, 1920): [(512, 896, 640), (512, 896, 1920), (512, 384, 640),
                   (1024, 896, 640), (512, 1344, 640)],
    (2688, 1856): [(512, 896, 1856), (512, 384, 1856), (256, 896, 1856)],
    (1920, 2688): [(512, 640, 896), (512, 1920, 896), (512, 640, 384),
                   (1024, 640, 896), (512, 960, 896)],
    (1856, 2688): [(512, 1856, 896), (512, 1856, 384), (256, 1856, 896)]}


def main():
    global M, G, ROWS, FWD, DLHS, DRHS
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="gmm_tile_bench")
    ap.add_argument("--set", default="smallthinker",
                    choices=("smallthinker", "nemotron"))
    args = ap.parse_args()
    real = {}
    if args.set == "nemotron":
        M, G, ROWS, real = (NEMOTRON[k] for k in ("M", "G", "ROWS", "real"))
        FWD, DLHS, DRHS = NEMOTRON_FWD, NEMOTRON_DLHS, NEMOTRON_DRHS
    sizes = jnp.full((G,), ROWS // G, jnp.int32)
    mb = gm._mb
    out = []
    for (k, n) in FWD:
        ks = jax.random.split(jax.random.PRNGKey(k), 3)
        lhs = jax.random.normal(ks[0], (M, k), jnp.bfloat16)
        rhs = (0.02 * jax.random.normal(ks[1], (G, k, n))).astype(
            jnp.bfloat16)
        dout = jax.random.normal(ks[2], (M, n), jnp.bfloat16)
        flops = 2 * ROWS * real.get(k, k) * real.get(n, n)
        products = {
            "fwd": (FWD, lambda t: jax.jit(lambda a, b: mb.gmm(
                a, b, sizes, a.dtype, t)), (lhs, rhs)),
            "dlhs": (DLHS, lambda t: jax.jit(lambda d, b: mb.gmm(
                d, b, sizes, d.dtype, t, transpose_rhs=True)), (dout, rhs)),
            "drhs": (DRHS, lambda t: jax.jit(lambda a, d: mb.tgmm(
                a.swapaxes(0, 1), d, sizes, a.dtype, t,
                num_actual_groups=G)), (lhs, dout))}
        for name, (tiles, build, operands) in products.items():
            for i, tile in enumerate(tiles[(k, n)]):
                row = {"product": name, "K": k, "N": n, "tile": tile,
                       "committed": i == 0}
                try:
                    ms = device_ms(build(tile), *operands)
                    row.update(ms=ms, mxu_pct=round(
                        100 * flops / (ms * 1e-3) / 197e12, 2))
                except Exception as e:  # a refused tile is a result
                    row["refused"] = str(e).splitlines()[0][:200]
                print(json.dumps(row), flush=True)
                out.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out + ".json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
