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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="gmm_tile_bench")
    args = ap.parse_args()
    sizes = jnp.full((G,), ROWS // G, jnp.int32)
    mb = gm._mb
    out = []
    for (k, n) in ((2560, 768), (768, 2560)):
        ks = jax.random.split(jax.random.PRNGKey(k), 3)
        lhs = jax.random.normal(ks[0], (M, k), jnp.bfloat16)
        rhs = (0.02 * jax.random.normal(ks[1], (G, k, n))).astype(
            jnp.bfloat16)
        dout = jax.random.normal(ks[2], (M, n), jnp.bfloat16)
        flops = 2 * ROWS * k * n
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
