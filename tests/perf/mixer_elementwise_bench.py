"""Microbenchmark of the recurrent mixers' elementwise stages alone on the
chip (``ops/mixer_elementwise.py``), at the shapes of the two cells that run
them: ``qwen3next-train-1chip-s8192`` (convolution over 8,192 columns of
[2, 8192, 12288] as q | k | v with the q / k L2 norm; gate after a norm over
heads of 128) and ``nemotron3nano-train-1chip-s16384`` (6,144 columns at
offset 4,096 of [1, 16384, 10304] as x | B | C, with bias; gate before a
norm over groups of 512). For each stage, the forward call and the gradient
of a sum of its results (the kernel form's gradient is its backward kernel
and the XLA ops that sum the parameters' cotangents and pad dx to the wide
array: its residuals are its inputs, no forward runs; the XLA form's runs
what it needs of the forward again), the kernel form against the XLA form:
DEVICE ms of the jitted module from a profiler trace, the bytes one read and
one write a pass move at bf16, and their share of the HBM peak. Not part of the benchmark:
PERF.md's Findings quote it.

    chiprun -- python tests/perf/mixer_elementwise_bench.py [--sweep]

``--sweep`` pokes the kernels' row block, column block and tile rows.
"""

import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.ops import mixer_elementwise as mixer  # noqa: E402
from deepspeed_tpu.ops.pallas import mixer_elementwise as kernels  # noqa: E402
from tests.perf.rows_to_tokens_bench import device_ms  # noqa: E402

HBM_GBS = 819.0          # v5e, benchmark/peaks.json
BF16, F32 = jnp.bfloat16, jnp.float32

# stage -> (entry arguments' shapes, keyword arguments, columns computed,
#           arrays of that many columns read + written: forward, backward)
STAGES = {
    "qwen3next_conv": (
        [((2, 8192, 12288), BF16), ((4, 8192), F32)],
        dict(runs=((2048, 128 ** -0.5), (2048, 1.0), (4096, None)),
             head_width=128), 8192, 2, 3),
    "nemotron_conv": (
        [((1, 16384, 10304), BF16), ((4, 6144), F32), ((6144,), F32)],
        dict(offset=4096, runs=((4096, None), (1024, None), (1024, None))),
        6144, 2, 3),
    "qwen3next_norm": (
        [((2, 8192, 4096), BF16), ((2, 8192, 12288), BF16), ((128,), F32)],
        dict(group=128, eps=1e-6, gate_first=False, offset=8192),
        4096, 3, 5),
    "nemotron_norm": (
        [((1, 16384, 4096), BF16), ((1, 16384, 10304), BF16),
         ((4096,), F32)],
        dict(group=512, eps=1e-5, gate_first=True), 4096, 3, 5),
}


def forms(name):
    conv = name.endswith("conv")
    return {"kernel": mixer.conv_act if conv else mixer.gated_group_norm,
            "xla": mixer.conv_act_xla if conv else mixer.gated_group_norm_xla}


def measure(name, form):
    shapes, kw, columns, passes_fwd, passes_bwd = STAGES[name]
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes))
    args = [jax.random.normal(k, s, F32).astype(d)
            for k, (s, d) in zip(keys, shapes)]
    fn = forms(name)[form]

    def total(*a):
        return sum(t.astype(F32).sum()
                   for t in jax.tree_util.tree_leaves(fn(*a, **kw)))

    rows = shapes[0][0][0] * shapes[0][0][1]
    out = {}
    for what, jitted, passes in (
            ("fwd", jax.jit(lambda *a: fn(*a, **kw)), passes_fwd),
            ("grad", jax.jit(jax.grad(
                total, argnums=tuple(range(len(args))))), passes_bwd)):
        ms, top = device_ms(jitted, *args, top=12)
        # the Pallas calls alone: a module also holds the pad of dx to the
        # wide array, the parameters' sums and, where a jit's parameter is
        # not laid out as a matmul's output is, a copy of it
        calls = round(sum(t for label, t in top if "custom-call" in label),
                      4)
        gb = rows * columns * 2 * passes / 1e9
        out[what] = {"ms": ms, "pallas_ms": calls, "gb": round(gb, 3),
                     "hbm_share": round(
                         gb / (calls or ms) * 1e3 / HBM_GBS, 3)}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--stages", default=",".join(STAGES))
    args = ap.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for name in args.stages.split(","):
        for form in ("kernel", "xla"):
            print(json.dumps({"stage": name, "form": form,
                              **measure(name, form)}), flush=True)
    if not args.sweep:
        return
    for rows_blocks, columns, tile in itertools.product(
            ((1024, 512, 256), (512, 256), (256,)), ((512, 256, 128),
                                                     (256, 128)), (32, 64)):
        kernels._ROW_BLOCKS = rows_blocks + (128, 64)
        kernels._COLUMN_BLOCKS = columns
        kernels._ROWS = tile
        kernels._conv_rule.cache_clear()
        kernels._norm_rule.cache_clear()
        for name in args.stages.split(","):
            poked = {"stage": name, "row_block": rows_blocks[0],
                     "column_block": columns[0], "tile_rows": tile}
            try:
                print(json.dumps({**poked, **measure(name, "kernel")}),
                      flush=True)
            except Exception as e:  # boundary: the compiler's refusal
                print(json.dumps({**poked, "refused": str(e)[:300]}),
                      flush=True)


if __name__ == "__main__":
    main()
