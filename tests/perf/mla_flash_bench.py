"""Microbenchmark of the causal CHUNKED flash kernels at latent attention's
two widths on the chip (``kanana2-train-1chip-s16384``'s layers: 32 heads x
16,384, q and k 192 wide, v 128 wide, bf16), one line a kernel and a plan:
the device ms of each Pallas custom call from a profiler trace
(``flash_chunked_bench.device_ms``: ``fwd``; the single-pass ``bwd``, or
``dq`` and ``dkv`` in a tree from before PR 49 — ``--tree=DIR``), ``bwd_xla``
(the XLA passes round the backward's kernels: delta, the dq slabs' sum and
cast), the grid steps a head walks and the share of the bf16 peak on the
flops the kernel EXECUTES (a causal half of its products of 192 or 128
columns). ``--plans`` sweeps (block, chunk) pairs; ``--dtype float32``.
Not part of the benchmark: PERF.md's Findings quote it.

    chiprun -- python tests/perf/mla_flash_bench.py \
        [--plans 512x4096,512x1024] [--heads 32] [--rehearse-cpu]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flash_chunked_bench import (PEAK, bwd_times, fa,  # noqa: E402
                                 kernel_ms, steps_a_head)

S, DQK, DV = 16384, 192, 128
# columns the products of each kernel contract or produce, a score element:
# fwd q·k + p·v; bwd k·q + v·do + p·do + ds·q + dsᵀ·k; before PR 49 dq q·k +
# do·v + ds·k and dkv q·k + do·v + pᵀ·do + dsᵀ·q
COLUMNS = {"fwd": DQK + DV, "bwd": 3 * DQK + 2 * DV, "dq": 2 * DQK + DV,
           "dkv": 2 * DQK + 2 * DV}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--plans", default="512x4096")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--tree", default=None,
                    help="--tree=DIR: the checkout whose kernels are timed "
                         "(read by flash_chunked_bench as it is imported)")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--out", default="mla_flash_bench")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        sys.exit("no TPU here: a kernel's time comes only from the chip")
    seq, heads = (512, 2) if args.rehearse_cpu else (S, args.heads)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    dtype = jnp.dtype(args.dtype)
    q, k = (jax.random.normal(key, (heads, seq, DQK), dtype)
            for key in ks[:2])
    v, do = (jax.random.normal(key, (heads, seq, DV), dtype)
             for key in ks[2:])
    lines = [{"device": dev.device_kind, "platform": dev.platform,
              "tree": args.tree or ".", "dtype": dtype.name}]
    print(json.dumps(lines[0]), flush=True)
    for plan in args.plans.split(","):
        block, chunk = (int(x) for x in plan.split("x"))
        if args.rehearse_cpu:
            block, chunk = 64, 128
        static = (DQK ** -0.5, True, block, block, chunk, args.rehearse_cpu)
        fwd = jax.jit(lambda q, k, v: fa._flash_fwd_chunked(q, k, v, *static))
        try:
            o, lse = fwd(q, k, v)
            bwd = jax.jit(lambda *a: fa._flash_bwd_chunked(*a, *static))
            times = [("fwd", kernel_ms(fwd, q, k, v))] + bwd_times(
                bwd, q, k, v, o, lse, do)
        except Exception as e:  # noqa: BLE001 — a plan the compiler refuses
            line = {"plan": plan, "refused": str(e).splitlines()[0][:300]}
            lines.append(line)
            print(json.dumps(line), flush=True)
            continue
        for kernel, ms in times:
            line = {"plan": plan, "kernel": kernel, "heads": heads, "S": seq,
                    "block": block, "chunk": chunk}
            if ms is not None:
                line["ms"] = ms
            if kernel != "bwd_xla":         # which has no grid, no products
                steps = steps_a_head(seq, block, chunk, True, kernel)
                line["grid_steps_a_head"] = steps
                if ms is not None:
                    flops = 2 * COLUMNS[kernel] * heads * (
                        seq * (seq + 1) // 2)
                    line.update(us_a_step=ms * 1e3 / (heads * steps),
                                roofline_pct=100 * flops / PEAK / (ms / 1e3))
            lines.append(line)
            print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out + ".jsonl"), "w") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
