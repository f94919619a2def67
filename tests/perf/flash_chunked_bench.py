"""Microbenchmark of the causal CHUNKED flash kernels alone on the chip
(``ops/pallas/flash_attention.py``: ``_fwd_kernel_chunked``,
``_bwd_dq_kernel_chunked``, ``_bwd_dkv_kernel_chunked``), one line a kernel
and a (block, chunk) plan, at the shapes of the cells that run them —
``laguna-train-1chip-s16384``'s full layers (48 query / 8 KV heads x 16,384 x
head_dim 128, bf16), ``qwen3next-train-1chip-s8192`` (2 x 16 / 2 heads x
8,192 x 256) and ``olmoe-train-1chip-s4096`` (4 x 16 / 16 x 4,096 x 128), and
at ``d64s32k`` (16 heads x 32,768 x 64: the shape whose VMEM overflow set the
first chunk budget) — with the grid steps a head walks and the us a step:
every time a DEVICE time of the Pallas custom call from a profiler trace.
``--plans`` sweeps (block, chunk) pairs (a plan that does not tile a shape's
S is a ``skipped`` line, one the compiler refuses a ``refused`` line with
the refusal's first line); without it the one plan is what
``flash_attention`` of the tree under test picks. ``--tree=DIR`` times
another checkout's kernels (the parent's, unpacked in a git-ignored
directory) with this harness; ``--parent FILE`` reads that run's lines and
adds what a grid step the other tree walked and this one does not costs
(PR 39: the steps above a causal diagonal, whose loops were empty). Not part
of the benchmark: PERF.md's Findings quote it.

    chiprun -- python tests/perf/flash_chunked_bench.py \
        --plans 512x512,512x1024,512x2048,512x4096
    chiprun -- python tests/perf/flash_chunked_bench.py --tree=_parent \
        --out flash_chunked_parent
    chiprun -- python tests/perf/flash_chunked_bench.py \
        --parent chiprun_out/flash_chunked_parent.jsonl
"""

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, next((os.path.abspath(a.split("=", 1)[1])
                         for a in sys.argv if a.startswith("--tree=")), HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import trace_reduce  # noqa: E402
from benchmark.tools import trace_look  # noqa: E402

# the module, not the function the package re-exports under its name
fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

PEAK = 197e12                       # bf16 flops a second, one v5e
# B, query heads, KV heads, S, head_dim
SHAPES = {"laguna": (1, 48, 8, 16384, 128),
          "qwen3next": (2, 16, 2, 8192, 256),
          "olmoe": (4, 16, 16, 4096, 128),
          "d64s32k": (1, 16, 16, 32768, 64)}
# products a score tile takes in each kernel (q·kᵀ, p·v | q·kᵀ, do·vᵀ, ds·k |
# q·kᵀ, do·vᵀ, pᵀ·do, dsᵀ·q)
PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def kernel_ms(fn, *args, reps=5):
    """Device ms a call of the ONE Pallas custom call of the jitted ``fn``
    (a profiler trace: no dispatch, fence or XLA cast beside it). Off the
    chip (``--rehearse-cpu``) the call is only run: no time is read."""
    jax.block_until_ready(fn(*args))
    if jax.default_backend() != "tpu":
        return None
    where = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(where):
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
        trace = trace_reduce.load(trace_look.find_xplane(where))
    finally:
        shutil.rmtree(where, ignore_errors=True)
    plane = sorted(trace.devices)[0]
    calls = [e for e in trace_reduce.ops(trace, plane)
             if trace_reduce.is_pallas(e.name)]
    assert len(calls) == reps, [e.name[:80] for e in calls]
    return sum(e.end - e.start for e in calls) / reps / 1e6


def steps_a_head(S, block, chunk, keys):
    """Grid steps a (batch, head) row of a causal chunked kernel walks in
    the tree under test: its pair list, or the rectangle it had before."""
    if hasattr(fa, "_pair_walk"):
        return len(fa._pair_walk(S, block, chunk, True, keys)[0])
    return (S // block) * (S // chunk)


def picked_plan(B, H, Hkv, S, D, dtype):
    """(block, chunk) that ``flash_attention`` of the tree under test picks
    for a causal call of this shape, read off its call of the kernels' entry
    (chunk 0: the whole-row kernels, which this script does not time)."""
    seen = {}
    real = fa._flash_attention

    def spy(q, k, v, scale, causal, block_q, block_k, chunk, *rest):
        seen["plan"] = (block_q, chunk)
        return real(q, k, v, scale, causal, block_q, block_k, chunk, *rest)

    fa._flash_attention = spy
    try:
        jax.eval_shape(
            lambda *a: fa.flash_attention(*a, causal=True, interpret=False),
            jax.ShapeDtypeStruct((B, H, S, D), dtype),
            *(jax.ShapeDtypeStruct((B, Hkv, S, D), dtype),) * 2)
    finally:
        fa._flash_attention = real
    return seen["plan"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="flash_chunked_bench")
    ap.add_argument("--shapes", default="laguna,qwen3next,olmoe")
    ap.add_argument("--plans", default=None,
                    help="block x chunk pairs to time at every shape, e.g. "
                         "512x1024,512x4096 (default: the one plan "
                         "flash_attention picks for the shape)")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--tree", default=HERE,
                    help="--tree=DIR: the checkout whose kernels are timed")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the control flow at S 512 in the interpreter: "
                         "no time is read")
    ap.add_argument("--parent", default=None,
                    help="the lines another tree's run wrote: adds the cost "
                         "of a grid step it walked and this tree does not")
    args = ap.parse_args()
    before = {}
    if args.parent:
        with open(args.parent) as f:
            before = {(ln["shape"], ln["kernel"]): ln
                      for ln in map(json.loads, f) if "kernel" in ln}
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        sys.exit("no TPU here: a kernel's time comes only from the chip")
    dtype = jnp.dtype(args.dtype)
    lines = [{"device": dev.device_kind, "platform": dev.platform,
              "tree": os.path.relpath(args.tree, HERE), "dtype": dtype.name}]
    print(json.dumps(lines[0]), flush=True)

    def note(line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    for name in args.shapes.split(","):
        B, H, Hkv, S, D = SHAPES[name]
        if args.rehearse_cpu:
            B, H, Hkv, S = 1, 2 * H // Hkv, 2, 512
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, do = (jax.random.normal(k, (B * H, S, D), dtype)
                 for k in (ks[0], ks[3]))
        k, v = (jax.random.normal(k, (B * Hkv, S, D), dtype)
                for k in ks[1:3])
        if args.plans:
            plans = [tuple(int(x) for x in p.split("x"))
                     for p in args.plans.split(",")]
        elif args.rehearse_cpu:         # S 512 is a whole-row call
            plans = [(64, 128)]
        else:
            plans = [picked_plan(B, H, Hkv, S, D, dtype)]
        for block, chunk in plans:
            plan = f"{block}x{chunk}"
            if not chunk or S % chunk or chunk % block:
                note({"shape": name, "plan": plan,
                      "skipped": f"does not tile S={S} in chunks of blocks"})
                continue
            static = (D ** -0.5, True, block, block, chunk,
                      args.rehearse_cpu, H, Hkv)
            fwd = jax.jit(
                lambda q, k, v: fa._flash_fwd_chunked(q, k, v, *static))
            # dq or dk, dv alone: XLA drops the call whose results nobody
            # reads
            bwd = {"dq": jax.jit(
                lambda *a: fa._flash_bwd_chunked(*a, *static)[0]),
                "dkv": jax.jit(
                    lambda *a: fa._flash_bwd_chunked(*a, *static)[1:])}
            try:
                o, lse = fwd(q, k, v)
                times = (("fwd", kernel_ms(fwd, q, k, v)),
                         ("dq", kernel_ms(bwd["dq"], q, k, v, o, lse, do)),
                         ("dkv", kernel_ms(bwd["dkv"], q, k, v, o, lse, do)))
            except Exception as e:  # noqa: BLE001 — the compiler's refusal
                note({"shape": name, "plan": plan,
                      "refused": str(e).splitlines()[0][:300]})
                continue
            tiles = (S // block) * (S // block + 1) // 2    # a head
            for kernel, ms in times:
                steps = steps_a_head(S, block, chunk, kernel != "dkv")
                flops = 2 * PRODUCTS[kernel] * B * H * (S * (S + 1) // 2) * D
                line = {"shape": name, "plan": plan, "kernel": kernel,
                        "rows": B * H, "S": S, "D": D, "block": block,
                        "chunk": chunk, "grid_steps_a_head": steps,
                        "tiles_a_head": tiles}
                if ms is not None:
                    line.update(
                        ms=ms, us_a_head=ms * 1e3 / (B * H),
                        us_a_step=ms * 1e3 / (B * H * steps),
                        roofline_pct=100 * flops / PEAK / (ms / 1e3))
                was = before.get((name, kernel))
                if ms and was and was["grid_steps_a_head"] > steps:
                    line.update(
                        parent_ms=was["ms"],
                        parent_grid_steps_a_head=was["grid_steps_a_head"],
                        us_a_step_no_longer_walked=(was["ms"] - ms) * 1e3 / (
                            B * H * (was["grid_steps_a_head"] - steps)))
                note(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out + ".jsonl"), "w") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
