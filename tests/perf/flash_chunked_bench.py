"""Microbenchmark of the CHUNKED flash kernels alone on the chip
(``ops/pallas/flash_attention.py``: ``_fwd_kernel_chunked`` and the
single-pass ``_bwd_kernel_chunked``; in a tree from before PR 49
``_bwd_dq_kernel_chunked`` and ``_bwd_dkv_kernel_chunked``), one line a kernel
and a (block, chunk) plan — the backward's Pallas calls a line each (``bwd``,
or ``dq`` and ``dkv``) and ``bwd_xla`` the XLA passes round them (delta; the
sum of the dq slabs, scale and cast, or the three casts of float32 dq / dk /
dv), so that a tree's whole backward is the sum of its lines other than
``fwd`` — at the shapes of the cells that run them —
``laguna-train-1chip-s16384``'s full layers (48 query / 8 KV heads x 16,384 x
head_dim 128, bf16), ``qwen3next-train-1chip-s8192`` (2 x 16 / 2 heads x
8,192 x 256) and ``olmoe-train-1chip-s4096`` (4 x 16 / 16 x 4,096 x 128), and
at ``d64s32k`` (16 heads x 32,768 x 64: the shape whose VMEM overflow set the
first chunk budget) — with the grid steps a head walks, the us a step and
(PR 67) the line through the plan and the plan at half its chunk — what a
grid step costs before its first tile (``us_a_step_fixed``) and a tile with
that taken off (``us_a_tile``): every time a DEVICE time from a profiler
trace. ``--full`` drops the causal
mask (ring / Ulysses attention's calls: the rectangle). ``--plans`` sweeps
(block, chunk) pairs (a plan that does not tile a shape's S is a ``skipped``
line, one the compiler refuses a ``refused`` line with
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
# products a score tile takes in each kernel (q·kᵀ, p·v | k·qᵀ, v·doᵀ, p·do,
# ds·q, dsᵀ·k | before PR 49 q·kᵀ, do·vᵀ, ds·k | q·kᵀ, do·vᵀ, pᵀ·do, dsᵀ·q)
PRODUCTS = {"fwd": 2, "bwd": 5, "dq": 3, "dkv": 4}
# the backward's Pallas calls in the tree under test, and the scope each runs
# under: the device plane names a call after its scope (``%flash_bwd_dkv.3``)
BWD_KERNELS = (("bwd",) if hasattr(fa, "_bwd_kernel_chunked")
               else ("dq", "dkv"))
SCOPES = {"fwd": "flash_fwd_chunk", "bwd": "flash_bwd_chunk",
          "dq": "flash_bwd_dq", "dkv": "flash_bwd_dkv"}


def device_ms(fn, *args, reps=5):
    """({kernel: device ms a call of its Pallas custom call}, device ms a
    call of every other operation together) of the jitted ``fn``, from a
    profiler trace: no dispatch and no fence in either. Off the chip
    (``--rehearse-cpu``) the call is only run: no time is read."""
    jax.block_until_ready(fn(*args))
    if jax.default_backend() != "tpu":
        return None, None
    where = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(where):
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
        trace = trace_reduce.load(trace_look.find_xplane(where))
    finally:
        shutil.rmtree(where, ignore_errors=True)
    plane = sorted(trace.devices)[0]
    events = trace_reduce.ops(trace, plane)
    calls, rest = {}, 0.0
    for i, ns in trace_reduce.self_times(events).items():
        name = events[i].name
        if not trace_reduce.is_pallas(name):
            rest += ns
            continue
        kernel = next(k for k, scope in SCOPES.items() if scope in name)
        calls.setdefault(kernel, []).append(ns)
    assert all(len(ns) == reps for ns in calls.values()), {
        k: len(ns) for k, ns in calls.items()}
    return ({k: sum(ns) / reps / 1e6 for k, ns in calls.items()},
            rest / reps / 1e6)


def kernel_ms(fn, *args, reps=5):
    """Device ms a call of the ONE Pallas custom call of ``fn``."""
    calls, _ = device_ms(fn, *args, reps=reps)
    if calls is None:
        return None
    (ms,) = calls.values()
    return ms


def bwd_times(bwd, *args):
    """[(line's name, ms)] of a jitted whole ``_flash_bwd_chunked``: its
    Pallas calls (``BWD_KERNELS``) and ``bwd_xla``, everything round them."""
    calls, rest = device_ms(bwd, *args)
    if calls is None:
        return [(name, None) for name in BWD_KERNELS + ("bwd_xla",)]
    return [(name, calls[name]) for name in BWD_KERNELS] + [("bwd_xla", rest)]


def steps_a_head(S, block, chunk, causal, kernel):
    """Grid steps a (batch, head) row of a chunked kernel walks in the tree
    under test: its pair list, or the rectangle it had before."""
    if not hasattr(fa, "_pair_walk"):
        return (S // block) * (S // chunk)
    # before PR 49 the last argument told the key blocks' walk (dkv's)
    # from the query blocks'; since, the backward's order from the forward's
    last = kernel == "bwd" if "bwd" in BWD_KERNELS else kernel != "dkv"
    return len(fa._pair_walk(S, block, chunk, causal, last)[0])


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


def step_and_tile_us(us, steps, half_us, half_steps, tiles):
    """A head's time as a line through two plans that walk the SAME tiles in
    different numbers of grid steps (the plan and the one at half its
    chunk): ``us = steps x us_a_step_fixed + tiles x us_a_tile`` — what a
    grid step costs before its first tile, and a tile with that taken off
    (Findings PR 48 read it by hand)."""
    fixed = (half_us - us) / (half_steps - steps)
    return {"half_chunk_us_a_head": half_us, "us_a_step_fixed": fixed,
            "us_a_tile": (us - steps * fixed) / tiles}


def plan_times(static, q, k, v, do):
    """[(line's name, ms)] of the forward call and the whole backward under
    one plan (``static``: the kernels' arguments after the operands)."""
    fwd = jax.jit(lambda q, k, v: fa._flash_fwd_chunked(q, k, v, *static))
    bwd = jax.jit(lambda *a: fa._flash_bwd_chunked(*a, *static))
    o, lse = fwd(q, k, v)
    return [("fwd", kernel_ms(fwd, q, k, v))] + bwd_times(
        bwd, q, k, v, o, lse, do)


def half_chunk_ms(static, block, operands):
    """{kernel: ms} of the Pallas calls at HALF the plan's chunk, the second
    point of ``step_and_tile_us``'s line; {} where half a chunk is no plan
    (under a block, or refused)."""
    chunk = static[4] // 2
    if chunk < block:
        return {}
    try:
        return dict(plan_times(static[:4] + (chunk,) + static[5:],
                               *operands))
    except Exception:  # noqa: BLE001 — the compiler's refusal: no line
        return {}


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
    ap.add_argument("--full", action="store_true",
                    help="no causal mask: the pairs' rectangle")
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
              "tree": os.path.relpath(args.tree, HERE), "dtype": dtype.name,
              "causal": not args.full}]
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
            causal = not args.full
            static = (D ** -0.5, causal, block, block, chunk,
                      args.rehearse_cpu, H, Hkv)
            try:
                times = plan_times(static, q, k, v, do)
            except Exception as e:  # noqa: BLE001 — the compiler's refusal
                note({"shape": name, "plan": plan,
                      "refused": str(e).splitlines()[0][:300]})
                continue
            n = S // block
            tiles = n * (n + 1) // 2 if causal else n * n   # a head
            # off the chip nothing is timed: no second point either
            half = ({} if args.rehearse_cpu
                    else half_chunk_ms(static, block, (q, k, v, do)))
            scores = S * (S + 1) // 2 if causal else S * S
            for kernel, ms in times:
                line = {"shape": name, "plan": plan, "kernel": kernel,
                        "rows": B * H, "S": S, "D": D, "block": block,
                        "chunk": chunk}
                if kernel == "bwd_xla":     # no grid and no products
                    note(dict(line, **({} if ms is None else {"ms": ms})))
                    continue
                steps = steps_a_head(S, block, chunk, causal, kernel)
                flops = 2 * PRODUCTS[kernel] * B * H * scores * D
                line.update(grid_steps_a_head=steps, tiles_a_head=tiles)
                if ms is not None:
                    line.update(
                        ms=ms, us_a_head=ms * 1e3 / (B * H),
                        us_a_step=ms * 1e3 / (B * H * steps),
                        roofline_pct=100 * flops / PEAK / (ms / 1e3))
                if half.get(kernel):
                    line.update(step_and_tile_us(
                        ms * 1e3 / (B * H), steps,
                        half[kernel] * 1e3 / (B * H),
                        steps_a_head(S, block, chunk // 2, causal, kernel),
                        tiles))
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
