"""Microbenchmark of the residual streams' mixers alone on the chip
(``models/hyper_connections.py``, ``ops/pallas/mhc_stream.py``) at the shape
of the cell that runs them, ``xing4-train-1chip-s4096``: a stream of
[1, 4096, 4 x 3584] bf16, ``phi`` [14336, 24] float32. Each of the six
kernel passes alone (``mix``: coefficients + read; ``write``; the two
backward passes; a trunk's ``tile`` and ``sum_streams``), then one whole
branch — ``mix``, a branch that is the identity, ``write`` — forward and as
the gradient of a sum of its result,
the kernel form against the ``jnp`` form: DEVICE ms of the jitted module
from a profiler trace, the Pallas calls' part of it, the bytes the pass HAS
to move (every array it reads or writes once, at bf16) and their share of
the HBM peak. Not part of the benchmark: PERF.md's Findings quote it.

    chiprun -- python tests/perf/mhc_stream_bench.py [--sweep]

``--sweep`` pokes the row tile and, at the row tile the program runs, the
column slabs a turn of each pass's loop (``SLABS_A_TURN``; all of a
stream's slabs = the body unrolled whole, PR 57's form), each pass alone and
inside the branch.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.models import hyper_connections as hc  # noqa: E402
from deepspeed_tpu.ops.pallas import mhc_stream as kernels  # noqa: E402
from deepspeed_tpu.utils.platform import is_tpu_backend  # noqa: E402
from tests.perf.rows_to_tokens_bench import device_ms  # noqa: E402

HBM_GBS = 819.0          # v5e, benchmark/peaks.json
BF16, F32 = jnp.bfloat16, jnp.float32
N = 4


def passes(T, C, dtype=BF16):
    """pass -> (arguments' shapes, GB it has to move at bf16)."""
    stream, narrow = T * N * C * 2 / 1e9, T * C * 2 / 1e9
    wide, thin = ((T, N * C), dtype), ((T, C), dtype)
    return {
        "mix": ([wide, ((N * C, 128), dtype), ((8, 128), F32)],
                stream + narrow),
        "write": ([wide, thin, ((24, T), F32)], 2 * stream + narrow),
        "write_backward": ([wide, wide, thin, ((24, T), F32)],
                           3 * stream + 2 * narrow),
        "mix_backward": ([wide, wide, thin, ((32, T), F32), ((24, T), F32),
                          ((8, 128), F32), ((128, N * C), dtype)],
                         3 * stream + narrow),
        "tile": ([thin], stream + narrow),
        "sum_streams": ([wide], stream + narrow),
        # a branch forward: mix + write; its gradient: the two backward
        # passes
        "branch_fwd": (None, 3 * stream + 2 * narrow),
        "branch_grad": (None, 6 * stream + 3 * narrow)}


def randoms(shapes, scale=1.0):
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes))
    return [(scale * jax.random.normal(k, s, F32)).astype(d)
            for k, (s, d) in zip(keys, shapes)]


def timed(jitted, args, gb):
    ms, top = device_ms(jitted, *args, top=12)
    each = [[label.split(" ")[0].strip("%"), t] for label, t in top
            if "custom-call" in label]
    calls = round(sum(t for _, t in each), 4)
    return {"ms": ms, "pallas_ms": calls, "gb": round(gb, 3),
            "hbm_share": round(gb / (calls or ms) * 1e3 / HBM_GBS, 3),
            **({"calls": each} if len(each) > 1 else {})}


def measure_pass(name, T, C, rows=kernels.ROW_TILE, slabs=None):
    """The pass alone; ``slabs`` pokes its loop's slabs a turn (the pass is
    then called under its ``jax.jit`` wrapper's skin, which would hand back
    its first trace)."""
    shapes, gb = passes(T, C)[name]
    fn = getattr(kernels, name)
    looped, was = name in kernels.SLABS_A_TURN, kernels.SLABS_A_TURN.get(name)
    if slabs is not None and looped:
        kernels.SLABS_A_TURN[name], fn = slabs, fn.__wrapped__
    plan = kernels.StreamPlan(T, N, C, rows, 20, 1e-6, (-30.0, 30.0))
    try:
        return {"rows": rows, "slabs_a_turn": kernels.SLABS_A_TURN.get(name),
                **timed(jax.jit(lambda *a: fn(*a, plan,
                                              not is_tpu_backend())),
                        randoms(shapes, 0.1), gb)}
    finally:
        if looped:
            kernels.SLABS_A_TURN[name] = was


def measure_branch(form, T, C, slabs=None):
    """mix -> identity -> write on one stream, the form forced; ``slabs``
    {pass: slabs a turn} pokes the kernels' loops (a pass between two others
    can read otherwise than alone: ``mix`` does)."""
    x, = randoms([((1, T, N * C), BF16)])
    mixer = hc.StreamMixer(n=N, phi_std=0.02, gate_mean=0.25, gate_std=0.05,
                           bias_std=0.5)
    takes, was = kernels.takes, dict(kernels.SLABS_A_TURN)
    if form == "jnp":
        kernels.takes = lambda *a: False
    kernels.SLABS_A_TURN.update(slabs or {})
    jax.clear_caches()       # the passes' jit wrappers keep their first trace
    try:
        params = jax.jit(lambda x: mixer.init(
            jax.random.PRNGKey(1), x))(x)["params"]

        def branch(params, x):
            (u, (_, post, res), through), _ = nn.apply(
                hc.mix, mixer, mutable=["stats"])({"params": params}, x)
            return hc.write(through, u, post, res)

        out = {}
        for what, jitted in (
                ("fwd", jax.jit(branch)),
                ("grad", jax.jit(jax.grad(lambda p, x: branch(p, x).astype(
                    F32).sum(), argnums=(0, 1))))):
            out[what] = timed(jitted, (params, x),
                              passes(T, C)["branch_" + what][1])
        return out
    finally:
        kernels.takes = takes
        kernels.SLABS_A_TURN.update(was)
        jax.clear_caches()
        hc._mix_rule.cache_clear()
        hc._write_rule.cache_clear()


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--passes", default="mix,write,write_backward,"
                    "mix_backward,tile,sum_streams")
    ap.add_argument("--shape", default="4096,3584",
                    help="tokens,columns a stream (the cell's)")
    args = ap.parse_args()
    T, C = map(int, args.shape.split(","))
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "stream": [T, N, C]}), flush=True)
    names = [n for n in args.passes.split(",") if n]
    for name in names:
        print(json.dumps({"pass": name, **measure_pass(name, T, C)}),
              flush=True)
    for form in ("kernel", "jnp"):
        print(json.dumps({"branch": form, **measure_branch(form, T, C)}),
              flush=True)
    if not args.sweep:
        return
    whole = C // kernels.LANES
    for name in ("mix", "write", "write_backward", "mix_backward"):
        for k in (4, 7, 14, whole):
            print(json.dumps({"branch": "kernel", "slabs": {name: k},
                              **measure_branch("kernel", T, C, {name: k})}),
                  flush=True)
    for name in names:
        pokes = [{"rows": rows} for rows in (128, 256, 512)[
            1:1 + (T % 256 == 0) + (T % 512 == 0)]]
        if name in kernels.SLABS_A_TURN:
            pokes += [{"slabs": k} for k in sorted(
                {k for k in (1, 2, 4, 7, 14, whole) if k <= whole})]
        for poke in pokes:
            try:
                print(json.dumps({"pass": name, **poke,
                                  **measure_pass(name, T, C, **poke)}),
                      flush=True)
            except Exception as e:  # boundary: the compiler's refusal
                print(json.dumps({"pass": name, **poke,
                                  "refused": str(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
