"""Microbenchmark of the gated delta rule alone on the chip: the XLA chunked
form against the Pallas kernels (``ops/pallas/gated_delta.py``), forward and
forward + backward, at the ``qwen3next-train-1chip-s8192`` cell's shapes
(2 x 8192 tokens, 16 key / 32 value heads of 128, bf16), with the kernels'
trace-time constants swept, and the kernels' accuracy against the XLA form
and against the float32 recurrence. Not part of the benchmark: PERF.md's
Findings quote it.

    chiprun -- python tests/perf/gdn_scan_bench.py [--sweep] [--out NAME]
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.ops import gated_delta as gd  # noqa: E402
from deepspeed_tpu.ops.pallas import gated_delta as kernels  # noqa: E402


def inputs(B, S, Hk, Hv, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, S, Hk, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, Hk, D)))
    v = jax.random.normal(ks[2], (B, S, Hv, D))
    g = -0.1 * jax.nn.softplus(jax.random.normal(ks[3], (B, S, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, Hv)))
    cot = jax.random.normal(ks[5], (B, S, Hv, D))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), \
        cot.astype(dtype)


def timed(fn, *args, reps=10):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return statistics.median(times) * 1e3


def programs(rule, heads=None):
    """(forward, gradients of sum(o * cot)) of ``rule``, jitted. With
    ``heads`` = (Hk, Hv) the programs take q, k, v and give o, dq, dk, dv as
    [B, S, H*D], the layout the model's projections have them in, and split
    the heads inside: no copy into the 4-D arrays' default tiling is timed."""
    if heads is not None:
        inner, (Hk, Hv) = rule, heads

        def rule(q, k, v, g, beta):
            split = lambda t, h: t.reshape(*t.shape[:2], h, -1)  # noqa: E731
            o = inner(split(q, Hk), split(k, Hk), split(v, Hv), g, beta)
            return o.reshape(*o.shape[:2], -1)

    fwd = jax.jit(rule)

    def loss(q, k, v, g, beta, cot):
        return jnp.sum(rule(q, k, v, g, beta).astype(jnp.float32)
                       * cot.astype(jnp.float32))

    return fwd, jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))


def calls_alone(shape, ops, cot):
    """ms of each ``pallas_call`` by itself, on operands already laid out:
    the primal call, the forward rule (it also writes the states) and the
    backward kernel."""
    plan = kernels._plan_for(shape["B"], shape["S"], shape["Hk"],
                             shape["Hv"], shape["D"], shape["D"], gd.CHUNK)
    q, k, v, g, beta = ops
    G = jnp.cumsum(kernels.gate_layout(g, plan), axis=-1)
    beta = kernels.gate_layout(beta, plan)
    primal = jax.jit(lambda *a: kernels._forward(*a, plan, False, False))
    rule = jax.jit(lambda *a: kernels._forward(*a, plan, False, True))
    bwd = jax.jit(lambda *a: kernels._backward(*a, plan, False))
    _, states, inverses = rule(q, k, v, G, beta)
    return dict(primal_call_ms=timed(primal, q, k, v, G, beta),
                rule_call_ms=timed(rule, q, k, v, G, beta),
                bwd_call_ms=timed(bwd, q, k, v, G, beta, states, inverses,
                                  cot))


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default="gdn_scan_bench")
    args = ap.parse_args()
    dev = jax.devices()[0]
    lines = [{"device": dev.device_kind, "platform": dev.platform}]
    print(json.dumps(lines[0]), flush=True)
    shape = dict(B=2, S=8192, Hk=16, Hv=32, D=128)
    ops, cot = inputs(dtype=jnp.bfloat16, **shape)

    def note(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    heads = (shape["Hk"], shape["Hv"])
    flat = lambda t: t.reshape(*t.shape[:2], -1)  # noqa: E731
    ops = tuple(flat(t) for t in ops[:3]) + ops[3:]
    cot = flat(cot)
    xla_fwd, xla_grad = programs(gd.gated_delta_rule_xla, heads)
    o_xla = xla_fwd(*ops)
    g_xla = xla_grad(*ops, cot)
    note(form="xla", fwd_ms=timed(xla_fwd, *ops),
         fwd_bwd_ms=timed(xla_grad, *ops, cot))

    variants = [dict(sub=16, chunks=8, pair=None)]
    if args.sweep:
        variants += [dict(sub=32, chunks=8, pair=None),
                     dict(sub=8, chunks=8, pair=None),
                     dict(sub=64, chunks=8, pair=None),
                     dict(sub=16, chunks=8, pair=2),
                     dict(sub=32, chunks=8, pair=2)]
    per_step = kernels._heads_per_step
    for var in variants:
        kernels._SUB, kernels._BLOCK_CHUNKS = var["sub"], var["chunks"]
        kernels._heads_per_step = (
            per_step if var["pair"] is None else lambda *_: var["pair"])
        kernels._rule.cache_clear()
        try:
            fwd, grad = programs(gd.gated_delta_rule, heads)
            o = fwd(*ops)
            gs = grad(*ops, cot)
            note(form="kernel", **var, fwd_ms=timed(fwd, *ops),
                 fwd_bwd_ms=timed(grad, *ops, cot),
                 **calls_alone(shape, ops, cot),
                 o_rel_xla=rel(o, o_xla),
                 grads_rel_xla=[rel(a, b) for a, b in zip(gs, g_xla)])
        except Exception as e:  # noqa: BLE001 — a variant the compiler refuses
            note(form="kernel", **var, error=str(e)[:400])
    kernels._SUB, kernels._BLOCK_CHUNKS = 16, 8
    kernels._heads_per_step = per_step
    kernels._rule.cache_clear()

    # accuracy against the float32 recurrence at S 1024: bf16 operands (each
    # form's distance) and float32 operands (the inverse at HIGHEST)
    small = dict(shape, S=1024, B=1)
    for dtype in (jnp.bfloat16, jnp.float32):
        ops_s, cot_s = inputs(dtype=dtype, **small)
        want = jax.jit(gd.gated_delta_recurrence)(*ops_s)
        _, ref_grad = programs(gd.gated_delta_recurrence)
        g_want = ref_grad(*ops_s, cot_s)
        for name, rule in (("xla", gd.gated_delta_rule_xla),
                           ("kernel", gd.gated_delta_rule)):
            fwd, grad = programs(rule)
            note(check="against_recurrence", form=name,
                 dtype=jnp.dtype(dtype).name, o_rel=rel(fwd(*ops_s), want),
                 grads_rel=[rel(a, b) for a, b in
                            zip(grad(*ops_s, cot_s), g_want)])
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out + ".jsonl"), "w") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
