"""Microbenchmark of ``moe/dropless.rows_to_tokens`` alone on the chip, at the
shapes of the two cells that hold a share of their experts
(``qwen3next-train-1chip-s8192``: [20480, 2048] rows -> [16384, 2048], k 10
of 512 experts, 32 held; ``laguna-train-1chip-s16384``: [32768, 2048] ->
[16384, 2048], k 8 of 256, 32 held), float32 rows (the forward call, under
``moe_combine``) and bfloat16 rows (the backward call, under
``moe_dispatch``): the form PR 31 wrote (sort, shifted adds, all-pairs search;
kept HERE for the comparison, the package no longer has it) against the
kernel form with its heaviest instructions (the sort of M keys, the row
gather, the ``pallas_call``), ``tokens_to_rows`` and the expert ``argsort`` of
all T x k keys beside them — every time a DEVICE time from a profiler trace,
the host's clock adds 0.3-0.5 ms of dispatch and fence to a call of 1 ms —
and both forms' distance from a float64 sum on the host. Not part of the
benchmark: PERF.md's Findings quote it.

    chiprun -- python tests/perf/rows_to_tokens_bench.py [--sweep] [--out NAME]
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import trace_reduce  # noqa: E402
from benchmark.tools import trace_look  # noqa: E402
from deepspeed_tpu.moe import dropless  # noqa: E402
from deepspeed_tpu.ops.pallas import rows_to_tokens as kernel  # noqa: E402

CELLS = {"qwen3next": dict(T=16384, k=10, E=512, held=32, H=2048),
         "laguna": dict(T=16384, k=8, E=256, held=32, H=2048)}


def _shift_rows(t, d, fill):
    head = jnp.full((d,) + t.shape[1:], fill, t.dtype)
    return jnp.concatenate([head, t[:-d]], axis=0)


def rows_to_tokens_pr31(rows, tok, T, k):
    """The form this PR replaces, as PR 31 wrote it."""
    by_tok = jnp.argsort(tok, stable=True)
    seg = jnp.take(tok, by_tok)
    z = jnp.take(rows.astype(jnp.float32), by_tok, axis=0)
    d = 1
    while d < k:
        same = (seg == _shift_rows(seg, d, -1))[:, None]
        z = z + jnp.where(same, _shift_rows(z, d, 0.0), 0.0)
        d *= 2
    tokens = jnp.arange(T, dtype=seg.dtype)
    last = jnp.searchsorted(seg, tokens, side="right",
                            method="compare_all").astype(jnp.int32) - 1
    has = jnp.take(seg, jnp.maximum(last, 0)) == tokens
    out = jnp.take(z, jnp.maximum(last, 0), axis=0)
    return jnp.where(has[:, None], out, 0.0).astype(rows.dtype)


def routing(T, k, E, held, H, seed, share=1.0):
    """(top_e [T, k], tok [M], rows held) of a random routing, the slab as
    ``DroplessMoE`` builds it; ``share`` scales the odds of a held expert."""
    rng = np.random.default_rng(seed)
    odds = np.ones(E)
    odds[:held] *= share
    scores = rng.random((T, E)) ** (1.0 / odds)
    top_e = np.argsort(-scores, axis=1)[:, :k].astype(np.int32)
    key = np.where(top_e < held, top_e, held).reshape(-1)
    order = np.argsort(key, kind="stable")
    M = dropless._HELD_ROWS_SLACK * T * k * held // E
    rows_held = int((key < held).sum())
    tok = np.where(np.arange(M) < rows_held, order[:M] // k, T)
    return top_e, tok.astype(np.int32), rows_held


def device_ms(fn, *args, reps=5, top=0):
    """Device ms of one call of the jitted ``fn`` (the median ``XLA Modules``
    event of a profiler trace: no dispatch or fence on the host's clock), and
    with ``top`` its heaviest instructions, [label, ms a call]."""
    jax.block_until_ready(fn(*args))
    where = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(where):
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
        trace = trace_reduce.load(trace_look.find_xplane(where))
    finally:
        shutil.rmtree(where, ignore_errors=True)
    plane = sorted(trace.devices)[0]
    ms = round(statistics.median(
        e.dur for e in trace_reduce.modules(trace, plane)) / 1e6, 4)
    if not top:
        return ms
    t0, t1 = trace_reduce.window_of(trace)
    return ms, [[label[:100], round(s * 1e3 / reps, 4)] for label, s in
                trace_reduce.top_ops(trace, plane, t0, t1, top)]


def error(out, rows, tok, T):
    """Largest distance of ``out`` from the float64 sum on the host, over the
    largest magnitude of that sum."""
    tok = np.asarray(tok)
    by_tok = np.argsort(tok, kind="stable")
    by_tok = by_tok[:int((tok < T).sum())]
    tokens, starts = np.unique(tok[by_tok], return_index=True)
    want = np.zeros((T, rows.shape[1]))
    want[tokens] = np.add.reduceat(
        np.asarray(rows, dtype=np.float64)[by_tok], starts, axis=0)
    return float(np.max(np.abs(np.asarray(out, dtype=np.float64) - want))
                 / np.max(np.abs(want)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default="rows_to_tokens_bench")
    args = ap.parse_args()
    dev = jax.devices()[0]
    lines = [{"device": dev.device_kind, "platform": dev.platform}]
    print(json.dumps(lines[0]), flush=True)

    def note(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    for cell, c in CELLS.items():
        T, k, H = c["T"], c["k"], c["H"]
        top_e, tok, rows_held = routing(seed=1, **c)
        M = tok.shape[0]
        tok = jnp.asarray(tok)
        x = jax.random.normal(jax.random.PRNGKey(0), (T, H), jnp.bfloat16)
        note(cell=cell, M=M, T=T, k=k, rows_held=rows_held,
             tokens_to_rows_ms=device_ms(jax.jit(
                 lambda x, t: dropless.tokens_to_rows(x, t, k)), x, tok),
             expert_argsort_ms=device_ms(jax.jit(lambda e: jnp.argsort(
                 e.reshape(-1), stable=True)), jnp.asarray(top_e)))
        for dtype in (jnp.float32, jnp.bfloat16):
            rows = jax.random.normal(jax.random.PRNGKey(1), (M, H), dtype)
            # what the grouped matmul leaves past the rows held
            rows = jnp.where((tok < T)[:, None], rows, jnp.nan)
            old = jax.jit(lambda r, t: rows_to_tokens_pr31(r, t, T, k))
            new = jax.jit(lambda r, t: dropless.rows_to_tokens(r, t, T, k))
            kernel_ms, kernel_ops = device_ms(new, rows, tok, top=12)
            note(cell=cell, dtype=jnp.dtype(dtype).name,
                 pr31_ms=device_ms(old, rows, tok), kernel_ms=kernel_ms,
                 pr31_err=error(old(rows, tok), rows, tok, T),
                 kernel_err=error(new(rows, tok), rows, tok, T),
                 kernel_ops=kernel_ops)
            if not args.sweep:
                continue
            was = kernel.ROW_TILE, kernel.TOKEN_BLOCK, kernel._LANE_CHUNK
            for var in ((128, 128, 2048), (128, 128, 1024), (128, 128, 256),
                        (256, 128, 512), (128, 256, 512), (256, 256, 512),
                        (512, 128, 512), (128, 64, 512)):
                kernel.ROW_TILE, kernel.TOKEN_BLOCK, kernel._LANE_CHUNK = var
                try:
                    fn = jax.jit(
                        lambda r, t: kernel.sum_rows_by_token(r, t, T))
                    note(cell=cell, dtype=jnp.dtype(dtype).name,
                         row_tile=var[0], token_block=var[1],
                         lane_chunk=var[2], kernel_ms=device_ms(fn, rows, tok),
                         kernel_err=error(fn(rows, tok), rows, tok, T))
                except Exception as e:  # noqa: BLE001 — the compiler refuses
                    note(cell=cell, row_tile=var[0], token_block=var[1],
                         lane_chunk=var[2], error=str(e)[:300])
            kernel.ROW_TILE, kernel.TOKEN_BLOCK, kernel._LANE_CHUNK = was
    # a router that drifted: the second slab's call holds few rows
    c = CELLS["qwen3next"]
    _, tok, rows_held = routing(seed=2, share=0.3, **c)
    rows = jax.random.normal(jax.random.PRNGKey(1), (tok.shape[0], c["H"]))
    new = jax.jit(lambda r, t: dropless.rows_to_tokens(r, t, c["T"], c["k"]))
    note(cell="qwen3next", case="few rows held", rows_held=rows_held,
         kernel_ms=device_ms(new, rows, jnp.asarray(tok)))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out + ".jsonl"), "w") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
