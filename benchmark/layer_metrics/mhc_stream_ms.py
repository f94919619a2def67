"""mhc_stream_ms (ms), read from device_trace.

What the residual STREAMS cost (manifold-constrained hyper-connections,
``deepspeed_tpu/models/hyper_connections.py``): device ms a step, self time,
of everything traced under ``mhc_coeff`` (the stream's norm, its projection
onto the 2n + n^2 coefficients, the sigmoids and Sinkhorn's rounds),
``mhc_read`` (``u = H_pre X``, and the sum of the streams at a chain's end)
and ``mhc_write`` (``X_new = H_res X + H_post y``, and the copy into the
streams at a chain's start), round every branch of every block, in EVERY
phase — forward, the recomputation, backward — on the busiest chip: the rows
of ``extra.scope_attribution`` whose tag is one of the family's ``MHC_TAGS``.
The mixer adds almost no flops and most of a block's HBM traffic; this is
what a fused stream kernel would move, and ``mhc_stream_roofline`` says how
far it is from the bytes it has to move. None where the family lists no such
tags or nothing ran under them (a program with one residual stream).
"""

from benchmark import scope_reduce

NAME = "mhc_stream_ms"
UNIT = "ms"
LAYER = "residual streams"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    tags = getattr(record.family, "MHC_TAGS", ())
    chip = scope_reduce.busiest_chip(record) if tags else None
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag in tags) or None
