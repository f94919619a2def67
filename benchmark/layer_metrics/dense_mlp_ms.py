"""dense_mlp_ms (ms), read from device_trace.

What the dense SwiGLU of every layer costs: device ms a step, self time, of
everything traced under the module ``shared_mlp`` (Granite 4.0-H's name for
it: the input matrix to gate and value halves, ``silu(g) * p``, the output
matrix) in every phase — forward, the recomputation under remat, backward —
on the busiest chip: the rows of ``extra.scope_attribution`` whose tag is
the family's ``MLP_TAG``. The largest part of a Granite 4.0-H step, as it
is of the published model's parameters; what is left of the step beside it
is what a change to a mixer's kernels can move. None where the family names
no such tag or nothing ran under it.
"""

from benchmark import scope_reduce

NAME = "dense_mlp_ms"
UNIT = "ms"
LAYER = "dense hybrid block"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    tag = getattr(record.family, "MLP_TAG", None)
    chip = scope_reduce.busiest_chip(record) if tag else None
    if not chip:
        return None
    return sum(ms for _, t, _, ms in chip["rows"] if t == tag) or None
