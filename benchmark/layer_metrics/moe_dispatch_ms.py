"""moe_dispatch_ms (ms), read from device_trace.

What the expert mechanism costs outside its matmuls: device ms a step, self
time, of everything traced under the scopes the family lists as
``DISPATCH_TAGS`` (``moe_router``: logits, softmax, top-k, the two losses;
``moe_dispatch``: the sort and the row gather; ``moe_combine``: the rows back
in token order, weighted and summed), in every phase, on the busiest chip —
the rows of ``extra.scope_attribution`` with those tags. None where the
family lists none or nothing ran under them.
"""

from benchmark import scope_reduce

NAME = "moe_dispatch_ms"
UNIT = "ms"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    tags = getattr(record.family, "DISPATCH_TAGS", ())
    chip = scope_reduce.busiest_chip(record) if tags else None
    if not chip:
        return None
    total = sum(ms for _, tag, _, ms in chip["rows"] if tag in tags)
    return total or None
