"""gdn_layer_ms (ms), read from device_trace.

What the Gated DeltaNet mixers cost: device ms a step, self time, of
everything traced under the module ``linear_attn`` — the two input
projections, the convolution, the gates and norms of q and k, the delta
rule, the gated output norm, the output projection — in every phase, on the
busiest chip: the rows of ``extra.scope_attribution`` whose tag is one of the
family's ``GDN_LAYER_TAGS`` (every tag a path through ``linear_attn`` can
take). None where the family lists none or nothing ran under them.
"""

from benchmark import scope_reduce

NAME = "gdn_layer_ms"
UNIT = "ms"
LAYER = "linear attention"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    tags = getattr(record.family, "GDN_LAYER_TAGS", ())
    chip = scope_reduce.busiest_chip(record) if tags else None
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag in tags) or None
