"""gdn_elementwise_ms (ms), read from device_trace.

What the two elementwise stages round the delta rule cost: device ms a step,
self time, of everything traced under ``gdn_conv`` (the causal convolution,
SiLU and the L2 norm of q and k; where the heads are laid out in whole lane
tiles, that re-layout of the projection's output) and ``gdn_out_norm`` (a
head's RMS norm, the gate, the cut back to the heads' own width) — the
kernels ``mixer_conv_*`` / ``mixer_norm_*`` inside them or their XLA forms —
in every phase, on the busiest chip: the rows of ``extra.scope_attribution``
whose tag is one of the family's ``GDN_ELEMENTWISE_TAGS``. None where the
family lists none or nothing ran under them.
"""

from benchmark import scope_reduce

NAME = "gdn_elementwise_ms"
UNIT = "ms"
LAYER = "linear attention"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def stage_ms(record):
    """Device ms a step under the family's ``GDN_ELEMENTWISE_TAGS`` on the
    busiest chip, or None."""
    tags = getattr(record.family, "GDN_ELEMENTWISE_TAGS", ())
    chip = scope_reduce.busiest_chip(record) if tags else None
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag in tags) or None


def read(record):
    return stage_ms(record)
