"""ssm_layer_ms (ms), read from device_trace.

What the Mamba-2 mixers cost: device ms a step, self time, of everything
traced under the module ``mamba`` — the two projections, the convolution
(``ssm_conv``), the softplus and the decay (``ssm_gates``), the scan
(``ssd_scan*``), the gate and the grouped norm (``ssm_norm``) — in every
phase, on the busiest chip: the rows of ``extra.scope_attribution`` whose tag
is one of the family's ``SSM_LAYER_TAGS`` (every tag a path through ``mamba``
can take). None where the family lists none or nothing ran under them.
"""

from benchmark import scope_reduce

NAME = "ssm_layer_ms"
UNIT = "ms"
LAYER = "state-space mixer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    tags = getattr(record.family, "SSM_LAYER_TAGS", ())
    chip = scope_reduce.busiest_chip(record) if tags else None
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag in tags) or None
