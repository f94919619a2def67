"""mla_layer_ms (ms), read from device_trace.

What the latent-attention modules cost: device ms a step, self time, of
everything traced under the module ``mla_attn`` — the query projection, the
down-projection and the latent's norm (``mla_latent``), the up-projection and
the kernels' K operand (``mla_expand``), the rotation (``mla_rope``), the
flash kernels (``flash_fwd*`` / ``flash_bwd*``) and the output projection — in
every phase, on the busiest chip: the rows of ``extra.scope_attribution``
whose tag is one of the family's ``MLA_LAYER_TAGS`` (every tag a path through
``mla_attn`` can take). None where the family lists none or nothing ran under
them (a program without latent attention).
"""

from benchmark import scope_reduce

NAME = "mla_layer_ms"
UNIT = "ms"
LAYER = "latent attention"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    tags = getattr(record.family, "MLA_LAYER_TAGS", ())
    chip = scope_reduce.busiest_chip(record) if tags else None
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag in tags) or None
