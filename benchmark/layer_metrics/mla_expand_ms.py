"""mla_expand_ms (ms), read from device_trace.

What the LATENT FORM costs round the attention kernels: device ms a step,
self time, of everything traced under ``mla_latent`` (the down-projection to
latent + rotated key, the latent's RMS norm), ``mla_expand`` (the
up-projection into every head's key without position and value, and the
kernels' K operand: the concat with the broadcast rotated key) and
``mla_rope`` (the de-interleaving rotation of q_rope and of the shared key),
in every phase, on the busiest chip — the rows of ``extra.scope_attribution``
with the family's ``MLA_EXPAND_TAGS``. It is what a change that feeds the
kernels the two key parts, or keeps the latent across the recomputation,
would move. None where the family lists none or nothing ran under them.
"""

from benchmark import scope_reduce

NAME = "mla_expand_ms"
UNIT = "ms"
LAYER = "latent attention"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    tags = getattr(record.family, "MLA_EXPAND_TAGS", ())
    chip = scope_reduce.busiest_chip(record) if tags else None
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag in tags) or None
