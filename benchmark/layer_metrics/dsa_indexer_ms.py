"""dsa_indexer_ms (ms), read from device_trace.

The indexer's scores: device ms a step, self time, of everything traced under
the scope ``dsa_indexer`` (the kernel that forms sum_j w_j relu(iq_j . ik)
over every causal tile, in the forward pass and again in a rematted block's
recomputation), on the busiest chip: the rows of ``extra.scope_attribution``
whose tag is ``dsa_indexer``. Its backward is ``dsa_kl_ms``'s. None where the
family lists no such scope, nothing ran under it or the run has no trace.
"""

from benchmark import scope_reduce

NAME = "dsa_indexer_ms"
UNIT = "ms"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


TAGS = ("dsa_indexer",)


def tagged_ms(record, tags):
    """Device ms a step, self time, of every kind under ``tags`` on the
    busiest chip; None where the family lists none of them, nothing ran under
    them or the run has no trace."""
    if not set(tags) <= set(getattr(record.family, "KERNEL_TAGS", ())):
        return None
    chip = scope_reduce.busiest_chip(record)
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag in tags) or None


def read(record):
    return tagged_ms(record, TAGS)
