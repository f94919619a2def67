"""dsa_attn_share (%), read from device_trace.

Device time of everything the learned sparse attention adds to a layer —
whatever ran under a scope of the family's ``DSA_TAGS`` (the indexer's scores
and their backward, the selection with its packing, the pruned forward and
backward kernels with the sum of the dq partials, the KL pass and its
backward: ``ops/pallas/learned_sparse_attention.py``), Pallas calls and the XLA
passes round them alike, found through ``scope_reduce``'s join of each device
event to the compiled text's ``op_name`` — over the slice's busy time, on the
busiest chip. None where the family lists no such scopes or nothing ran under
them (the parent's program, no trace).
"""

from benchmark import scope_reduce

NAME = "dsa_attn_share"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    tags = getattr(record.family, "DSA_TAGS", ())
    chip = scope_reduce.busiest_chip(record) if tags else None
    if not chip or not chip["busy_ms"]:
        return None
    ms = sum(ms for _, tag, _, ms in chip["rows"] if tag in tags)
    return 100.0 * ms / chip["busy_ms"] if ms else None
