"""dsa_select_ms (ms), read from device_trace.

The exact top-k: device ms a step, self time, of everything traced under the
scopes that start with ``dsa_select`` (the selection kernel — the k-th
largest score of a query found bit by bit over its keys in VMEM, the ties by
index — and the packing of the kept set to the bits a rematted block keeps,
``dsa_select_pin``), on the busiest chip. None where the family lists no such
scope, nothing ran under it or the run has no trace.
"""

from benchmark.layer_metrics.dsa_indexer_ms import tagged_ms

NAME = "dsa_select_ms"
UNIT = "ms"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return tagged_ms(record, ("dsa_select",))
