"""dsa_kl_ms (ms), read from device_trace.

What the indexer's loss costs: device ms a step, self time, of everything
traced under the scopes that start with ``dsa_kl`` (the pass that sums the 32
heads' probabilities a tile and forms the KL and its gradient in the scores,
forward and in the recomputation, and ``dsa_kl_bwd``) or ``dsa_indexer_bwd``
(that gradient through the indexer: the kernel and the sums after it), on the
busiest chip. None where the family lists no such scopes, nothing ran under
them or the run has no trace.
"""

from benchmark.layer_metrics.dsa_indexer_ms import tagged_ms

NAME = "dsa_kl_ms"
UNIT = "ms"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return tagged_ms(record, ("dsa_kl", "dsa_indexer_bwd"))
