"""dsa_indexer_bwd_roofline (%), read from device_trace.

The indexer's backward kernel against its compute roofline: the flops it
needs (the score product again for relu's gate, the query side's and the key
side's: three products of 2 x 64 a causal pair a head, the family's
``indexer_flops_per_step``, backward) over the bf16 peak, over the device time
of the Pallas custom-calls traced under the scope ``dsa_indexer_bwd``, on the
busiest chip. Bound: compute (two of the three products are 64 columns wide).
"""

from benchmark.layer_metrics.dsa_indexer_roofline import share

NAME = "dsa_indexer_bwd_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return share(record, "dsa_indexer_bwd", "indexer_flops_per_step", lambda n: n[1],
                 "bf16_flops_per_s")
