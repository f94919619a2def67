"""dsa_select_roofline (%), read from device_trace.

The selection kernel against its memory roofline: the bytes it must move
(every causal score read once as float32, a byte of mask a causal pair
written: the family's ``select_bytes_per_step``) over the HBM peak, over the
device time of the Pallas custom-calls traced under the scope ``dsa_select``,
on the busiest chip. Bound: memory by the count; the kernel is in fact held
by the VPU (47 counting passes over a query's keys in VMEM), and the share
says how far an exact top-k on this chip is from free.
"""

from benchmark.layer_metrics.dsa_indexer_roofline import share

NAME = "dsa_select_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return share(record, "dsa_select", "select_bytes_per_step", lambda n: n,
                 "hbm_bytes_per_s")
