"""dsa_kl_roofline (%), read from device_trace.

The KL pass against its compute roofline: the flops the SELECTED pairs need
(QK^T of the 32 heads, 2 x 128 a pair a head: the family's
``kl_flops_per_step``) times the passes of a step in which the kernel ran
(the forward and a rematted block's recomputation: two), over the bf16 peak,
over the device time of the Pallas custom-calls traced under the scope
``dsa_kl``, on the busiest chip. Bound: compute; the walk of every causal
tile lets it reach 100 / ``dsa_tile_overcompute``.
"""

from benchmark.layer_metrics.dsa_indexer_roofline import share

NAME = "dsa_kl_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return share(record, "dsa_kl", "kl_flops_per_step", lambda n: n,
                 "bf16_flops_per_s")
