"""flash_bwd_roofline (%), read from device_trace.

The flash backward kernel against its compute roofline: the causal flops it
needs (dV, dP, dQ, dK, four of the step's six S x S x D matmuls per head: 2/3
of ``train_attention_flops_per_step``; its recomputed QK^T is not counted)
over the bf16 peak, over the device time of the Pallas custom-calls traced
under the scope ``flash_bwd``, on the busiest chip. Bound: compute.
"""

from benchmark import readers, scope_reduce

NAME = "flash_bwd_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    if record.peaks is None:
        return None
    return scope_reduce.kernel_roofline(
        record, "flash_bwd", 2 / 3 * readers.attention_flops_per_step(record),
        record.peaks["bf16_flops_per_s"])
