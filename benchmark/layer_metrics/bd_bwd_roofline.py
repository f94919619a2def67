"""bd_bwd_roofline (%), read from device_trace.

The block-diffusion backward kernel against its compute roofline: the flops
the ALLOWED pairs need (dV, dP, dQ, dK: four of the step's six products a
pair, 2/3 of the family's ``train_attention_flops_per_step``; the QK^T the
kernel forms again is recomputation and is not counted) over the bf16 peak,
over the device time of the Pallas custom-calls traced under the scope
``bd_bwd``, on the busiest chip. Bound: compute.
"""

from benchmark import readers, scope_reduce

NAME = "bd_bwd_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    # a program without the scope (the parent's) has nothing to count for
    if record.peaks is None or not scope_reduce.kernel_ms(record, ("bd_bwd",)):
        return None
    return scope_reduce.kernel_roofline(
        record, "bd_bwd", 2 / 3 * readers.attention_flops_per_step(record),
        record.peaks["bf16_flops_per_s"])
