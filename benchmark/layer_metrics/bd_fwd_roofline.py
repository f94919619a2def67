"""bd_fwd_roofline (%), read from device_trace.

The block-diffusion forward kernel against its compute roofline: the flops
the ALLOWED pairs need (QK^T and PV, two of the step's six products a pair:
1/3 of the family's ``train_attention_flops_per_step``, which counts
``L^2 + L x block_length`` pairs a head and not the dense ``(2L)^2``) over
the bf16 peak, over the device time of the Pallas custom-calls traced under
the scope ``bd_fwd``, on the busiest chip. Bound: compute. What the tiling
lets it reach is 100 / ``bd_tile_overcompute``.
"""

from benchmark import readers, scope_reduce

NAME = "bd_fwd_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    # a program without the scope (the parent's) has nothing to count for
    if record.peaks is None or not scope_reduce.kernel_ms(record, ("bd_fwd",)):
        return None
    return scope_reduce.kernel_roofline(
        record, "bd_fwd", 1 / 3 * readers.attention_flops_per_step(record),
        record.peaks["bf16_flops_per_s"])
