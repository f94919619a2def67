"""flash_attn_roofline (%), read from device_trace.

Flash forward + backward against their compute roofline: the causal flops
the calls need a step (six S x S x D matmuls per head, halved by the mask;
the backward's recomputed QK^T is not counted) over the bf16 peak, over the
device time a step of the Pallas custom-calls traced under the scopes
``flash_fwd`` and ``flash_bwd`` (``scope_reduce``'s join), chips averaged.
Bound: compute (head_dim 64 keeps the MXU half fed).
"""

from benchmark import readers, roofline, scope_reduce

NAME = "flash_attn_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAGS = ("flash_fwd", "flash_bwd")


def read(record):
    per_chip = scope_reduce.kernel_ms(record, TAGS)
    if not per_chip or record.peaks is None:
        return None
    secs = sum(per_chip.values()) / len(per_chip) / 1e3
    return roofline.share(readers.attention_flops_per_step(record),
                          record.peaks["bf16_flops_per_s"], secs)
