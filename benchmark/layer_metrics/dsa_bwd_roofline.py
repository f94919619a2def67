"""dsa_bwd_roofline (%), read from device_trace.

The pruned backward kernel against its compute roofline: the flops the
SELECTED pairs need (dV, dP, dQ, dK, four of the step's six products a pair:
2/3 of the family's ``train_attention_flops_per_step``) over the bf16 peak,
over the device time of the Pallas custom-calls traced under the scope
``dsa_bwd`` (one kernel: five products a tile walked, the score tile formed
once), on the busiest chip. Bound: compute. What the walk of every causal
tile lets it reach is 100 / ``dsa_tile_overcompute`` x 4 / 5.
"""

from benchmark import readers, scope_reduce

NAME = "dsa_bwd_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    # a program without the scope (the parent's) has nothing to count for
    if record.peaks is None or not scope_reduce.kernel_ms(record, ("dsa_bwd",)):
        return None
    return scope_reduce.kernel_roofline(
        record, "dsa_bwd", 2 / 3 * readers.attention_flops_per_step(record),
        record.peaks["bf16_flops_per_s"])
