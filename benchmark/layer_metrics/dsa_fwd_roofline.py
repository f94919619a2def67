"""dsa_fwd_roofline (%), read from device_trace.

The pruned forward kernel against its compute roofline: the flops the SELECTED
pairs need (QK^T and PV, two of the step's six products a pair: 1/3 of the
family's ``train_attention_flops_per_step``, which counts sum_t min(t + 1,
top-k) pairs a head and not the causal tiles the kernel walks) over the bf16
peak, over the device time of the Pallas custom-calls traced under the scope
``dsa_fwd``, on the busiest chip. Bound: compute. The same count whatever
walks the tiles: a masked walk of every causal tile reads at most 100 /
``dsa_tile_overcompute``, and a later gather or tile-skipping kernel is judged
against the same needed work.
"""

from benchmark import readers, scope_reduce

NAME = "dsa_fwd_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    # a program without the scope (the parent's) has nothing to count for
    if record.peaks is None or not scope_reduce.kernel_ms(record, ("dsa_fwd",)):
        return None
    return scope_reduce.kernel_roofline(
        record, "dsa_fwd", 1 / 3 * readers.attention_flops_per_step(record),
        record.peaks["bf16_flops_per_s"])
