"""flash_attn_share (%), read from device_trace.

Device time of the step's Pallas kernels (the flash forward
``_fwd_kernel`` and backward ``_bwd_fused_kernel``; on the device plane
both are ``%attn.N`` custom-calls to ``tpu_custom_call`` and the step holds
no other Pallas kernel) over the slice's busy time, worst chip.
"""

from benchmark import readers, trace_reduce

NAME = "flash_attn_share"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return readers.slice_op_share(record, trace_reduce.is_pallas)
