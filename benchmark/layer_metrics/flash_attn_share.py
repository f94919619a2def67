"""flash_attn_share (%), read from device_trace.

Device time of the flash kernels — the Pallas custom-calls traced under the
scopes ``flash_fwd`` and ``flash_bwd`` (their long-S variants included),
found through ``scope_reduce``'s join of each device event to the compiled
text's ``op_name`` — over the slice's busy time, worst chip. Another Pallas
kernel in the step is not counted here: it has a scope, and a reader, of its
own.
"""

from benchmark import scope_reduce

NAME = "flash_attn_share"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAGS = ("flash_fwd", "flash_bwd")


def read(record):
    per_chip = scope_reduce.kernel_ms(record, TAGS)
    if not per_chip:
        return None
    chips = scope_reduce.attribution(record)["chips"]
    return max(100.0 * ms / chips[plane]["busy_ms"]
               for plane, ms in per_chip.items() if chips[plane]["busy_ms"])
