"""bd_attn_share (%), read from device_trace.

Device time of the block-diffusion mask kernels — the Pallas custom-calls
traced under the scopes ``bd_fwd`` and ``bd_bwd``
(``ops/pallas/block_diffusion_attention.py``), found through
``scope_reduce``'s join of each device event to the compiled text's
``op_name`` — over the slice's busy time, worst chip. The XLA passes round
the backward kernel (delta, the sum of the dq partials under
``bd_bwd_dq_sum``) are not Pallas calls and are not counted. None where no
kernel ran under either scope (a program without them, no trace).
"""

from benchmark import scope_reduce

NAME = "bd_attn_share"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAGS = ("bd_fwd", "bd_bwd")


def read(record):
    per_chip = scope_reduce.kernel_ms(record, TAGS)
    if not per_chip:
        return None
    chips = scope_reduce.attribution(record)["chips"]
    return max(100.0 * ms / chips[plane]["busy_ms"]
               for plane, ms in per_chip.items() if chips[plane]["busy_ms"])
