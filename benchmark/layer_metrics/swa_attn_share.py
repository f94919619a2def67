"""swa_attn_share (%), read from device_trace.

Device time of the window kernels — the Pallas custom-calls traced under the
scopes ``swa_fwd`` and ``swa_bwd`` (ONE backward call since PR 53:
``ops/pallas/flash_attention.py``'s sliding-window family), forward,
backward and recomputation — over the slice's busy time, on the busiest
chip. The full-attention layers' kernels are ``flash_attn_share``'s. None
where no event carries the tags (a family that does not list them, a
program without the window kernels).
"""

from benchmark import scope_reduce

NAME = "swa_attn_share"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAGS = ("swa_fwd", "swa_bwd")


def read(record):
    per_chip = scope_reduce.kernel_ms(record, TAGS)
    if not per_chip:
        return None
    found = scope_reduce.attribution(record)
    busiest = found["chip"]
    return 100.0 * per_chip[busiest] / found["chips"][busiest]["busy_ms"]
