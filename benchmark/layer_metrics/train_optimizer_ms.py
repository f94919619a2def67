"""train_optimizer_ms (ms), read from device_trace.

Device ms a step under the scope ``ds_optimizer``: gradient norm, clip, AdamW
and, under ZeRO-3, the slicing of gradients to their owners, on the busiest
chip; found by joining each ``XLA Ops`` event's instruction name to the
``op_name`` the compiled step's text gives it (``scope_reduce``).
"""

from benchmark import readers, scope_reduce

NAME = "train_optimizer_ms"
UNIT = "ms"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    if not readers.traced(record):
        return None
    return scope_reduce.phase_ms(record, "optimizer")
