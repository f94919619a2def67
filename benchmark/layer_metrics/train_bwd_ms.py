"""train_bwd_ms (ms), read from device_trace.

Device ms a step in the backward pass proper: self time of the slice's
events whose ``op_name`` holds ``transpose(jvp(`` and not
``rematted_computation``, over the slice's steps, on the busiest chip; found
by joining each ``XLA Ops`` event's instruction name to the ``op_name`` the
compiled step's text gives it (``scope_reduce``).
"""

from benchmark import readers, scope_reduce

NAME = "train_bwd_ms"
UNIT = "ms"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    if not readers.traced(record):
        return None
    return scope_reduce.phase_ms(record, "backward")
