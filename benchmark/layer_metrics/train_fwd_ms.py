"""train_fwd_ms (ms), read from device_trace.

Device ms a step in the forward pass: self time of the slice's events whose
``op_name`` holds ``jvp(`` and none of ``transpose(jvp(``,
``rematted_computation`` and ``/ds_optimizer``, over the slice's steps, on the
busiest chip; found by joining each ``XLA Ops`` event's instruction name to the
``op_name`` the compiled step's text gives it (``scope_reduce``).
"""

from benchmark import readers, scope_reduce

NAME = "train_fwd_ms"
UNIT = "ms"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    if not readers.traced(record):
        return None
    return scope_reduce.phase_ms(record, "forward")
