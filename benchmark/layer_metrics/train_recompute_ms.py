"""train_recompute_ms (ms), read from device_trace.

Device ms a step spent recomputing forward values inside the backward pass
(``rematted_computation`` in the ``op_name``): what the remat policy
``dots_flash_fc_lean`` and the chunked loss head pay for their memory, on the
busiest chip; found by joining each ``XLA Ops`` event's instruction name to
the ``op_name`` the compiled step's text gives it (``scope_reduce``).
"""

from benchmark import readers, scope_reduce

NAME = "train_recompute_ms"
UNIT = "ms"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    if not readers.traced(record):
        return None
    return scope_reduce.phase_ms(record, "recompute")
