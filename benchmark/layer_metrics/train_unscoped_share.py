"""train_unscoped_share (%), read from device_trace.

Self time of the slice's events that get no phase (no ``op_name``, an
``op_name`` outside ``ds_fwd_bwd``'s ``jvp(`` and ``ds_optimizer``, or an
instruction the compiled text does not hold under the same opcode and shape)
over the busy time, on the busiest chip: how much of the step
``train_fwd_ms`` + ``train_bwd_ms`` + ``train_recompute_ms`` +
``train_optimizer_ms`` do not explain.
"""

from benchmark import readers, scope_reduce

NAME = "train_unscoped_share"
UNIT = "%"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    if not readers.traced(record):
        return None
    chip = scope_reduce.busiest_chip(record)
    if not chip or not chip["busy_ms"]:
        return None
    return 100.0 * chip["phase_ms"]["unscoped"] / chip["busy_ms"]
