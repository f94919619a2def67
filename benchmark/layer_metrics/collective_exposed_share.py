"""collective_exposed_share (%), read from device_trace.

Time the core spends in collective instructions (synchronous ones and the
waits of ``*-start``/``*-done`` pairs: all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute) over the slice's busy
time, worst chip: the collective time NOT hidden behind compute.
"""

from benchmark import readers, trace_reduce

NAME = "collective_exposed_share"
UNIT = "%"
LAYER = "ZeRO partitioning"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return readers.slice_op_share(record, trace_reduce.is_collective)
