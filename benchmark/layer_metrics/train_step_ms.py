"""train_step_ms (ms), read from device_trace.

Median device time from one train step's first op to the next step's
first op (``XLA Modules`` events of the step program), worst chip.
"""

from benchmark import readers, stats, trace_reduce

NAME = "train_step_ms"
UNIT = "ms"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    if not readers.traced(record):
        return None
    meds = []
    for plane in record.planes():
        gaps = trace_reduce.step_starts_ms(record.trace, plane,
                                           record.extra["step_module"])
        if gaps:
            meds.append(stats.median(gaps))
    return max(meds) if meds else None
