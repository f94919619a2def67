"""collectives_per_step (count), read from program_counter.

Collective instructions in the compiled step's text (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute; a ``-start``
counts once, its ``-done`` not). A count: it repeats exactly.
"""

import re

from benchmark import trace_reduce

NAME = "collectives_per_step"
UNIT = "count"
LAYER = "ZeRO partitioning"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    if not record.compiled_text:
        return None
    return sum(len(re.findall(rf"\b{op}(?:-start)?\(", record.compiled_text))
               for op in trace_reduce.COLLECTIVE_OPS)
