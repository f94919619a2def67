"""train_peak_hbm_gb (GB), read from program_counter.

Largest ``device.memory_stats()["peak_bytes_in_use"]`` over the cell's
chips. On this backend it counts live buffers (state, batches) and NOT a
program's temporaries: headroom here is batch.
"""

from benchmark import readers

NAME = "train_peak_hbm_gb"
UNIT = "GB"
LAYER = "device"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    return readers.peak_hbm_gb(record)
