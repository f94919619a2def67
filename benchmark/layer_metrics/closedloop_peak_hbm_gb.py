"""closedloop_peak_hbm_gb (GB), read from program_counter.

Largest ``peak_bytes_in_use`` on the chip: weights + KV pool (a program's
temporaries are not counted by the backend). Headroom is slots and pages.
"""

from benchmark import readers

NAME = "closedloop_peak_hbm_gb"
UNIT = "GB"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    return readers.peak_hbm_gb(record)
