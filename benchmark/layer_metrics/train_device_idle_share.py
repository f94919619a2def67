"""train_device_idle_share (%), read from device_trace.

Share of the traced slice (the steps after the window) in which no
operation ran on the chip; chips averaged.
"""

from benchmark import readers

NAME = "train_device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return readers.device_idle_share(record)
