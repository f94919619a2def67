"""closedloop_device_idle_share (%), read from device_trace.

Share of the traced slice (closed-loop traffic after the window) in which
no operation ran on the chip: the host turn between ticks and prefills.
"""

from benchmark import readers

NAME = "closedloop_device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return readers.device_idle_share(record)
