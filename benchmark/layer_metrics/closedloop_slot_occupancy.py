"""closedloop_slot_occupancy (%), read from program_counter.

Mean ``serving/slot_utilization`` over the window's ticks. Under one
client per slot anything under 100 % is the page pool or admission holding
slots empty.
"""

from benchmark import readers

NAME = "closedloop_slot_occupancy"
UNIT = "%"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    return readers.registry_mean_pct(record, "serving/slot_utilization")
