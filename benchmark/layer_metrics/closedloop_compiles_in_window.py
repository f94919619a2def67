"""closedloop_compiles_in_window (count), read from program_counter.

JAX backend compiles (or persistent-cache fetches) inside the window; must
be 0.
"""

from benchmark import readers

NAME = "closedloop_compiles_in_window"
UNIT = "count"
LAYER = "compile"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    return readers.compiles_in_window(record)
