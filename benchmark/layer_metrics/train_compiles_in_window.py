"""train_compiles_in_window (count), read from program_counter.

JAX backend compiles (or persistent-cache fetches) between the window's
first and last instant; must be 0.
"""

from benchmark import readers

NAME = "train_compiles_in_window"
UNIT = "count"
LAYER = "compile"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    return readers.compiles_in_window(record)
