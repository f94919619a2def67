"""setup_engine_init_s (s), read from program_span.

Wall seconds of the engine's one-time start-up phases before the window: the
program's host spans ``startup/sharded_init`` (``runtime/zero/init.py``: the
weights made on the device, sharded), ``startup/engine_init``
(``dstpu.initialize``, entry to return), ``startup/state_init`` (the first
``train_batch``'s, where no parameters were handed in) and
``startup/build_fns`` (``_build_jit_fns``), overlaps once, the compiles inside
them included. Read from the flight recorder's ``span`` events by their start
on the harness's clock (``setup_reduce``). None where the program leaves no
``startup/engine_init`` event or the ring has pushed events out.
"""

from benchmark import setup_reduce

NAME = "setup_engine_init_s"
UNIT = "s"
LAYER = "engine set-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_reduce.metric(record, "engine_init_s")
