"""setup_first_step_s (s), read from program_span.

Wall seconds of step 0's ``train/step_dispatch`` span: the trace, lowering and
compile-or-fetch of the step's program and of what it pulls (the jit call
blocks on them), and the enqueue. The first such span that starts after
``startup/engine_init`` has ended (``setup_reduce``). None where no step was
dispatched before the window, or there is no attribution at all.
"""

from benchmark import setup_reduce

NAME = "setup_first_step_s"
UNIT = "s"
LAYER = "engine set-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_reduce.metric(record, "first_step_s")
