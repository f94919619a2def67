"""setup_outside_program_s (s), read from program_span.

``setup_s`` less the union of the program's five start-up spans (the four
``startup/*`` and step 0's ``train/step_dispatch``): the interpreter, imports
and the backend's start, the benchmark's float32 reference, step 0's
execution and the warm-up steps — what only a
``benchmark`` PR or the device can shorten. The timeline's rows in the detail
file (``extra.setup_attribution``) say which (``setup_reduce``). None where
there is no attribution.
"""

from benchmark import setup_reduce

NAME = "setup_outside_program_s"
UNIT = "s"
LAYER = "engine set-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_reduce.metric(record, "outside_program_s")
