"""setup_programs_compiled (count), read from program_counter.

Backend compile requests that began before the window, a persistent-cache
fetch too: the flight recorder's ``compile`` events of phase ``backend``,
counted (``setup_reduce``; the detail file names the ten longest). From the
first ``sharded_init`` / ``initialize`` on, where the program installs its
listener: the harness's own ``CompileCounter``, installed before the family
builds anything, counts the few programs before it as well. None where there
is no attribution.
"""

from benchmark import setup_reduce

NAME = "setup_programs_compiled"
UNIT = "count"
LAYER = "compile"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(record):
    return setup_reduce.metric(record, "programs_compiled")
