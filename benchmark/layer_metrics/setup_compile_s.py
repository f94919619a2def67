"""setup_compile_s (s), read from program_span.

Seconds before the window in which SOME program of the process was being
traced, lowered or compiled (or fetched from the persistent cache): the union
of the intervals of the flight recorder's ``compile`` events, which
``telemetry.spans.watch_compiles`` records from ``jax.monitoring``'s time
spans — FROM THE FIRST ``sharded_init`` / ``initialize`` ON, where the program
installs the listener: what the process compiled before it (a family's
``PRNGKey`` and example input) is in the row ``before_first_span`` and in no
compile figure. The benchmark's reference's programs are included. A cut by
kind ACROSS the timeline's rows, not a further row (``setup_reduce``). None
where there is no attribution.
"""

from benchmark import setup_reduce

NAME = "setup_compile_s"
UNIT = "s"
LAYER = "compile"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_reduce.metric(record, "compile_s")
