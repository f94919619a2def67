"""setup_cache_misses (count), read from program_counter.

Of the backend compile requests before the window, those the persistent cache
did not hold: the flight recorder's ``compile`` events of phase ``backend``
with ``cache: "miss"`` (the program's counter ``compile/cache_misses`` is the
same count over the whole process, kept past the ring's turnover). 0 says
the line's ``setup_s`` is a warm one, more says how many programs it paid for
(``setup_reduce``). Counted from the first ``sharded_init`` / ``initialize``
on, where the program installs its listener. None where there is no
attribution.
"""

from benchmark import setup_reduce

NAME = "setup_cache_misses"
UNIT = "count"
LAYER = "compile"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(record):
    return setup_reduce.metric(record, "cache_misses")
