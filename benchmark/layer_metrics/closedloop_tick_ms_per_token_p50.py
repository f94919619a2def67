"""closedloop_tick_ms_per_token_p50 (ms), read from program_span.

Median ``serving/decode_latency_per_token_s`` inside the window: a tick's
host-fenced time over its steps.
"""

from benchmark import readers

NAME = "closedloop_tick_ms_per_token_p50"
UNIT = "ms"
LAYER = "serving model step"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(record):
    return readers.registry_median_ms(
        record, "serving/decode_latency_per_token_s")
