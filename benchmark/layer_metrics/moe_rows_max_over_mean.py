"""moe_rows_max_over_mean (ratio), read from program_counter.

The program's gauge ``moe/rows_max_over_mean`` of the LAST WARM-UP STEP: the
rows the fullest expert received over the mean (1.0 is perfect balance; 64
would be every token on one expert), averaged over the layers. Folded once,
when the family judges the warm-up (``judge_train``: a fence and a fold of
the engine's telemetry), and read from there: the same step of every run, so
the number does not depend on how many steps a window held. It DESCRIBES the
routing the window starts from: from random weights over uniform tokens the
routing is ~1.07 at the first step and AdamW moves the router away from
balance from then on (~2 after the warm-up, 3-4 at the end of a 30 s window);
the step time does not follow it (the grouped matmul visits at most one more
row tile an expert whatever the sizes), and the skew a trained router shows
on text is not in the cell. None where the family has no gauges or the
program does not set this one.
"""

NAME = "moe_rows_max_over_mean"
UNIT = "ratio"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    gauges = getattr(record.family, "program_gauges", None)
    return gauges().get("moe/rows_max_over_mean") if gauges else None
