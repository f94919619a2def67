"""dsa_selected_share (%), read from program_counter.

The program's gauge ``attention/dsa_selected_share`` of the LAST WARM-UP STEP,
as a percentage: the (query, key) pairs the selection kernels KEPT, counted by
the kernel a query, over the causal pairs, averaged over the layers; sum_t
min(t + 1, top-k) over S (S + 1) / 2 whatever the scores are: 23.44 at 16,384
tokens and top-2,048. It guards the count the ``dsa_*_roofline`` readers
divide by: a selection that kept more or fewer keys would move it. None where
the program sets no such gauge.
"""

NAME = "dsa_selected_share"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    gauges = getattr(record.family, "program_gauges", None)
    value = gauges().get("attention/dsa_selected_share") if gauges else None
    return None if value is None else 100.0 * value
