"""dsa_tile_overcompute (ratio), read from program_counter.

The program's gauge ``attention/dsa_tile_overcompute``: score elements the
pruned kernels' walked tiles compute over the pairs the indexer selects,
forward and backward alike
(``ops/pallas/learned_sparse_attention.tile_overcompute``: every causal tile
of 512 at 16,384 tokens, 528 of them, over sum_t min(t + 1, 2,048) pairs:
4.40). Set at trace time by the kernels' plan, folded with the family's other
gauges at the last warm-up step (``program_gauges``). What ``dsa_*_roofline``
cannot reach because of the walk is 100 / this. None where the program sets
no such gauge.
"""

NAME = "dsa_tile_overcompute"
UNIT = "ratio"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    gauges = getattr(record.family, "program_gauges", None)
    return gauges().get("attention/dsa_tile_overcompute") if gauges else None
