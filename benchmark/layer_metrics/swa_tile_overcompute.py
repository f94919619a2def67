"""swa_tile_overcompute (ratio), read from program_counter.

The program's gauge ``attention/window_tile_overcompute``: score elements
the window kernels' tiles compute over the elements the band holds, forward
and backward together (``ops/pallas/flash_attention.window_tile_overcompute``:
1.0 would be no waste; blocks of 256 at a window of 512 compute 1.5 x). Set
at trace time by the kernels' plan, folded with the family's other gauges at
the last warm-up step (``program_gauges``). What ``swa_*_roofline`` cannot
reach because of the tiling is 100 / this. None where the program sets no
such gauge (no window layer, or a program without the kernels).
"""

NAME = "swa_tile_overcompute"
UNIT = "ratio"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    gauges = getattr(record.family, "program_gauges", None)
    return gauges().get("attention/window_tile_overcompute") if gauges \
        else None
