"""bd_tile_overcompute (ratio), read from program_counter.

The program's gauge ``attention/bd_tile_overcompute``: score elements the
block-diffusion kernels' walked tiles compute over the pairs the mask
allows, forward and backward alike
(``ops/pallas/block_diffusion_attention.tile_overcompute``: 1.0 would be no
waste; ``n^2 + 2n`` tiles of 512 at L 8,192 and block length 4 compute 1.124
x). Set at trace time by the kernels' plan, folded with the family's other
gauges at the last warm-up step (``program_gauges``). What
``bd_*_roofline`` cannot reach because of the tiling is 100 / this. None
where the program sets no such gauge.
"""

NAME = "bd_tile_overcompute"
UNIT = "ratio"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    gauges = getattr(record.family, "program_gauges", None)
    return gauges().get("attention/bd_tile_overcompute") if gauges else None
