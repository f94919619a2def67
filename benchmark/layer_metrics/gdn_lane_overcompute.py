"""gdn_lane_overcompute (ratio), read from program_counter.

The program's gauge ``linear_attn/gdn_lane_overcompute``: the lanes of q | k
| v and of a head's state the delta rule's kernels compute on over the lanes
the model's own heads have, ``(2 Dk' + Dv' + Dk' Dv') / (2 Dk + Dv + Dk Dv)``
(``ops/pallas/gated_delta.lane_count``). 1.0 where the heads lie on the
128-lane grid (Qwen3-Next's 128 x 128); 1.77 where heads of 96 x 192 run
zero-padded to 128 x 256 — the price of the layout, and what
``gdn_scan_roofline``, which counts the PUBLISHED heads' work, cannot reach
because of it is 100 / this. Set at trace time by the kernels' plan, folded
with the family's other gauges at the last warm-up step
(``program_gauges``). None where the program sets no such gauge (no DeltaNet
layer, the XLA form, or a program from before the gauge).
"""

NAME = "gdn_lane_overcompute"
UNIT = "ratio"
LAYER = "linear attention"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    gauges = getattr(record.family, "program_gauges", None)
    return gauges().get("linear_attn/gdn_lane_overcompute") if gauges \
        else None
