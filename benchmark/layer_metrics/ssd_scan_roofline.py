"""ssd_scan_roofline (%), read from device_trace.

The state-space scan against its roofline: the time its REQUIRED work takes
at the chip's peaks — the family's ``ssd_scan_flops_and_bytes``: the
RECURRENCE's 5 P N flops a token a head (decay, outer product, read-out),
x 3 with the backward pass, over the bf16 peak; x, B, C, dt, y and their
cotangents once each (and the inputs once more for the backward pass) over
the HBM peak; whichever takes LONGER — over the device time traced under
``ssd_scan*`` on the busiest chip (``ssd_scan_share``'s rows). At heads of
64 x 128 the bytes bind (~27 flops a byte against the v5e's 240). What a
chunked form adds — ``C B^T``, the masked intra-chunk product, the state
written once a chunk for the backward pass, recomputation under remat — is
time and no counted work, so the share can only fall short. None on a CPU
rehearsal, where the family counts no such work, or where nothing ran under
the scope.
"""

from benchmark import roofline
from benchmark.layer_metrics.ssd_scan_share import scan_ms

NAME = "ssd_scan_roofline"
UNIT = "%"
LAYER = "state-space mixer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    count = getattr(record.family, "ssd_scan_flops_and_bytes", None)
    if record.peaks is None or count is None:
        return None
    ms = scan_ms(record)
    if not ms:
        return None
    tokens = record.extra["tokens_per_step"] // record.cell["chips"]
    flops, nbytes = count(record.config, tokens, record.rehearse)
    needed_s = max(flops / record.peaks["bf16_flops_per_s"],
                   nbytes / record.peaks["hbm_bytes_per_s"])
    return roofline.share(needed_s, 1.0, ms / 1e3)
