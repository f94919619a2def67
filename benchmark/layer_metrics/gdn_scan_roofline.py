"""gdn_scan_roofline (%), read from device_trace.

The gated delta rule against its roofline: the time its REQUIRED work takes
at the chip's peaks — the family's ``gdn_scan_flops_and_bytes``: the
recurrence's 6 Dk Dv flops a token a value head, x 3 with the backward pass,
over the bf16 peak; q, k, v, g, beta, o and their cotangents once each (and
the inputs once more for the backward pass) over the HBM peak; whichever
takes LONGER — over the device time traced under ``gdn_scan*`` on the
busiest chip (``gdn_scan_share``'s rows). At 128 x 128 heads the bytes bind
(~142 flops a byte against the v5e's 240: 4.0 ms of bytes, 2.4 ms of flops
a step at 3 layers x 16,384 tokens). What a chunked form adds — the
intra-chunk products, the triangular inverse, the state written once a chunk
for the backward pass, recomputation under remat — is time and no counted
work, so the share can only fall short. None on a CPU rehearsal, where the
family counts no such work, or where nothing ran under the scope.
"""

from benchmark import roofline
from benchmark.layer_metrics.gdn_scan_share import scan_ms

NAME = "gdn_scan_roofline"
UNIT = "%"
LAYER = "linear attention"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    count = getattr(record.family, "gdn_scan_flops_and_bytes", None)
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
