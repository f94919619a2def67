"""mhc_stream_roofline (%), read from device_trace.

The residual streams' mixers against their roofline: the time the bytes they
HAVE to move take at the chip's HBM peak — the family's
``mhc_stream_bytes_per_step``: a branch reads the stream and the branch's
output once and writes the new stream once forward, reads the cotangent, the
stream and the output once and writes the two cotangents once backward, the
coefficients' few numbers a token besides, each array once — over
``mhc_stream_ms`` (everything traced under the family's ``MHC_TAGS``, every
phase, the busiest chip). The count is the least ANY implementation moves and
reads the same whatever implements the mixer; recomputation under remat is
time and no counted work (as ``train_mfu`` counts none), so the share can
only fall short of 100. It is the size of what a fused stream kernel could
win. None on a CPU rehearsal, where the family counts no such bytes, or where
nothing ran under the scopes.
"""

from benchmark import roofline
from benchmark.layer_metrics import mhc_stream_ms

NAME = "mhc_stream_roofline"
UNIT = "%"
LAYER = "residual streams"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    count = getattr(record.family, "mhc_stream_bytes_per_step", None)
    if record.peaks is None or count is None:
        return None
    ms = mhc_stream_ms.read(record)
    if not ms:
        return None
    tokens = record.extra["tokens_per_step"] // record.cell["chips"]
    return roofline.share(count(record.config, tokens, record.rehearse),
                          record.peaks["hbm_bytes_per_s"], ms / 1e3)
