"""gdn_elementwise_roofline (%), read from device_trace.

The two elementwise stages round the delta rule against their roofline: the
time the bytes they HAVE to move take at the chip's HBM peak — the family's
``gdn_elementwise_bytes_per_step``, at the published head sizes whichever
form or layout runs: the projection's q | k | v columns read and q, k, v
written, o and the gate read and the gated norm's result written, forward;
the cotangents and the inputs read and the inputs' cotangents written,
backward, each array once — over ``gdn_elementwise_ms``'s device time. Both
stages are memory-bound (a few flops a byte). What a re-layout of the heads
to whole lane tiles moves, what zero lanes add to every array and what
remat's recomputation reads again are time and no counted work, so the share
can only fall short. None on a CPU rehearsal, where the family counts no
such bytes, or where nothing ran under the scopes.
"""

from benchmark import roofline
from benchmark.layer_metrics.gdn_elementwise_ms import stage_ms

NAME = "gdn_elementwise_roofline"
UNIT = "%"
LAYER = "linear attention"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    count = getattr(record.family, "gdn_elementwise_bytes_per_step", None)
    if record.peaks is None or count is None:
        return None
    ms = stage_ms(record)
    if not ms:
        return None
    tokens = record.extra["tokens_per_step"] // record.cell["chips"]
    nbytes = count(record.config, tokens, record.rehearse)
    return roofline.share(nbytes, record.peaks["hbm_bytes_per_s"], ms / 1e3)
