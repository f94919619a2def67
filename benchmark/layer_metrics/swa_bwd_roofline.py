"""swa_bwd_roofline (%), read from device_trace.

The window backward kernels (dq, and dk + dv) against their compute
roofline: the flops the BAND needs backward — dV, dP, dQ and dK over
``S*W - W(W-1)/2`` scores a head: the kernels' five products less the QK^T
each of the two recomputes (the family's ``swa_flops_per_step``, its second
value) — over the bf16 peak, over the device time of the Pallas custom-calls
traced under ``swa_bwd*`` on the busiest chip. Bound: compute. None where
the family counts no such flops or no event carries the scope.
"""

from benchmark import scope_reduce
from benchmark.layer_metrics.swa_fwd_roofline import needed

NAME = "swa_bwd_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    flops = needed(record)
    if flops is None:
        return None
    return scope_reduce.kernel_roofline(
        record, "swa_bwd", flops[1], record.peaks["bf16_flops_per_s"])
