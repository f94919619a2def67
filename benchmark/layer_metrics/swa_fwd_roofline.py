"""swa_fwd_roofline (%), read from device_trace.

The window forward kernel against its compute roofline: the flops the BAND
needs — ``S*W - W(W-1)/2`` scores a head, QK^T and PV (the family's
``swa_flops_per_step``, its first value) — over the bf16 peak, over the
device time of the Pallas custom-calls traced under ``swa_fwd`` on the
busiest chip. What the kernel's tiles compute outside the band
(``swa_tile_overcompute``) and what remat re-runs are time and no counted
work, so the share can only fall short. Bound: compute. None where the
family counts no such flops or no event carries the scope.
"""

from benchmark import scope_reduce

NAME = "swa_fwd_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def needed(record):
    """(forward, backward) flops one chip's step needs, or None."""
    count = getattr(record.family, "swa_flops_per_step", None)
    if record.peaks is None or count is None:
        return None
    per_chip = record.extra["global_batch"] // record.cell["chips"]
    return count(record.config, per_chip, record.extra["seq_len"],
                 record.rehearse)


def read(record):
    flops = needed(record)
    if flops is None:
        return None
    return scope_reduce.kernel_roofline(
        record, "swa_fwd", flops[0], record.peaks["bf16_flops_per_s"])
