"""moe_gmm_roofline (%), read from device_trace.

The expert layer's grouped matmuls against their compute roofline: the flops
the step's routed rows NEED (the family's ``moe_gmm_flops_per_step``: three
products — forward, dlhs, drhs — of gate, up and down over tokens x k rows)
over the bf16 peak, over the device time of the Pallas custom-calls traced
under the scopes ``moe_gmm*``, on the busiest chip. Tiles that straddle two
experts and whatever remat re-runs add time and no counted flops, so the
share can only fall short. Bound: compute. None where the family counts no
such flops or no event carries the scope (a program without the kernel).
"""

from benchmark import scope_reduce

NAME = "moe_gmm_roofline"
UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    count = getattr(record.family, "moe_gmm_flops_per_step", None)
    if record.peaks is None or count is None:
        return None
    tokens = record.extra["tokens_per_step"] // record.cell["chips"]
    return scope_reduce.kernel_roofline(
        record, "moe_gmm", count(record.config, tokens, record.rehearse),
        record.peaks["bf16_flops_per_s"])
