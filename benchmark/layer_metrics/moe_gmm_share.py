"""moe_gmm_share (%), read from device_trace.

Device time of the grouped-matmul kernels — the Pallas custom-calls traced
under the scopes ``moe_gmm*`` (forward, dlhs, drhs), found through
``scope_reduce``'s join — over the slice's busy time, worst chip. None where
no event carries the scope.
"""

from benchmark import scope_reduce

NAME = "moe_gmm_share"
UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    per_chip = scope_reduce.kernel_ms(record, ("moe_gmm",))
    if not per_chip:
        return None
    chips = scope_reduce.attribution(record)["chips"]
    return max(100.0 * ms / chips[plane]["busy_ms"]
               for plane, ms in per_chip.items() if chips[plane]["busy_ms"])
