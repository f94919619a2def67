"""moe_router_ms (ms), read from device_trace.

What the ROUTER costs: device ms a step, self time, of everything traced
under the scope ``moe_router`` (the float32 logits, the softmax, the top-k,
the group sizes and the two auxiliary losses, and their backward passes), in
every phase, on the busiest chip — the rows of ``extra.scope_attribution``
whose tag is ``moe_router``. The largest part of ``moe_dispatch_ms`` on the
cells that hold a share of their experts, and the one part a router that
reads the block's INPUT moves: its logits no longer wait for the mixer. None
where the family lists no such tag or nothing ran under it.
"""

from benchmark import scope_reduce

NAME = "moe_router_ms"
UNIT = "ms"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAG = "moe_router"


def read(record):
    if TAG not in getattr(record.family, "MODULE_TAGS", ()):
        return None
    chip = scope_reduce.busiest_chip(record)
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag == TAG) or None
