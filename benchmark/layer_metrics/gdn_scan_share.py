"""gdn_scan_share (%), read from device_trace.

Device time of the gated delta rule — everything traced under a scope that
starts with ``gdn_scan`` (``ops/gated_delta.py``: the all-chunks preparation
``gdn_scan_prep``, the chunk loop ``gdn_scan``, forward, backward and
recomputation; a later Pallas kernel under ``gdn_scan_fwd`` / ``gdn_scan_bwd``
keeps the tag) — over the slice's busy time, on the busiest chip: the rows
of ``extra.scope_attribution`` tagged ``gdn_scan``, whatever their kind. None
where the family lists no such tag or nothing ran under it.
"""

from benchmark import scope_reduce

NAME = "gdn_scan_share"
UNIT = "%"
LAYER = "linear attention"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAG = "gdn_scan"


def scan_ms(record):
    """Device ms a step under ``gdn_scan*`` on the busiest chip, or None."""
    if TAG not in getattr(record.family, "KERNEL_TAGS", ()):
        return None
    chip = scope_reduce.busiest_chip(record)
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag == TAG) or None


def read(record):
    ms = scan_ms(record)
    if not ms:
        return None
    return 100.0 * ms / scope_reduce.busiest_chip(record)["busy_ms"]
