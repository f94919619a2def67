"""ssd_scan_share (%), read from device_trace.

Device time of the state-space scan — everything traced under a scope that
starts with ``ssd_scan`` (``ops/pallas/ssd.py``: the kernels under
``ssd_scan_fwd`` / ``ssd_scan_bwd``, forward, backward and recomputation, and
the gates' re-layout round them under ``ssd_scan_prep``; ``ops/ssd.py``'s XLA
form under ``ssd_scan``) — over the slice's busy time, on the busiest chip:
the rows of ``extra.scope_attribution`` tagged ``ssd_scan``, whatever their
kind. None where the family lists no such tag or nothing ran under it.
"""

from benchmark import scope_reduce

NAME = "ssd_scan_share"
UNIT = "%"
LAYER = "state-space mixer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAG = "ssd_scan"


def scan_ms(record):
    """Device ms a step under ``ssd_scan*`` on the busiest chip, or None."""
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
