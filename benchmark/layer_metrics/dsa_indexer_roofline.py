"""dsa_indexer_roofline (%), read from device_trace.

The indexer's score kernel against its compute roofline: the flops its
products need (2 x 64 a causal pair a head, 16 heads: the family's
``indexer_flops_per_step``, forward) times the passes of a step in which the
kernel ran (the forward and a rematted block's recomputation: two), over the
bf16 peak, over the device time of the Pallas custom-calls traced under the
scope ``dsa_indexer``, on the busiest chip. Bound: compute; a contraction of
64 fills half the MXU's depth, and relu, the weight and the sum over heads are
VPU work beside it.
"""

from benchmark import scope_reduce

NAME = "dsa_indexer_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


TAG = "dsa_indexer"


def share(record, tag, count, pick, peak):
    """A ``dsa_*`` kernel's share of its roofline: the family's ``count`` (a
    function of the configuration, the chip's batch and the sequence; ``pick``
    takes the kernel's figure out of what it returns) TIMES the passes of a
    step in which the kernel ran (forward, recompute, backward: a call's work
    each), over the row ``peak`` of ``record.peaks``, over the Pallas time
    under ``tag``. None where the family counts no such thing or nothing ran
    under the tag (the parent's program, no trace)."""
    count = getattr(record.family, count, None)
    if record.peaks is None or count is None \
            or not scope_reduce.kernel_ms(record, (tag,)):
        return None
    passes = len({phase for phase, t, kind, ms
                  in scope_reduce.busiest_chip(record)["rows"]
                  if t == tag and kind == "pallas" and ms > 0})
    per_chip = record.extra["global_batch"] // record.cell["chips"]
    needed = pick(count(record.config, per_chip, record.extra["seq_len"],
                        record.rehearse))
    return scope_reduce.kernel_roofline(record, tag, needed * passes,
                                        record.peaks[peak])


def read(record):
    return share(record, TAG, "indexer_flops_per_step", lambda n: n[0],
                 "bf16_flops_per_s")
