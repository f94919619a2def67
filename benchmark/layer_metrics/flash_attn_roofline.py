"""flash_attn_roofline (%), read from device_trace.

Flash forward + backward against their compute roofline: the causal flops
the calls need (six S x S x D matmuls per head, halved by the mask; the
backward's recomputed QK^T is not counted) over the bf16 peak, over the
kernels' device time, per chip. Bound: compute (head_dim 64 keeps the
MXU half fed).
"""

from benchmark import readers, roofline, trace_reduce

NAME = "flash_attn_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    secs = readers.slice_op_seconds(record, trace_reduce.is_pallas)
    if not secs or record.peaks is None:
        return None
    steps = len(trace_reduce.modules(record.trace, record.planes()[0],
                                     record.extra["step_module"]))
    per_chip = record.extra["global_batch"] // record.cell["chips"]
    flops = steps * record.family.train_attention_flops_per_step(
        record.config, per_chip, record.extra["seq_len"], record.rehearse)
    return roofline.share(flops, record.peaks["bf16_flops_per_s"],
                                  secs)
