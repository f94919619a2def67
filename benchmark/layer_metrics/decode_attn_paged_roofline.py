"""decode_attn_paged_roofline (%), read from device_trace.

``decode_attention_paged`` against its HBM roofline: the K/V bytes the
slice's decode steps needed (from the slots' contexts at each step,
``roofline.kv_read_bytes``) over 819 GB/s, over the kernel's device time.
The kernel is the Pallas custom-call that takes the whole pool
(``[layers, blocks, heads, page, head_dim]``) as an operand. Bound: HBM.
"""

from benchmark import readers, roofline, trace_reduce

NAME = "decode_attn_paged_roofline"
UNIT = "%"
LAYER = "decode kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    if record.peaks is None or not record.extra.get("slice_steps"):
        return None
    pool = "[" + record.extra["pool_shape"] + "]"
    secs = readers.slice_op_seconds(
        record, lambda n: trace_reduce.is_pallas(n) and pool in n)
    if not secs:
        return None
    needed = 0
    for steps, ends in record.extra["slice_steps"]:
        for j in range(steps):
            needed += record.family.decode_kv_bytes(
                record.config, [max(e - j, 1) for e in ends], record.rehearse)
    return roofline.share(needed, record.peaks["hbm_bytes_per_s"],
                                  secs)
