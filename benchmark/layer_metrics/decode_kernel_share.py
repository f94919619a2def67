"""decode_kernel_share (%), read from device_trace.

Device time of the Pallas kernels in the traced slice (the tick's
``ln_qkv``, ``decode_attention_paged`` and ``out_ffn`` stacked kernels, and
the flash kernel of whatever prefills fell into the slice) over the
slice's busy time. The device plane names all of them ``%closed_call``-
style custom-calls; see PERF.md on telling them apart.
"""

from benchmark import readers, trace_reduce

NAME = "decode_kernel_share"
UNIT = "%"
LAYER = "decode kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    return readers.slice_op_share(record, trace_reduce.is_pallas)
