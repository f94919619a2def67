"""gdn_xla_sites (count), read from program_counter.

Stages of the Gated DeltaNet layers that fell to an XLA form at trace time:
the program's gauges ``mixer/conv_xla_sites`` + ``mixer/norm_xla_sites``
(call sites of ``ops/mixer_elementwise.conv_act`` / ``gated_group_norm``
traced through their XLA forms) + 1 where the delta rule itself took its XLA
form (``linear_attn/gdn_kernel_heads_per_step`` 0). 0 when every stage of
every layer took its Pallas kernel; a shape a kernel refuses is no longer
silent (each refusal is also one log line naming the condition). Folded with
the family's other gauges at the last warm-up step (``program_gauges``).
None where the family names no DeltaNet layer or the program sets none of
the gauges.
"""

NAME = "gdn_xla_sites"
UNIT = "count"
LAYER = "linear attention"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    gauges = getattr(record.family, "program_gauges", None)
    if gauges is None or not getattr(record.family, "GDN_LAYER_TAGS", ()):
        return None
    found = gauges()
    heads = found.get("linear_attn/gdn_kernel_heads_per_step")
    sites = [found.get(f"mixer/{stage}_xla_sites")
             for stage in ("conv", "norm")]
    if heads is None and all(s is None for s in sites):
        return None
    return sum(s or 0 for s in sites) + (heads == 0)
