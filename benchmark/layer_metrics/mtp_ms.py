"""mtp_ms (ms), read from device_trace.

What the multi-token-prediction module costs
(``deepspeed_tpu/models/deepseek_v3.py``, ``num_nextn_predict_layers``):
device ms a step, self time, of every instruction whose ``op_name`` path
holds the family's ``MTP_SCOPE`` (``mtp``) as an element — the lookup of the
next token's embedding, the two norms and the join ``mtp_eh_proj``, the
module's own block (its latent attention and its flash kernels, its expert
FFN, its stream mixers), its head norm and ITS pass through the shared head
and loss — in every phase, on the busiest chip. The detail table's ``tag``
column cannot give this: a row has ONE tag, and what runs inside the module
keeps the tag of what it runs through (``ds_loss_head`` counts both head
passes, ``mla_layer_ms`` all six attention modules), so this reader joins the
slice's events to the compiled text itself, as ``scope_reduce`` does. The
module is a training loss term; its time is what the step pays for the
second target. None where the family names no such scope or nothing ran
under it.
"""

from benchmark import scope_reduce, trace_reduce

NAME = "mtp_ms"
UNIT = "ms"
LAYER = "multi-token prediction"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    scope = getattr(record.family, "MTP_SCOPE", None)
    found = scope_reduce.attribution(record) if scope else None
    if not found:
        return None
    plane = found["chip"]
    table = scope_reduce.scope_table(record.compiled_text)
    events = trace_reduce.clip(trace_reduce.ops(record.trace, plane),
                               *record.slice)
    ns = sum(t for i, t in trace_reduce.self_times(events).items()
             if scope in scope_reduce.place(
                 table, events[i].name, record.family)[2].split("/"))
    return ns / (1e6 * max(found["chips"][plane]["steps"], 1)) or None
