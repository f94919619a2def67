"""loss_head_ms (ms), read from device_trace.

What the language-model head and its loss cost: device ms a step, self
time, of everything traced under the scope ``ds_loss_head``
(``models/gpt2.chunked_lm_loss``: the slice and pad of the hidden states,
each chunk's logits matmul, log-sum-exp and nll, dlogits and its two
products, and what the backward pass does to them) in EVERY phase —
forward, backward and, in a program that derives the logits a second time,
the recomputation — on the busiest chip: the rows of
``extra.scope_attribution`` whose tag is ``ds_loss_head``, a module tag of
every family. The head is a third of a step where the model is shallow and
the vocabulary whole; what is left of the step beside it is the layers'.
None where nothing ran under the scope or the run has no trace.
"""

from benchmark import scope_reduce

NAME = "loss_head_ms"
UNIT = "ms"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAG = "ds_loss_head"


def read(record):
    chip = scope_reduce.busiest_chip(record)
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag == TAG) or None
