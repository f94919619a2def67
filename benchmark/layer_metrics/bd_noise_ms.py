"""bd_noise_ms (ms), read from device_trace.

What a block-diffusion step costs outside its layers: device ms a step, self
time, of everything traced under the scope ``bd_noise``
(``models/llama.py``: the two uniform draws, the masking, the concatenation
of the noised and the clean ids, the weights 1 / t, and the split that
hands the noised half to the head, with what the backward pass does to it)
in every phase, on the busiest chip: the rows of
``extra.scope_attribution`` whose tag is ``bd_noise``. None where the
family lists no such tag, nothing ran under the scope or the run has no
trace.
"""

from benchmark import scope_reduce

NAME = "bd_noise_ms"
UNIT = "ms"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
TAG = "bd_noise"


def read(record):
    if TAG not in getattr(record.family, "MODULE_TAGS", ()):
        return None
    chip = scope_reduce.busiest_chip(record)
    if not chip:
        return None
    return sum(ms for _, tag, _, ms in chip["rows"] if tag == TAG) or None
