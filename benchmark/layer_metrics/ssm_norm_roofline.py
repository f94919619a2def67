"""ssm_norm_roofline (%), read from device_trace.

A Mamba-2 mixer's gated RMS norm against its roofline: the time the bytes it
HAS to move take at the chip's HBM peak — the family's
``ssm_norm_bytes_per_step``: y and z in and the normed y out forward; the
cotangent, y and z in and the cotangents of y and z out backward, each array
once — over the device time traced under ``ssm_norm`` (the family's
``SSM_NORM_TAG``: the kernels ``mixer_norm_fwd`` / ``mixer_norm_bwd`` and
the XLA ops round them, in every phase) on the busiest chip. Granite
4.0-H's norm runs over ONE group of all 4,096 channels, the form in which
a group is its own column block and a row tile is walked twice
(``ops/pallas/mixer_elementwise.py``); the stage is memory-bound (about two
flops a byte). What remat adds — the forward kernel runs again as the
recomputation — is time and no counted work, so the share can only fall
short. None on a CPU rehearsal, where the family counts no such bytes, or
where nothing ran under the scope.
"""

from benchmark import roofline, scope_reduce

NAME = "ssm_norm_roofline"
UNIT = "%"
LAYER = "state-space mixer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(record):
    count = getattr(record.family, "ssm_norm_bytes_per_step", None)
    tag = getattr(record.family, "SSM_NORM_TAG", None)
    if record.peaks is None or count is None or tag is None:
        return None
    chip = scope_reduce.busiest_chip(record)
    ms = chip and sum(ms for _, t, _, ms in chip["rows"] if t == tag)
    if not ms:
        return None
    tokens = record.extra["tokens_per_step"] // record.cell["chips"]
    nbytes = count(record.config, tokens, record.rehearse)
    return roofline.share(nbytes, record.peaks["hbm_bytes_per_s"], ms / 1e3)
