"""train_mfu (%), read from host_clock.

Model flops utilization: the window's tokens/s x the flops a token
REQUIRES (6 per matmul parameter + causal attention, nothing recomputed —
``roofline.dense_train_flops_per_token``) over chips x the bf16 peak of
``peaks.json``. An end-to-end utilization, not a kernel's roofline share.
"""

NAME = "train_mfu"
UNIT = "%"
LAYER = "train step program"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(record):
    if record.peaks is None or "train_tokens_per_s" not in record.e2e:
        return None
    per_token = record.family.train_flops_per_token(
        record.config, record.extra["seq_len"], record.rehearse)
    peak = record.cell["chips"] * record.peaks["bf16_flops_per_s"]
    return 100.0 * record.e2e["train_tokens_per_s"] * per_token / peak
