"""Trajectory parity against stage 0 in float32 (``tests/zero_matrix.py``):
GPT-2 in its two layer layouts at stages 1, 2 and 3, and the GPT-2 head's
three forms (tied, untied, tied with a chunked loss) at stage 3. LLaMA and
OLMoE: ``tests/test_zero_matrix_fp32_llama.py``."""

import pytest

from tests import zero_matrix

CASES = [(f, s) for f in zero_matrix.FAMILIES if f.startswith("gpt2")
         for s in (1, 2, 3)] + [
    (f, 3) for f in ("gpt2-tied", "gpt2-untied", "gpt2-tied-chunked")]


@pytest.mark.parametrize("family,stage", CASES,
                         ids=[f"{f}-stage{s}" for f, s in CASES])
def test_stage_trajectory_matches_stage0(family, stage):
    zero_matrix.assert_trajectory_matches_stage0(family, stage, "fp32-gas1")
