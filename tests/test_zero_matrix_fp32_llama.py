"""Trajectory parity against stage 0 in float32 (``tests/zero_matrix.py``):
LLaMA and OLMoE at stages 1, 2 and 3. GPT-2: ``tests/test_zero_matrix_fp32.py``
(a file a model class: these are the last files ``--dist loadfile`` deals, and
what the last file takes is the run's tail)."""

import pytest

from tests import zero_matrix

CASES = [(f, s) for f in zero_matrix.FAMILIES if not f.startswith("gpt2")
         for s in (1, 2, 3)]


@pytest.mark.parametrize("family,stage", CASES,
                         ids=[f"{f}-stage{s}" for f, s in CASES])
def test_stage_trajectory_matches_stage0(family, stage):
    zero_matrix.assert_trajectory_matches_stage0(family, stage, "fp32-gas1")
