"""Trajectory parity against stage 0 (``tests/zero_matrix.py``) under bf16
compute with bf16 gradients over two micro-batches: LLaMA and OLMoE at stages
1, 2 and 3. GPT-2: ``tests/test_zero_matrix_bf16.py`` (a file a model class:
these are the last files ``--dist loadfile`` deals, and what the last file
takes is the run's tail)."""

import pytest

from tests import zero_matrix


@pytest.mark.parametrize("stage", [1, 2, 3])
@pytest.mark.parametrize("family", [
    f for f in zero_matrix.FAMILIES if not f.startswith("gpt2")])
def test_stage_trajectory_matches_stage0_bf16(family, stage):
    zero_matrix.assert_trajectory_matches_stage0(family, stage, "bf16-gas2")
