"""Trajectory parity against stage 0 (``tests/zero_matrix.py``) under bf16
compute with bf16 gradients over two micro-batches: the four families at
stages 1, 2 and 3."""

import pytest

from tests import zero_matrix


@pytest.mark.parametrize("stage", [1, 2, 3])
@pytest.mark.parametrize("family", zero_matrix.FAMILIES)
def test_stage_trajectory_matches_stage0_bf16(family, stage):
    zero_matrix.assert_trajectory_matches_stage0(family, stage, "bf16-gas2")
