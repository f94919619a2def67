"""The training tolerances against what the chip measured and what a wrong
step would give.

At random initialisation the loss is ln(vocabulary) and the gradient norm
an average over millions of unbiased roundings, so neither tells matmul
precisions apart (PERF.md Findings PR 22, finding 6: weights cast to fp8 or
int8 move the norm by 0.003-0.56 % at the rehearsal's size). What the check
holds a step to is its arithmetic: the loss's reduction, the completeness
of the gradient, its scaling across chips.
"""

import json
import math
import os

import pytest

from benchmark import manifest
from benchmark.families import gpt2

# (configuration, largest |loss diff|, largest norm offset) on the chip,
# my chip runs, PR 22 (PERF.md section 6)
MEASURED = {"gpt2-large-774m": (2.7e-4, 0.0029),
            "gpt2-xl-1558m": (1.6e-4, 0.0029)}
LOSS, NORM = 11.0, 7.5


def _config(name):
    with open(os.path.join(manifest.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _passes(config, loss, norm):
    checks, _ = gpt2.judge_train(config, loss, norm, LOSS, NORM)
    return all(checks.values())


@pytest.mark.parametrize("name", sorted(MEASURED))
def test_what_the_chip_measured_passes_with_room(name):
    config, (dloss, dnorm) = _config(name), MEASURED[name]
    tol = config["train"]["tolerance"]
    assert tol["loss_abs"] <= 1e-3 and tol["grad_norm_rel"] <= 0.005
    assert _passes(config, LOSS + 1.5 * dloss, NORM * (1 - 1.5 * dnorm))
    assert _passes(config, LOSS - 1.5 * dloss, NORM * (1 + 1.5 * dnorm))


@pytest.mark.parametrize("name", sorted(MEASURED))
@pytest.mark.parametrize("fault,loss,norm", [
    # mean over S positions when S - 1 have a target: 1/1024 of the loss
    ("loss averaged over one position too many",
     LOSS * 1023 / 1024, NORM * 1023 / 1024),
    # one of n_layer + 2 about equal shares of the squared norm missing
    ("one layer's gradient left out", LOSS, None),
    # gradients summed over four chips and not averaged
    ("gradient not averaged over the chips", LOSS, 4 * NORM),
    ("clipped norm reported (clip 1.0)", LOSS, 1.0),
])
def test_a_wrong_step_fails(name, fault, loss, norm):
    config = _config(name)
    if norm is None:
        norm = NORM * math.sqrt(1 - 1 / (config["n_layer"] + 2))
    assert not _passes(config, loss, norm), fault
