"""The three share cells that started at the full rate warm up (PR 55).

``smallthinker-21b-a3b-ep4-depth4``, ``laguna-xs2-33b-a3b-ep8-depth5`` and
``qwen3-next-80b-a3b-ep16-depth4`` carry the ``scheduler`` block the Nemotron
and Kanana-2 files have, letter for letter, and say why; the CPU rehearsal
keeps the full rate from step 0 (a warm-up from 1e-4 to 1e-4), which the
engine tests' six steps on a repeated batch need for the loss to fall.
"""

import json
import os

import pytest

from benchmark import manifest
from benchmark.families import common

CELLS = ("smallthinker-21b-a3b-ep4-depth4", "laguna-xs2-33b-a3b-ep8-depth5",
         "qwen3-next-80b-a3b-ep16-depth4")
WARMED = ("nemotron-3-nano-30b-a3b-ep16-depth9",
          "kanana-2-30b-a3b-ep8-depth6")


def _config(name):
    with open(os.path.join(manifest.ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CELLS)
def test_the_share_cells_warm_up_as_the_nemotron_cell_does(name):
    want = _config(WARMED[0])["train"]["engine"]["scheduler"]
    assert want == _config(WARMED[1])["train"]["engine"]["scheduler"]
    config = _config(name)
    assert config["train"]["engine"]["scheduler"] == want
    assert want == {"type": "WarmupLR", "params": {
        "warmup_min_lr": 0.0, "warmup_max_lr": 0.0001,
        "warmup_num_steps": 2000, "warmup_type": "linear"}}
    assert "WarmupLR" in config["train"]["scheduler_why"]


@pytest.mark.parametrize("name", CELLS)
def test_the_rehearsal_keeps_the_full_rate_from_step_zero(name):
    """The rehearsal's override is a warm-up from the optimizer's rate to
    itself, and touches nothing else of the block."""
    config = _config(name)
    full = config["train"]["engine"]["scheduler"]
    got = common.merged(config, "train", True)["engine"]["scheduler"]
    lr = config["train"]["engine"]["optimizer"]["params"]["lr"]
    assert got["params"]["warmup_min_lr"] == lr \
        == got["params"]["warmup_max_lr"]
    assert {**got, "params": {**got["params"], "warmup_min_lr": 0.0}} == full
    assert config["rehearse_cpu"]["train"]["scheduler_why"]
