"""Laguna on the engine, on the CPU at small sizes: ``dstpu.initialize``
steps under ZeRO-3 with remat over two devices, at the cut's depth and at one
with a tail outside the scan. The window layers on the window kernels where
flash is on: ``tests/test_laguna_layers.py``; the blocks against the
reference: ``tests/test_laguna.py``.
"""

import copy

import jax
import numpy as np
import pytest

from benchmark.families import laguna as fam
from deepspeed_tpu.models.laguna import FULL, SLIDING
from tests.cell_config import config_file

FILE = config_file("laguna-xs2-33b-a3b-ep8-depth5")


@pytest.mark.parametrize("depth", [5, 12], ids=["the_cut", "1+2x4+3"])
def test_trains_through_the_engine_under_zero3_with_remat(depth):
    """``dstpu.initialize`` over two devices, ZeRO-3, every block under its
    gather edge and remat — the cut (1 + 4) and a depth with a tail outside
    the scan (1 + 2 x 4 + 3, as the published 40 = 1 + 9 x 4 + 3): the loss
    falls on a repeated batch, the first loss is the system step's, and the
    ``moe/*`` gauges are folded."""
    config = copy.deepcopy(FILE)
    config["rehearse_cpu"]["model"].update(remat=True)
    config["rehearse_cpu"].update(
        num_hidden_layers=depth,
        layer_types=[FULL if i % 4 == 0 else SLIDING for i in range(depth)],
        mlp_layer_types=["dense"] + ["sparse"] * (depth - 1),
        num_attention_heads_per_layer=[3 if i % 4 == 0 else 4
                                       for i in range(depth)])
    ids = np.random.default_rng(1).integers(0, 512, (2, 64)).astype(np.int32)
    engine, params = fam.build_train(config, 2, 0, jax.devices()[:2], True)
    assert engine.zero.layer_stacked_prefixes == ("layers",)
    assert fam.model_config(config, True).plan == (1, 4, (depth - 1) // 4,
                                                   (depth - 1) % 4)
    want = float(fam.system_step(config, params, ids, jax.devices()[0],
                                 True)[0])
    losses = [float(engine.train_batch({"input_ids": ids}))
              for _ in range(6)]
    assert losses[0] == pytest.approx(want, abs=0.02)
    assert losses[-1] < losses[0] - 0.02
    gauges = engine.telemetry_flush()["gauges"]
    assert gauges["moe/dropped_rows"] == 0
    assert 0.05 < gauges["moe/rows_held_share"] < 0.6      # 1/4 at uniform
    assert gauges["moe/held_slabs"] >= 1.0
    assert gauges["moe/combine_rows_walked"] >= 1.0
