"""Qwen3-Next on the engine, on the CPU at small sizes: the published depth
built abstractly, and ``dstpu.initialize`` steps under ZeRO-3 with remat over
two devices. The blocks against the reference: ``tests/test_qwen3_next.py``.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import qwen3_next as fam
from tests.cell_config import config_file

FILE = config_file("qwen3-next-80b-a3b-ep16-depth4")


def test_builds_at_the_published_depth_abstractly():
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                                 qwen3_next_80b_a3b)
    cfg = qwen3_next_80b_a3b(experts_held=32)
    shapes = jax.eval_shape(
        lambda r, x: Qwen3NextForCausalLM(cfg).init(r, x)["params"],
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    layers = shapes["layers"]
    assert sorted(layers) == ["l0", "l1", "l2", "l3"]
    assert layers["l0"]["linear_attn"]["in_proj_qkvz"]["kernel"].shape == (
        12, 2048, 12288)
    assert layers["l3"]["attn"]["q_proj"]["kernel"].shape == (12, 2048, 8192)
    assert layers["l3"]["mlp"]["router"].shape == (12, 2048, 512)
    assert layers["l3"]["mlp"]["gate_proj"].shape == (12, 32, 2048, 512)
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert count == cfg.num_params()
    # all 512 experts held: the published model, 80B by this count
    assert qwen3_next_80b_a3b().num_params() == pytest.approx(79.67e9,
                                                              rel=1e-3)


def test_trains_through_the_engine_under_zero3_with_remat():
    """``dstpu.initialize`` over two devices, ZeRO-3, every block under its
    gather edge and remat: the loss falls on a repeated batch, the first
    loss is the system step's, and the ``moe/*`` gauges are folded."""
    config = copy.deepcopy(FILE)
    config["rehearse_cpu"]["model"].update(remat=True)
    config["rehearse_cpu"]["num_hidden_layers"] = 8
    ids = np.random.default_rng(1).integers(0, 512, (2, 64)).astype(np.int32)
    engine, params = fam.build_train(config, 2, 0, jax.devices()[:2], True)
    assert engine.zero.layer_stacked_prefixes == ("layers",)
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
    assert gauges["moe/rows_max_over_mean"] >= 1.0
