"""SmallThinker on the engine, on the CPU at small sizes: ``dstpu.initialize``
steps under ZeRO-3 with remat over two devices, remat on and off agreeing, and
the layers on the flash kernels where flash is on. The blocks against the
reference: ``tests/test_smallthinker.py``.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import smallthinker as fam
from deepspeed_tpu.models.smallthinker import (SmallThinkerForCausalLM,
                                               smallthinker_tiny)
from tests import hlo_text, model_cases
from tests.cell_config import config_file

FILE = config_file("smallthinker-21b-a3b-ep4-depth4")


# ------------------------------------------------ the model on the engine

@pytest.mark.parametrize("depth", [4, 5], ids=["2_periods", "2x2+1"])
def test_trains_through_the_engine_under_zero3_with_remat(depth):
    """``dstpu.initialize`` over two devices, ZeRO-3, every block under its
    gather edge and remat — whole periods (of two layers, full and sliding)
    and a depth with a tail outside the scan: the loss falls on a repeated
    batch, the first loss is the system step's, and the ``moe/*`` gauges are
    folded."""
    config = copy.deepcopy(FILE)
    config["rehearse_cpu"]["model"].update(remat=True)
    layout = [i % 2 for i in range(depth)]
    config["rehearse_cpu"].update(num_hidden_layers=depth,
                                  sliding_window_layout=layout,
                                  rope_layout=layout)
    ids = np.random.default_rng(1).integers(0, 512, (2, 48)).astype(np.int32)
    engine, params = fam.build_train(config, 2, 0, jax.devices()[:2], True)
    assert engine.zero.layer_stacked_prefixes == ("layers",)
    assert fam.model_config(config, True).plan == (2, 2, depth - 4)
    want = float(fam.system_step(config, params, ids, jax.devices()[0],
                                 True)[0])
    losses = [float(engine.train_batch({"input_ids": ids}))
              for _ in range(5)]
    assert losses[0] == pytest.approx(want, abs=0.02)
    assert losses[-1] < losses[0] - 0.02
    gauges = engine.telemetry_flush()["gauges"]
    assert gauges["moe/dropped_rows"] == 0
    assert 0.05 < gauges["moe/rows_held_share"] < 0.6      # 1/4 at uniform
    assert gauges["moe/held_slabs"] >= 1.0
    assert gauges["moe/combine_rows_walked"] >= 1.0


def test_the_layers_run_the_kernels_where_flash_is_on():
    """``use_flash=True`` (the TPU's choice) sends a sliding layer through
    the window kernels — here in the interpreter — and the full layer
    through the causal ones, at a KV group of 3 query heads; the outputs are
    the reference path's."""
    import dataclasses
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, 128)),
                      jnp.int32)
    cfg = smallthinker_tiny(num_hidden_layers=2, sliding_window_layout=[0, 1],
                            rope_layout=[0, 1], experts_held=4)
    params = jax.jit(SmallThinkerForCausalLM(cfg).init)(
        jax.random.PRNGKey(0), ids)["params"]

    def run(use_flash):
        model = SmallThinkerForCausalLM(dataclasses.replace(
            cfg, use_flash=use_flash))
        fn = lambda p: model.apply({"params": p}, ids, labels=ids)  # noqa
        # the loss from the undifferentiated program (the kernels' primal
        # calls), the gradients from their forward and backward rules
        loss, text = hlo_text.run_with_jaxpr(fn, params)
        return loss, jax.jit(jax.grad(fn))(params), text

    (want, want_g, plain), (got, got_g, flash) = run(False), run(True)
    assert "_flash_attention_swa" in flash \
        and "_flash_attention_swa" not in plain
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-3)


def test_remat_on_and_off_agree_and_keep_the_routers_choice():
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 48)),
                      jnp.int32)

    def model_of(remat):
        return SmallThinkerForCausalLM(smallthinker_tiny(
            num_hidden_layers=2, sliding_window_layout=[0, 1],
            rope_layout=[0, 1], experts_held=4, remat=remat))

    (want, plain), (got, rematted) = \
        model_cases.gradients_without_and_with_remat(model_of, ids)
    assert "moe_experts" in rematted and "moe_experts" not in plain
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)
