"""Nemotron-H on the engine, on the CPU at small sizes: ``dstpu.initialize``
steps under ZeRO-3 with remat over two devices with the routers' selection
bias unmoved, remat on and off agreeing, and no auxiliary term traced; and
its expert layer alone: the 16 shares adding up to the uncut reference's
layer with the shared expert counted once. The blocks against the reference:
``tests/test_nemotron_h.py``.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import nemotron_h as fam
from benchmark.reference import nemotron_h as ref
from deepspeed_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                             nemotron_h_tiny)
from deepspeed_tpu.moe.dropless import DroplessMoE
from tests import hlo_text, model_cases
from tests.cell_config import config_file

FILE = config_file("nemotron-3-nano-30b-a3b-ep16-depth9")


# ------------------------------------------------ the model on the engine

def test_trains_through_the_engine_under_zero3_with_remat():
    """``dstpu.initialize`` over two devices, ZeRO-3, every layer under its
    gather edge and remat: the loss falls on a repeated batch, the first
    loss is the system step's, the ``moe/*`` and ``ssm/*`` gauges are
    folded, and the routers' selection bias comes out of five AdamW steps
    with weight decay as it went in."""
    config = copy.deepcopy(FILE)
    config["rehearse_cpu"]["model"].update(remat=True)
    config["rehearse_cpu"].update(num_hidden_layers=4,
                                  hybrid_override_pattern="ME*M")
    # the full rate from the first step: the file's warm-up over 2,000 steps
    # moves nothing in five
    del config["train"]["engine"]["scheduler"]
    ids = np.random.default_rng(1).integers(0, 512, (2, 48)).astype(np.int32)
    # the registry is the process's: another file's model in this worker
    # may have left a gauge this model must not set
    from deepspeed_tpu.telemetry.registry import default_registry
    default_registry().reset()
    engine, params = fam.build_train(config, 2, 0, jax.devices()[:2], True)
    bias = np.asarray(params["layer_1"]["mixer"]["e_score_correction_bias"])
    router = np.asarray(params["layer_1"]["mixer"]["router"])
    assert np.abs(bias).max() > 0.05        # drawn, then levelled: not zeros
    want = float(fam.system_step(config, params, ids, jax.devices()[0],
                                 True)[0])
    losses = [float(engine.train_batch({"input_ids": ids}))
              for _ in range(5)]
    assert losses[0] == pytest.approx(want, abs=0.02)
    assert losses[-1] < losses[0] - 0.02
    after = engine.state.params["layer_1"]["mixer"]
    np.testing.assert_array_equal(
        np.asarray(after["e_score_correction_bias"]), bias)
    assert np.abs(np.asarray(after["router"]) - router).max() > 1e-5
    gauges = engine.telemetry_flush()["gauges"]
    assert gauges["moe/dropped_rows"] == 0
    assert 0.05 < gauges["moe/rows_held_share"] < 0.6      # 1/4 at uniform
    assert "moe/aux_loss" not in gauges
    assert gauges["ssm/ssd_kernel_heads_per_step"] == 2


def test_remat_on_and_off_agree_and_keep_the_routers_choice():
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 48)),
                      jnp.int32)

    def model_of(remat):
        return NemotronHForCausalLM(nemotron_h_tiny(
            hybrid_override_pattern="ME*", experts_held=4, remat=remat))

    (want, plain), (got, rematted) = \
        model_cases.gradients_without_and_with_remat(model_of, ids)
    assert "moe_experts" in rematted and "moe_experts" not in plain
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


def test_no_auxiliary_term_is_traced_or_sown():
    """The config has no auxiliary loss: nothing lands in ``losses``, the
    two statistics of it are not sown, and no ``logsumexp`` (the z-loss) is
    in the traced layer."""
    ids = jnp.zeros((1, 16), jnp.int32)
    model = NemotronHForCausalLM(nemotron_h_tiny(
        hybrid_override_pattern="ME", experts_held=4))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    (_, vs), text = hlo_text.run_with_jaxpr(lambda p: model.apply(
        {"params": p}, ids, labels=ids, mutable=["losses", "stats"]), params)
    assert not jax.tree_util.tree_leaves(vs.get("losses", {}))
    sown = set(vs["stats"]["layer_1"]["mixer"])
    assert sown == set(model.stat_gauges) - {"moe_aux_loss", "moe_z_loss"}
    assert "reduce_max" in text and "logsumexp" not in text


# ------------------------------------------------ the expert layer's shares

H, E, K, F, FS, RANKS = 32, 32, 4, 24, 48, 16


def _layer_weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, *s: 0.3 * jax.random.normal(k, s)  # noqa: E731
    return {"router": n(ks[0], H, E), "bias": n(ks[1], E),
            "up": n(ks[2], E, H, F), "down": n(ks[3], E, F, H),
            "shared_up": n(ks[4], H, FS), "shared_down": n(ks[5], FS, H)}


def _layer(held=0, rank=0, shared=FS):
    return DroplessMoE(E, K, F, norm_topk_prob=True, balance_coeff=0.0,
                       z_coeff=0.0, dtype=jnp.float32, experts_held=held,
                       expert_share=rank, shared_d_ff=shared,
                       routed_scale=2.5, act="relu2", gated=False,
                       shared_gate=False, score="sigmoid", choice_bias=True)


def _params(p, lo=0, held=E, shared=True):
    out = {"router": p["router"], "e_score_correction_bias": p["bias"],
           "up_proj": p["up"][lo:lo + held],
           "down_proj": p["down"][lo:lo + held]}
    if shared:
        out.update(shared_up_proj=p["shared_up"],
                   shared_down_proj=p["shared_down"])
    return out


def test_the_sixteen_shares_with_the_shared_expert_once_are_the_whole_layer():
    """The parts all 16 ranks give (each its 2 experts' rows; rank 0 with
    the shared expert, the others without) add up to the uncut reference's
    layer."""
    p = _layer_weights()
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, H))
    held = E // RANKS
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(x.reshape(-1, H), p, K, 0)[0]
        parts, rows = [], 0.0
        for rank in range(RANKS):
            first = rank == 0
            out, vs = _layer(held, rank, FS if first else 0).apply(
                {"params": _params(p, rank * held, held, shared=first)}, x,
                mutable=["stats"])
            parts.append(out)
            rows += float(vs["stats"]["moe_rows_held_share"][0])
            assert float(vs["stats"]["moe_dropped_rows"][0]) == 0
        all_held = _layer().apply({"params": _params(p)}, x)
    assert rows == pytest.approx(1.0)       # every routed row is somewhere
    np.testing.assert_allclose(sum(parts).reshape(-1, H), whole, atol=2e-4)
    np.testing.assert_allclose(all_held.reshape(-1, H), whole, atol=2e-4)
