"""Laguna's layers apart, on the CPU at small sizes: the expert layer's eight
shares adding up to the uncut reference's layer with the shared expert counted
once, the routed scale on the renormalised weights, and the window layers on
the window kernels where flash is on (the kernels in the interpreter). The
model against the reference: ``tests/test_laguna.py``; on the engine:
``tests/test_laguna_engine.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna as ref
from deepspeed_tpu.models.laguna import LagunaForCausalLM, laguna_tiny
from deepspeed_tpu.moe.dropless import DroplessMoE
from tests import hlo_text


H, E, K, F, RANKS = 32, 32, 4, 16, 8
SCALE = 2.5


def _layer_weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda k, *s: 0.3 * jax.random.normal(k, s)  # noqa: E731
    return {"router": n(ks[0], H, E), "gate": n(ks[1], E, H, F),
            "up": n(ks[2], E, H, F), "down": n(ks[3], E, F, H),
            "shared_gate": n(ks[4], H, F), "shared_up": n(ks[5], H, F),
            "shared_down": n(ks[6], F, H),
            "shared_expert_gate": n(ks[7], H, 1)}


def _share(p, x, rank, held=E // RANKS, shared=False, scale=SCALE):
    layer = DroplessMoE(E, K, F, norm_topk_prob=True, dtype=jnp.float32,
                        experts_held=held, expert_share=rank,
                        shared_d_ff=F if shared else 0, routed_scale=scale)
    lo = rank * held
    params = {"router": p["router"], "gate_proj": p["gate"][lo:lo + held],
              "up_proj": p["up"][lo:lo + held],
              "down_proj": p["down"][lo:lo + held]}
    if shared:
        params.update({f"shared_{n}_proj": p[f"shared_{n}"]
                       for n in ("gate", "up", "down")},
                      shared_expert_gate=p["shared_expert_gate"])
    out, vs = layer.apply({"params": params}, x, mutable=["stats"])
    return out, {k: float(v[0]) for k, v in vs["stats"].items()}


def test_the_eight_shares_and_the_shared_expert_once_are_the_whole_layer():
    """The parts all 8 ranks give (each its 4 experts' rows, scaled by 2.5),
    the shared expert counted ONCE, add up to the uncut reference's layer."""
    p = _layer_weights()
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, H))
    h = x.reshape(-1, H)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(h, p, K, 0, SCALE)[0]
        shared = ref.moe(h, p, K, 0, 0.0)[0]        # routed weights x 0
        parts, held = [], 0.0
        for rank in range(RANKS):
            out, stats = _share(p, x, rank)
            parts.append(out)
            held += stats["moe_rows_held_share"]
            assert stats["moe_dropped_rows"] == 0
        with_shared, _ = _share(p, x, 3, shared=True)
    assert held == pytest.approx(1.0)       # every routed row is somewhere
    np.testing.assert_allclose(sum(parts).reshape(-1, H) + shared, whole,
                               atol=5e-5)
    # a rank's own output carries the shared expert in full, unscaled
    np.testing.assert_allclose(with_shared.reshape(-1, H),
                               parts[3].reshape(-1, H) + shared, atol=5e-5)
    # and the factor is on the routed part alone: 2.5 x the part at 1.0
    with jax.default_matmul_precision("highest"):
        plain, _ = _share(p, x, 3, scale=1.0)
    np.testing.assert_allclose(parts[3], SCALE * plain, atol=5e-5)


@pytest.mark.parametrize("pin", [False, True], ids=["own_choice", "pinned"])
def test_routed_scale_multiplies_the_renormalised_weights(pin):
    from deepspeed_tpu.moe.dropless import route
    logits = jax.random.normal(jax.random.PRNGKey(0), (12, E))
    w1, e1, p1 = route(logits, K, True, pin_choice=pin)
    w2, e2, p2 = route(logits, K, True, pin_choice=pin, routed_scale=SCALE)
    np.testing.assert_array_equal(e1, e2)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_allclose(w2, SCALE * w1, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w2, axis=1), SCALE, rtol=1e-5)
    # the default leaves the traced program as it was: no multiply
    text = lambda **kw: str(jax.make_jaxpr(  # noqa: E731
        lambda x: route(x, K, True, **kw)[0])(logits))
    assert text() == text(routed_scale=1.0) != text(routed_scale=SCALE)


def test_the_window_layers_run_the_window_kernels_where_flash_is_on():
    """``use_flash=True`` (the TPU's choice) sends a sliding layer through
    the window kernels — here in the interpreter — and a full layer through
    the causal ones; the outputs are the reference path's."""
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, 128)),
                      jnp.int32)
    cfg = laguna_tiny(num_hidden_layers=5, experts_held=4)
    params = jax.jit(LagunaForCausalLM(cfg).init)(jax.random.PRNGKey(0),
                                                  ids)["params"]

    def run(use_flash):
        model = LagunaForCausalLM(dataclasses.replace(cfg,
                                                      use_flash=use_flash))
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
