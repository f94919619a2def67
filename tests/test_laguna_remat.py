"""Laguna under remat, on the CPU at small sizes: remat on and off agree and
keep the router's choice, and a rematted block keeps what its attention
kernels — causal and window — produced. The blocks against the reference:
``tests/test_laguna.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.laguna import LagunaForCausalLM, laguna_tiny
from tests import hlo_text, model_cases


def test_remat_on_and_off_agree_and_keep_the_routers_choice():
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 64)),
                      jnp.int32)

    def model_of(remat):
        return LagunaForCausalLM(laguna_tiny(experts_held=4, remat=remat))

    (want, plain), (got, rematted) = \
        model_cases.gradients_without_and_with_remat(model_of, ids)
    assert "moe_experts" in rematted and "moe_experts" not in plain
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


@pytest.mark.parametrize("base,again", [(None, 0), (("moe_experts",), 5)],
                         ids=["kept", "control"])
def test_rematted_blocks_keep_what_their_attention_kernels_produced(
        base, again, monkeypatch, capsys):
    """Under remat a block keeps ``flash_o`` / ``flash_lse``
    (``models/gpt2.block_remat_policy``): the backward pass is handed them,
    no forward attention kernel — causal or window — sits under
    ``rematted_computation`` in the compiled step, and the gradients are the
    unrematted ones. The control cuts the base set back to the router's
    choice: all five layers' forward kernels are then run again."""
    from deepspeed_tpu.models import gpt2
    if base:
        monkeypatch.setattr(gpt2, "REMAT_BASE_NAMES", base)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, 128)),
                      jnp.int32)
    cfg = laguna_tiny(num_hidden_layers=5, experts_held=4, use_flash=True)
    params = jax.jit(LagunaForCausalLM(cfg).init)(jax.random.PRNGKey(0),
                                                  ids)["params"]

    def loss(remat):
        model = LagunaForCausalLM(dataclasses.replace(cfg, remat=remat))
        return lambda p: model.apply({"params": p}, ids, labels=ids)

    sites, handed, step = hlo_text.remat_report(loss(True), params, capsys)
    assert len(sites) == again, sites
    assert ("named 'flash_lse'" in handed) == (base is None)
    if base is None:
        want = jax.jit(jax.grad(loss(False)))(params)
        for a, b in zip(jax.tree_util.tree_leaves(step.compile()(params)),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)
