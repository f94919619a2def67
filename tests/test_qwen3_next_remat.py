"""Qwen3-Next under remat, on the CPU at small sizes: a rematted block keeps
the router's choice whatever share of the experts is held. What its attention
kernel produced: ``tests/test_qwen3_next_remat_attention.py``; the blocks
against the reference: ``tests/test_qwen3_next.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the XLA chunked form, whatever the backend
# (tests/test_qwen3_next_delta_rule.py)
from deepspeed_tpu.ops.gated_delta import \
    gated_delta_rule_xla as gated_delta_rule
from tests import model_cases


@pytest.mark.parametrize("held", [0, 4], ids=["all_experts", "a_share"])
def test_remat_keeps_the_routers_choice_whatever_is_held(held, monkeypatch):
    """A rematted block recomputes its forward pass in the backward pass; the
    policy saves the router's choice under the name ``moe_experts``, and the
    expert layer carries that name because the MODEL recomputes, whether or
    not it holds a share: the gradients are those of the step without
    remat, and the name is among what the backward pass is handed."""
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                                 qwen3_next_tiny)
    # the router's choice is what is asked about, not the delta rule's form
    # (as in the test below): the XLA form, and each model ONE program
    monkeypatch.setattr("deepspeed_tpu.models.qwen3_next.gated_delta_rule",
                        gated_delta_rule)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 64)),
                      jnp.int32)

    def model_of(remat):
        return Qwen3NextForCausalLM(qwen3_next_tiny(
            num_hidden_layers=4, experts_held=held, remat=remat))

    (want, plain), (got, rematted) = \
        model_cases.gradients_without_and_with_remat(model_of, ids)
    assert "moe_experts" in rematted and "moe_experts" not in plain
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)
