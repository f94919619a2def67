"""Qwen3-Next under remat, on the CPU at small sizes: a rematted block keeps
the router's choice whatever share of the experts is held, and keeps what its
attention kernel produced. The blocks against the reference:
``tests/test_qwen3_next.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the XLA chunked form, whatever the backend (tests/test_qwen3_next.py)
from deepspeed_tpu.ops.gated_delta import \
    gated_delta_rule_xla as gated_delta_rule


@pytest.mark.parametrize("held", [0, 4], ids=["all_experts", "a_share"])
def test_remat_keeps_the_routers_choice_whatever_is_held(held):
    """A rematted block recomputes its forward pass in the backward pass; the
    policy saves the router's choice under the name ``moe_experts``, and the
    expert layer carries that name because the MODEL recomputes, whether or
    not it holds a share: the gradients are those of the step without
    remat, and the name is among what the backward pass is handed."""
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                                 qwen3_next_tiny)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 64)),
                      jnp.int32)

    def grads(remat):
        model = Qwen3NextForCausalLM(qwen3_next_tiny(
            num_hidden_layers=4, experts_held=held, remat=remat))
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        fn = jax.grad(lambda p: model.apply({"params": p}, ids, labels=ids))
        return fn(params), str(jax.make_jaxpr(fn)(params))

    (want, plain), (got, rematted) = grads(False), grads(True)
    assert "moe_experts" in rematted and "moe_experts" not in plain
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


@pytest.mark.parametrize("base,again", [(None, 0), (("moe_experts",), 1)],
                         ids=["kept", "control"])
def test_rematted_blocks_keep_what_their_attention_kernel_produced(
        base, again, monkeypatch, capsys):
    """As ``tests/test_laguna_remat.py``'s test of the same name: under remat the
    period's attention layer keeps ``flash_o`` / ``flash_lse``, its forward
    kernel is not under ``rematted_computation`` in the compiled step, and
    the gradients are the unrematted ones; with the base set cut back to
    the router's choice it is."""
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                                 qwen3_next_tiny)
    from tests import hlo_text
    if base:
        monkeypatch.setattr(gpt2, "REMAT_BASE_NAMES", base)
    # the delta rule's form is not what is asked about: its kernels in the
    # interpreter take most of a minute to lower
    monkeypatch.setattr("deepspeed_tpu.models.qwen3_next.gated_delta_rule",
                        gated_delta_rule)      # this file's: the XLA form
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 64)),
                      jnp.int32)

    def loss(remat):
        model = Qwen3NextForCausalLM(qwen3_next_tiny(
            num_hidden_layers=4, experts_held=4, use_flash=True,
            remat=remat))
        return lambda p: model.apply({"params": p}, ids, labels=ids)

    params = Qwen3NextForCausalLM(qwen3_next_tiny(
        num_hidden_layers=4, experts_held=4)).init(
        jax.random.PRNGKey(0), ids)["params"]
    sites, handed, step = hlo_text.remat_report(loss(True), params, capsys)
    assert len(sites) == again, sites
    # the layer scan hands its blocks' residuals on stacked, their names
    # gone: lse is [periods, B * H, S / 64, 1, 64] (blocks of 64 on the CPU)
    assert ("f32[1,4,1,1,64] output of scan" in handed) == (base is None)
    if base is None:
        want = jax.jit(jax.grad(loss(False)))(params)
        for a, b in zip(jax.tree_util.tree_leaves(step.compile()(params)),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)
