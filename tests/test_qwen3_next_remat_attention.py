"""Qwen3-Next under remat, on the CPU at small sizes: a rematted block keeps
what its attention kernel produced. The router's choice under remat:
``tests/test_qwen3_next_remat.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the XLA chunked form, whatever the backend
# (tests/test_qwen3_next_delta_rule.py)
from deepspeed_tpu.ops.gated_delta import \
    gated_delta_rule_xla as gated_delta_rule
from tests import hlo_text


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

    params = jax.jit(Qwen3NextForCausalLM(qwen3_next_tiny(
        num_hidden_layers=4, experts_held=4)).init)(
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
