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
    the router's choice it is. The "kept" case runs with free bytes handed
    to the trace (``runtime/remat_budget.py``; the base set alone:
    ``tests/test_laguna_remat.py``): the period — three Gated DeltaNet
    layers, one gated attention layer — then keeps all six candidates its
    layers carry (``scan_states`` among them: a name of the delta rule's
    KERNELS, which the XLA form this test runs does not carry —
    ``tests/test_gated_delta_kernel_forms.py``), and no projection matmul (DeltaNet's two, the attention
    layer's with its gate) sits under ``rematted_computation``; the delta
    rule's own preparation and the shared expert's gated output alone are
    formed again."""
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
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.telemetry.registry import default_registry
    with mesh_lib.layout_pins(None, remat_free_bytes=0 if base else 10 ** 8):
        sites, handed, step = hlo_text.remat_report(loss(True), params,
                                                    capsys)
    assert len(sites) == again, sites
    matmuls = hlo_text.rematted_matmuls(step.as_text(debug_info=True))
    if base is None:
        assert [m for m in matmuls if "moe_shared" not in m
                and "gdn_scan" not in m] == [], matmuls
        # moe_scores, attn_proj, qkv, mlp_fc, mixer_in, scan_states
        assert default_registry().peek_gauge("remat/kept_names") == 6
    else:
        for part in ("in_proj_qkvz", "in_proj_ba", "q_proj", "out_proj"):
            assert any(f"/{part}/" in m for m in matmuls), (part, matmuls)
    # the layer scan hands its blocks' residuals on stacked, their names
    # gone: lse is [periods, B * H, S / 64, 1, 64] (blocks of 64 on the CPU)
    assert ("f32[1,4,1,1,64] output of scan" in handed) == (base is None)
    if base is None:
        want = jax.jit(jax.grad(loss(False)))(params)
        for a, b in zip(jax.tree_util.tree_leaves(step.compile()(params)),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)

