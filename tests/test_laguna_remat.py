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


def _tiny(family):
    if family == "laguna":
        return LagunaForCausalLM, laguna_tiny(
            num_hidden_layers=2, experts_held=4, use_flash=True)
    from deepspeed_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                                 nemotron_h_tiny)
    return NemotronHForCausalLM, nemotron_h_tiny(
        hybrid_override_pattern="ME*", num_hidden_layers=3, experts_held=4,
        use_flash=True)


@pytest.mark.parametrize("family,free,kept,again", [
    ("laguna", 10 ** 8, 4, ()),   # moe_scores, attn_proj, qkv, mlp_fc
    # moe_scores, qkv, mlp_fc, mixer_in, scan_states
    ("mamba2", 10 ** 8, 5, ()),
    ("mamba2", 0, 0, ("in_proj", "moe_router", "q_proj"))],
    ids=["laguna-kept", "mamba2-kept", "mamba2-base"])
def test_rematted_blocks_keep_what_the_bytes_fit(family, free, kept, again,
                                                 capsys):
    """With free bytes handed to the trace (``runtime/remat_budget.py``) a
    stack's blocks keep the candidates beside their base names: no projection
    matmul sits under ``rematted_computation`` (the shared expert's gated
    output alone is formed again; JAX's listing of what the backward pass is
    handed shows a kept projection as the ``dot_general`` that wrote it), the
    base names are still handed on, and the gradients are the unrematted
    ones. With none (no engine round the model, a device kind unknown) the
    blocks keep their base names and every projection is run again. The
    Mamba-2 layer's scan (its kernels, in the interpreter) runs its forward
    rule's kernel again under ``rematted_computation`` with none, and not
    where ``scan_states`` is among what is kept."""
    from deepspeed_tpu.telemetry.registry import default_registry
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, 128)),
                      jnp.int32)
    cls, cfg = _tiny(family)
    params = jax.jit(cls(cfg).init)(jax.random.PRNGKey(0), ids)["params"]

    def loss(remat):
        model = cls(dataclasses.replace(cfg, remat=remat))
        return lambda p: model.apply({"params": p}, ids, labels=ids)

    matmuls, handed, step = hlo_text.kept_under_budget(
        loss(True), params, capsys, free)
    if kept:
        assert [m for m in matmuls if "moe_shared" not in m] == [], matmuls
    for part in again:
        assert any(f"/{part}/" in m for m in matmuls), (part, matmuls)
    assert "named 'flash_lse'" in handed
    assert default_registry().peek_gauge("remat/kept_names") == kept
    assert (default_registry().peek_gauge("remat/kept_mb") > 0) == bool(kept)
    if family == "mamba2":
        scans = hlo_text.rematted_scopes(step.as_text(debug_info=True),
                                         hlo_text.FORWARD_SCAN_SCOPES)
        assert len(scans) == (0 if kept else 1), scans
        assert default_registry().peek_gauge("remat/scan_states_kept") \
            == bool(kept)
    if kept:
        want = jax.jit(jax.grad(loss(False)))(params)
        for a, b in zip(jax.tree_util.tree_leaves(step.compile()(params)),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


@pytest.mark.parametrize("policy", [None, "dots_flash_fc_lean"])
def test_no_budget_and_a_named_policy_lower_as_without_the_rule(policy):
    """No free bytes in the trace — a scope that says 0, or none — and the
    blocks' policy is ``save_only_these_names(*REMAT_BASE_NAMES)``: the text
    that policy lowers to. A configuration that NAMES a policy gets it
    joined with the base names whatever the bytes: the same text with 100 MB
    free as with none."""
    from jax.ad_checkpoint import checkpoint_name
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.parallel import mesh as mesh_lib
    stack = dict(rows=128, hidden=64, layers=1, itemsize=4,
                 row_bytes={"qkv": 4 * 64, "mlp_fc": 4 * 64},
                 inflight_row_bytes=12 * 64)

    def text(policy_of, free=None):
        def block(x, w):
            q = checkpoint_name(x @ w, "qkv")
            o = checkpoint_name(jnp.tanh(q) @ w, "flash_o")
            return checkpoint_name(jnp.tanh(o) @ w, "mlp_fc")

        def lowered():
            return jax.jit(jax.grad(lambda x, w: jnp.sum(jax.checkpoint(
                block, policy=policy_of())(x, w)))).lower(
                    jnp.ones((128, 64)), jnp.ones((64, 64))).as_text()
        if free is None:
            return lowered()
        with mesh_lib.layout_pins(None, remat_free_bytes=free):
            return lowered()

    ours = lambda: gpt2.block_remat_policy(policy, **stack)  # noqa: E731
    before = jax.checkpoint_policies.save_only_these_names(
        *gpt2.REMAT_BASE_NAMES)
    if policy:
        before = jax.checkpoint_policies.save_from_both_policies(
            gpt2._remat_policy(policy), before)
    assert text(ours) == text(ours, 0) == text(lambda: before)
    assert (text(ours, 10 ** 8) == text(ours)) == bool(policy)
