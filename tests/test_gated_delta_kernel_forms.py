"""The gated delta rule's Pallas kernels (``ops/pallas/gated_delta.py``) on
the CPU, in the interpreter, beside ``tests/test_gated_delta_kernel.py``'s
float32 parity: in bf16 against the XLA form; where keys are alike; under
``jax.checkpoint`` (a block's remat runs the primal call, the forward rule
and the backward kernel; one whose policy keeps ``scan_states`` the forward
rule's kernel once, in the forward pass); and the rule that says which head
sizes take the kernels, with the gauges that say which form took a call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.gated_delta import (CHUNK, gated_delta_recurrence,
                                           gated_delta_rule,
                                           gated_delta_rule_xla)
from deepspeed_tpu.ops.pallas import gated_delta as kernels
from deepspeed_tpu.telemetry.registry import default_registry
from tests import hlo_text
from tests.gated_delta_cases import _grads, _inputs

HEADS = "linear_attn/gdn_kernel_heads_per_step"
KEPT = "linear_attn/gdn_states_kept_every"


@pytest.mark.parametrize("D,rep", [(16, 2), (128, 2), (16, 1)])
def test_kernels_in_bf16_round_as_the_xla_form_does(D, rep):
    """bf16 operands: both forms stand as far from the float32 recurrence,
    and as near each other as two bf16 orders of operation do."""
    args = _inputs(2 * CHUNK, rep, D, B=1, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    want = gated_delta_recurrence(*exact)
    got, xla = gated_delta_rule(*args), gated_delta_rule_xla(*args)
    assert got.dtype == jnp.bfloat16

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert rel(got, want) < 1.2 * rel(xla, want) + 1e-3 < 0.02
    assert rel(got, xla) < 0.01
    g_got, g_xla = _grads(gated_delta_rule, args), _grads(
        gated_delta_rule_xla, args)
    g_want = _grads(gated_delta_recurrence, exact)
    for name, a, b, c in zip("q k v g beta".split(), g_got, g_xla, g_want):
        assert a.dtype == c.dtype or name in "qkv", name
        assert rel(a, c) < 1.2 * rel(b, c) + 2e-3 < 0.03, name


def test_kernels_without_writes_read_nothing_and_keys_alike_are_stable():
    q, k, v, g, beta = _inputs(2 * CHUNK)
    assert not np.any(gated_delta_rule(q, k, v, g, jnp.zeros_like(beta)))
    # every key the same, no decay, beta near one: a Neumann series of L
    # would overflow float32 here; substitution is exact
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.zeros_like(g), jnp.full_like(beta, 0.999)
    np.testing.assert_allclose(gated_delta_rule(q, k, v, g, beta),
                               gated_delta_recurrence(q, k, v, g, beta),
                               atol=2e-5)


@pytest.mark.parametrize("sub", [8, 64])
def test_the_inverse_is_the_same_at_every_split_of_substitution_and_rounds(
        monkeypatch, sub):
    """``_SUB`` moves work between the VPU's substitution and the block
    rounds' matmuls: 8 leaves three rounds, 64 none."""
    lower = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (CHUNK, CHUNK)),
                     -1) * 0.3
    monkeypatch.setattr(kernels, "_SUB", sub)
    inv = kernels._unit_lower_inverse(lower)
    np.testing.assert_allclose(inv @ (jnp.eye(CHUNK) + lower), jnp.eye(CHUNK),
                               atol=1e-4)


def test_primal_forward_rule_and_backward_agree_under_checkpoint():
    """``jax.checkpoint`` round the rule, as the block's remat is: the
    forward pass, the recomputation and the backward kernel give the
    gradients of the plain call, and the outputs of the primal call."""
    args = _inputs(3 * CHUNK)
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(3.0 * fn(*a))))  # noqa: E731
    plain = jax.value_and_grad(loss(gated_delta_rule),
                               argnums=(0, 1, 2, 3, 4))(*args)
    remat = jax.jit(jax.value_and_grad(
        loss(jax.checkpoint(gated_delta_rule)), argnums=(0, 1, 2, 3, 4)))
    again = remat(*args)
    np.testing.assert_allclose(again[0], plain[0], rtol=1e-6)
    for a, b in zip(again[1], plain[1]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # the differentiated program holds the kernels of all three: the primal
    # call (the forward pass: o only), the forward rule (the recomputation:
    # o, the states and the inverses) and the backward kernel
    calls = [eqn for eqn in _eqns(jax.make_jaxpr(remat)(*args).jaxpr)
             if eqn.primitive.name == "pallas_call"]
    outs = sorted(len(eqn.outvars) for eqn in calls)
    assert outs == [1, 3, 5], outs


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "not_kept"])
def test_a_policy_that_keeps_the_scans_name_runs_the_forward_kernel_once(
        kept):
    """``scan_states`` (``ops/pallas/scan_residuals.py``) on o, the states
    and ``T`` where the forward rule returns them. A block whose policy keeps
    the name runs the forward rule's kernel in its forward pass and holds
    no scan forward call under ``rematted_computation``; one that does not
    lowers as if the name did not exist — the primal call (o alone), the
    forward rule under the recomputation, the backward kernel. Gradients
    are the unrematted ones either way."""
    from deepspeed_tpu.ops.pallas.scan_residuals import SCAN_NAME
    args = _inputs(3 * CHUNK)
    names = jax.checkpoint_policies.save_only_these_names

    def grads(policy):
        block = jax.checkpoint(gated_delta_rule, policy=policy)
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(3.0 * block(*a))),
            argnums=(0, 1, 2, 3, 4)))

    ours = grads(names(SCAN_NAME) if kept else names("flash_o"))
    again, outs = hlo_text.scan_forward_calls(ours, *args)
    if kept:
        assert again == [] and outs == [3, 5], (again, outs)
    else:
        assert len(again) == 1 and outs == [1, 3, 5], (again, outs)
        nothing = grads(jax.checkpoint_policies.nothing_saveable)
        assert ours.lower(*args).as_text() == nothing.lower(*args).as_text()
    for a, b in zip(ours(*args), _grads(gated_delta_rule, args)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("D,tpu,kernel", [
    (16, True, False), (64, True, False), (128, True, True),
    (256, True, True), (16, False, True), (64, False, True)])
def test_which_head_sizes_take_the_kernels(D, tpu, kernel):
    assert kernels.takes_kernel(D, D, tpu) is kernel
    # both head sizes must be lane-aligned
    assert kernels.takes_kernel(D, 128, tpu) is kernel
    assert kernels.takes_kernel(128, D, tpu) is kernel


@pytest.mark.parametrize("Dk,Dv,lanes,takes", [
    (96, 192, (128, 256), True),      # whole tiles add a third: padded
    (192, 96, (256, 128), True), (128, 192, (128, 256), True),
    (64, 64, (64, 64), False),        # ... would double: the XLA form
    (24, 48, (24, 48), False), (160, 128, (160, 128), False),
    (128, 128, (128, 128), True)])
def test_heads_whole_tiles_add_a_third_to_are_padded_and_logged_once(
        Dk, Dv, lanes, takes, caplog):
    """``lane_heads``: a size is rounded up to whole 128-lane tiles where
    that adds at most a third of its lanes; a head the rule leaves off the
    grid is refused on a TPU with ONE log line naming the size."""
    import logging
    from deepspeed_tpu.utils.logging import logger
    assert kernels.lane_heads(Dk, Dv) == lanes
    kernels._refused.discard((Dk, Dv))
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            assert kernels.takes_kernel(Dk, Dv, True) is takes
            assert kernels.takes_kernel(Dk, Dv, True) is takes
            assert kernels.takes_kernel(Dk, Dv, False)
    finally:
        logger.removeHandler(caplog.handler)
    said = [r.getMessage() for r in caplog.records
            if "the XLA form" in r.getMessage()]
    assert len(said) == (0 if takes else 1), said
    for name, d in (("Dk", Dk), ("Dv", Dv)):
        assert all((f"{name} {d}" in line) == bool(d % 128)
                   for line in said), said


def test_gauges_say_which_form_took_the_call(monkeypatch):
    args = _inputs(CHUNK, B=1)
    gauge = default_registry().peek_gauge
    want = gated_delta_rule(*args)                 # the interpreter: kernels
    assert gauge(HEADS) == 4 and gauge(KEPT) == 1
    # a TPU backend and heads of 16: the XLA form
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = gated_delta_rule(*args)
    assert gauge(HEADS) == 0 and gauge(KEPT) == 8
    np.testing.assert_allclose(got, want, atol=5e-6)
    monkeypatch.undo()
    # a key head that serves four value heads goes alone
    gated_delta_rule(*_inputs(CHUNK, rep=4, B=1, Hk=3))
    assert gauge(HEADS) == 4 and gauge(KEPT) == 1
