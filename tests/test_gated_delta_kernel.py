"""The gated delta rule's Pallas kernels (``ops/pallas/gated_delta.py``) on
the CPU, in the interpreter: against the recurrence as written, forward and
for all five gradients, in float32 at the limits the XLA chunked form is held
to (``tests/test_qwen3_next_delta_rule.py``). In bf16, where keys are alike,
under ``jax.checkpoint``, and which head sizes take the kernels:
``tests/test_gated_delta_kernel_forms.py``.
"""

import numpy as np
import pytest

from deepspeed_tpu.ops.gated_delta import (CHUNK, gated_delta_recurrence,
                                           gated_delta_rule)
from deepspeed_tpu.ops.pallas import gated_delta as kernels
from deepspeed_tpu.telemetry.registry import default_registry
from tests.gated_delta_cases import _grads, _inputs, _out_and_grads, _worst

HEADS = "linear_attn/gdn_kernel_heads_per_step"
LANES = "linear_attn/gdn_lane_overcompute"


@pytest.mark.parametrize("D", [16, 128])
@pytest.mark.parametrize("rep", [1, 2])
@pytest.mark.parametrize("S", [2 * CHUNK, 3 * CHUNK, 4 * CHUNK, 100, 37])
def test_kernels_are_the_recurrence_forward_and_backward(S, rep, D):
    """Float32 operands, the XLA form's limits. A grid step takes two key
    heads and the value heads they serve (2 at rep 1, 4 at rep 2); 3 chunks
    walk one chunk a grid step, 4 chunks four; 100 and 37 tokens pad a last
    chunk."""
    args = _inputs(S, rep, D, B=1 if D == 128 else 2)
    got = gated_delta_rule(*args)
    assert default_registry().peek_gauge(HEADS) == 2 * rep
    want, want_grads = _out_and_grads(gated_delta_recurrence, args)
    assert got.shape == want.shape and got.dtype == args[2].dtype
    np.testing.assert_allclose(got, want, atol=5e-6)
    worst = _worst(_grads(gated_delta_rule, args), want_grads)
    assert max(worst.values()) < 2e-5, worst


@pytest.mark.parametrize("Dk,Dv,Hk,S,lanes", [
    (96, 192, 6, 2 * CHUNK, True), (96, 192, 30, CHUNK, True),
    (24, 48, 6, 100, False), (24, 48, 30, CHUNK, False)])
def test_heads_off_the_lane_grid_at_beta_below_two(Dk, Dv, Hk, S, lanes):
    """A value head twice its key head, as many value heads as key heads
    (rep 1), neither width on the 128-lane grid, beta in (0, 2): 96 x 192
    runs zero-padded to 128 x 256 (``lane_heads``: whole tiles add a third;
    the gauge prices them at 1.77 x) and o comes back 192 wide, 24 x 48 as
    it comes in the interpreter (on a TPU: the XLA form); forward and all
    five gradients against the recurrence, at the limits of the aligned
    heads; two key heads a grid step, 3 or 15 programs."""
    args = _inputs(S, 1, Dk, B=1, Hk=Hk, Dv=Dv, beta_scale=2.0)
    assert float(args[4].max()) > 1.8
    assert kernels.lane_heads(Dk, Dv) == ((128, 256) if lanes else (Dk, Dv))
    assert kernels.takes_kernel(Dk, Dv, True) is lanes
    got = gated_delta_rule(*args)
    gauge = default_registry().peek_gauge
    assert gauge(HEADS) == 2
    assert gauge(LANES) == pytest.approx(
        (2 * 128 + 256 + 128 * 256) / (2 * 96 + 192 + 96 * 192)
        if lanes else 1.0)
    want, want_grads = _out_and_grads(gated_delta_recurrence, args)
    assert got.shape == want.shape == (1, S, Hk, Dv)
    np.testing.assert_allclose(got, want, atol=5e-6)
    worst = _worst(_grads(gated_delta_rule, args), want_grads)
    assert max(worst.values()) < 2e-5, worst


@pytest.mark.parametrize("beta,limit", [(1.9, 1e-3), (1.999, 4e-2)])
def test_alike_keys_at_beta_near_two_are_held_as_far_as_three_passes_go(
        beta, limit):
    """Every key the same, no decay, beta near 2: the state's component
    along the key changes sign every token (eigenvalue 1 - beta near -1)
    and ``T = (I + L)^-1`` has entries that alternate near +-2 where beta 1
    gives a bidiagonal: the doubling rounds' products cancel, and their
    float32 as three bf16 passes (``_dot_x3``, 2^-16) is what limits the
    kernels here — MEASURED, not copied from beta 0.999's 2e-5: the worst
    element over the largest reads 4e-4 at beta 1.9, 5.6e-3 at 1.99 and
    1.6e-2 at 1.999 where the XLA form's six passes read 1e-5 ... 2.4e-5
    (PERF.md Findings PR 68; keys within 5 % of each other under a slow
    decay read 7e-5 at 1.999: the corner is the exact one)."""
    import jax.numpy as jnp
    q, k, v, g, b = _inputs(2 * CHUNK, 1, 96, B=1, Dv=192)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (q, k, v, jnp.zeros_like(g), jnp.full_like(b, beta))
    want, want_grads = _out_and_grads(gated_delta_recurrence, args)
    got = gated_delta_rule(*args)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < limit
    # the gradients, worst element over the largest: 0.1 at beta 1.999
    worst = _worst(_grads(gated_delta_rule, args), want_grads)
    assert max(worst.values()) < 4 * limit, worst


def test_a_caller_that_padded_its_heads_names_them_for_the_gauge():
    """``heads=``: operands that came zero-padded (the layer's one
    re-layout) run as they are and the gauge is priced against the layer's
    own widths; the outputs are those of the op's own padding."""
    import jax.numpy as jnp
    q, k, v, g, beta = _inputs(CHUNK, 1, 96, B=1, Dv=192, beta_scale=2.0)
    want = gated_delta_rule(q, k, v, g, beta)
    pad = lambda t, n: jnp.pad(t, ((0, 0),) * 3 + ((0, n),))  # noqa: E731
    got = gated_delta_rule(pad(q, 32), pad(k, 32), pad(v, 64), g, beta,
                           heads=(96, 192))
    assert default_registry().peek_gauge(LANES) > 1.7
    np.testing.assert_array_equal(got[..., :192], want)
    assert not np.any(got[..., 192:])
