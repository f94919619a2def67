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
from deepspeed_tpu.telemetry.registry import default_registry
from tests.gated_delta_cases import _grads, _inputs, _out_and_grads, _worst

HEADS = "linear_attn/gdn_kernel_heads_per_step"


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
