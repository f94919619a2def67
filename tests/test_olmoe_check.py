"""OLMoE's check sees an omission: ``judge_train`` of the program's model as
it is passes against the plain reference, and each named omission — in the
configuration, in the routing, in the grouped matmul's backward products —
fails the check it should. The engine's step against the reference:
``tests/test_olmoe.py``.
"""

import contextlib
import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import olmoe as family
from deepspeed_tpu.models import llama
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import grouped_matmul as grouped_matmul_module
from tests.cell_config import config_file

CONFIG = config_file("olmoe-1b-7b-0125-depth1")


def _ids(seed=0, rows=8):
    return np.random.default_rng(seed).integers(
        0, 512, (rows, 128), dtype=np.int32)


# --------------------------------------------- the check sees an omission

@functools.lru_cache(maxsize=None)
def _weights():
    """The TRUE configuration's model, initialised once for every case."""
    model = llama.LlamaForCausalLM(family.model_config(CONFIG, rehearse=True))
    return jax.jit(model.init)(jax.random.PRNGKey(5),
                               jnp.asarray(_ids(1)))["params"]


def _judge(system_config, patch=None):
    """``judge_train`` of the program's model built from ``system_config``
    (its loss and gradients as ``system_step`` forms them: cross-entropy plus
    the sown ``losses``, in the step's precision) against the reference of
    the TRUE configuration, on the same weights."""
    ids, params = _ids(1), _weights()
    device = jax.devices()[0]
    with patch or contextlib.nullcontext():
        system = family.system_step(system_config, params, ids, device, True)
    want_loss, want_gnorm, differences = family.compare(
        CONFIG, params, ids, device, True, system)
    return family.judge_train(
        CONFIG, float(system[0]), differences["system_grad_norm"], want_loss,
        want_gnorm, differences)


class _drop_token_zero:
    """One dropped token: token 0 reaches no expert (its routing weights are
    thrown away), as a full expert buffer would do to it."""

    def __enter__(self):
        self.route = dropless.route

        def route(logits, k, norm):
            w, e, p = self.route(logits, k, norm)
            return w.at[0].set(0.0), e, p
        dropless.route = route
        jax.clear_caches()

    def __exit__(self, *exc):
        dropless.route = self.route
        jax.clear_caches()
        return False


def _fp8(x):
    """Rounded to e4m3's grid (4 exponent bits, 3 of mantissa) under one
    scale a tensor, as an fp8 training path rounds. ``reduce_precision`` and
    not a cast there and back, which XLA may remove as excess precision."""
    scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 224.0
    return (jax.lax.reduce_precision(x.astype(jnp.float32) / scale, 4, 3)
            * scale).astype(x.dtype)


@contextlib.contextmanager
def _backward_fault(kind):
    """The grouped matmul's backward products wrong, its forward untouched:
    ``zero_drhs`` (no expert weight learns), ``fp8_dout`` (the cotangent
    rounded to fp8 before both products)."""
    mb = grouped_matmul_module._mb
    gmm, tgmm = mb.gmm, mb.tgmm

    def gmm_(lhs, rhs, *args, **kw):
        if kw.get("transpose_rhs") and kind == "fp8_dout":      # dlhs
            lhs = _fp8(lhs)
        return gmm(lhs, rhs, *args, **kw)

    def tgmm_(lhs, dout, *args, **kw):                           # drhs
        out = tgmm(lhs, _fp8(dout) if kind == "fp8_dout" else dout,
                   *args, **kw)
        return jnp.zeros_like(out) if kind == "zero_drhs" else out

    mb.gmm, mb.tgmm = gmm_, tgmm_
    try:
        yield
    finally:
        mb.gmm, mb.tgmm = gmm, tgmm


def _with(**over):
    out = copy.deepcopy(CONFIG)
    out.update(over)
    return out


def test_the_program_as_it_is_passes_the_check():
    checks, info = _judge(CONFIG)
    assert all(checks.values()), (checks, info)


@pytest.mark.parametrize("omission,system,patch,fails", [
    ("no z-loss", _with(router_z_loss_coef=0.0), None,
     "first_loss_matches_reference"),
    ("renormalised top-k", _with(norm_topk_prob=True), None,
     "expert_branch_matches_reference"),
    ("one dropped token", CONFIG, _drop_token_zero,
     "expert_branch_matches_reference"),
    ("no QK-norm", _with(qk_norm=False), None,
     "attention_branch_matches_reference"),
    # the backward pass alone: loss, norm, routing and both branches pass
    ("no expert weight gradient", CONFIG,
     lambda: _backward_fault("zero_drhs"),
     "gradients_match_reference_leaf_by_leaf"),
    ("the grouped matmul's cotangent in fp8", CONFIG,
     lambda: _backward_fault("fp8_dout"),
     "gradients_match_reference_leaf_by_leaf"),
], ids=["no-z-loss", "renormalised-top-k", "dropped-token", "no-qk-norm",
        "zero-drhs", "fp8-dout"])
def test_an_omission_fails_the_familys_check(omission, system, patch, fails):
    checks, info = _judge(system, patch() if patch else None)
    assert not checks[fails], (omission, info)
    if "gradient" in fails:
        assert all(v for k, v in checks.items() if k != fails), checks
        assert {"gate", "up", "down"} <= set(
            info["differences"]["gradient_leaves_over"]), info
