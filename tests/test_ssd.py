"""The state-space scan (``ops/ssd.py``): the XLA chunked form and the Pallas
kernels (in the interpreter) against the token-by-token recurrence — values
and the gradient of every input — at chunk boundaries, at a sequence that
is one chunk, at a ragged tail, with B / C shared by a group of heads, with
two heads side by side in a lane block, under strong and weak decay, with
``D`` and a ``dt_bias`` in front of the softplus. Float32."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssd
from deepspeed_tpu.ops.pallas import ssd as kernels


def _inputs(B, S, H, P, G, N, A, seed=0, dt_bias=0.5):
    """x, dt (softplus of a projection plus ``dt_bias``: steps of 0.05-3),
    A = -``A`` for every head, B, C, D: the operands as a Mamba-2 layer
    hands them over."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 2.0 + dt_bias)
    a = -A * (1.0 + 0.1 * jax.random.uniform(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    D = 1.0 + jax.random.normal(ks[5], (H,))
    return x, dt, a, Bm, Cm, D


def _xla(chunk):
    return lambda *a: ssd.ssd_scan_xla(*a, chunk=chunk)


def _kernel(chunk):
    return lambda *a: kernels.ssd_scan_kernel(*a, chunk=chunk,
                                              interpret=True)


FORMS = {"xla": _xla, "kernel": _kernel}
# (B, S, H, P, G, N, chunk): what each case is for
SHAPES = {
    "three_chunks_groups_of_two": (2, 48, 4, 8, 2, 16, 16),
    "one_chunk": (1, 16, 2, 8, 1, 8, 16),
    "ragged_tail": (1, 40, 4, 8, 2, 16, 16),
    "two_heads_a_lane_block": (1, 32, 4, 64, 2, 16, 16),
    "one_group_serves_every_head": (1, 32, 4, 8, 1, 16, 8),
    # granite-4.0-h's ``mamba_n_groups 1``: ONE B, C for all heads, the
    # group cut into head blocks of 8 whose dB / dC parts are summed
    "one_group_of_64_heads_in_8_head_blocks": (1, 32, 64, 8, 1, 16, 16),
    "one_group_of_16_heads_two_a_lane_block": (2, 24, 16, 64, 1, 8, 8),
    "two_groups_of_12_heads_in_blocks_of_6": (1, 16, 24, 8, 2, 8, 8),
}


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@functools.lru_cache(maxsize=None)
def _values_and_gradients(form, chunk):
    """``(w, *args) -> (fn(*args), the gradient of its ``w``-weighted sum in
    each of ``args``)`` as ONE jitted program; ``fn`` the recurrence
    (``form`` None) or a form at a chunk. Cached, so the two decays of a shape
    share the compile."""
    fn = FORMS[form](chunk) if form else ssd.ssd_recurrence

    def weighted(w, *args):
        out = fn(*args)
        return jnp.sum(out * w), out

    @jax.jit
    def run(w, *args):
        (_, out), grads = jax.value_and_grad(
            weighted, argnums=tuple(range(1, 7)), has_aux=True)(w, *args)
        return out, grads
    return run


@functools.lru_cache(maxsize=None)
def _primal(form, chunk):
    """A form's UNDIFFERENTIATED call as its own jitted program: under the
    kernels' ``custom_vjp`` the program of ``_values_and_gradients`` runs
    the forward rule (it keeps the states), never the primal kernel call
    with its own refs and ``out_specs``."""
    return jax.jit(FORMS[form](chunk))


@functools.lru_cache(maxsize=None)
def _expected(shape, A):
    """(inputs, weights, the recurrence's output, its six gradients) of a
    case: the expected side does not depend on the form under test."""
    B, S, H, P, G, N, _ = SHAPES[shape]
    args = _inputs(B, S, H, P, G, N, A)
    w = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, P))
    return (args, w) + _values_and_gradients(None, None)(w, *args)


@pytest.mark.parametrize("A", [1.0, 16.0], ids=["weak_decay", "strong_decay"])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("form", FORMS, ids=str)
def test_values_and_every_gradient_match_the_recurrence(form, shape, A):
    B, S, H, P, G, N, chunk = SHAPES[shape]
    args, w, want, want_grads = _expected(shape, A)
    got = _primal(form, chunk)(*args)
    assert got.shape == want.shape == (B, S, H, P)
    assert _rel(got, want) < 2e-6
    kept, grads = _values_and_gradients(form, chunk)(w, *args)
    assert _rel(kept, want) < 2e-6
    for name, a, b in zip("x dt A B C D".split(), grads, want_grads):
        assert a.shape == b.shape
        assert _rel(a, b) < 1e-4, (name, _rel(a, b))


@pytest.mark.parametrize("form", FORMS, ids=str)
def test_a_token_sees_the_chunks_before_it_and_none_after(form):
    """Changing a token in the LAST chunk leaves every earlier output as it
    was; changing one in the FIRST moves outputs two chunks on (weak decay:
    the state carries)."""
    args = _inputs(1, 48, 2, 8, 1, 8, 0.05, seed=3)
    scan = FORMS[form](16)
    base = scan(*args)
    x = args[0]
    late = scan(x.at[0, 40].add(1.0), *args[1:])
    np.testing.assert_array_equal(late[0, :40], base[0, :40])
    early = scan(x.at[0, 3].add(1.0), *args[1:])
    assert float(jnp.abs(early[0, 40:] - base[0, 40:]).max()) > 1e-3


def test_strong_decay_neither_overflows_nor_leaks_across_chunks():
    """A = -16 at steps near 6: exp(dt A) underflows to 0 inside a chunk.
    Every exponent the chunked forms take is <= 0, so nothing overflows,
    and the outputs are the skip and the token's own write alone."""
    x, dt, a, Bm, Cm, D = _inputs(1, 32, 2, 8, 1, 8, 16.0, dt_bias=8.0)
    for form in FORMS.values():
        got = form(16)(x, dt, a, Bm, Cm, D)
        assert bool(jnp.all(jnp.isfinite(got)))
        own = jnp.einsum("bsgn,bsgn->bsg", Cm, Bm)[..., None] \
            * (dt[..., None] * x) + D[:, None] * x
        np.testing.assert_allclose(got, own, atol=1e-4)


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "not_kept"])
def test_a_policy_that_keeps_the_scans_name_runs_the_forward_kernel_once(
        kept):
    """``scan_states`` (``ops/pallas/scan_residuals.py``) on y and the
    states where the forward rule returns them. A block whose policy keeps
    the name runs the forward rule's kernel in its forward pass and holds
    no scan forward call under ``rematted_computation``; one that does not
    lowers as if the name did not exist — the primal call (y alone), the
    forward rule under the recomputation, the backward kernel. Gradients
    are the unrematted ones either way."""
    from deepspeed_tpu.ops.pallas.scan_residuals import SCAN_NAME
    from tests import hlo_text
    B, S, H, P, G, N, chunk = SHAPES["three_chunks_groups_of_two"]
    args = _inputs(B, S, H, P, G, N, 1.0)
    scan = _kernel(chunk)
    names = jax.checkpoint_policies.save_only_these_names

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=tuple(range(6))))

    def rematted(policy):
        return grads(jax.checkpoint(scan, policy=policy))

    ours = rematted(names(SCAN_NAME) if kept else names("flash_o"))
    again, outs = hlo_text.scan_forward_calls(ours, *args)
    if kept:
        assert again == [] and outs == [2, 6], (again, outs)
    else:
        assert len(again) == 1 and outs == [1, 2, 6], (again, outs)
        nothing = rematted(jax.checkpoint_policies.nothing_saveable)
        assert ours.lower(*args).as_text() == nothing.lower(*args).as_text()
    for a, b in zip(ours(*args), grads(scan)(*args)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_dispatcher_takes_the_kernel_off_the_tpu_and_gauges_it():
    """``ssd_scan`` off a TPU runs the kernels in the interpreter at any
    shape whose heads divide into the groups; ``takes_kernel`` says what a
    TPU takes: whole-vreg lane blocks (a head of 128, or two of 64), a
    state of whole vregs, a chunk of 128."""
    from deepspeed_tpu.telemetry.registry import default_registry
    args = _inputs(1, 32, 4, 8, 2, 16, 1.0)
    got = ssd.ssd_scan(*args, chunk=16)
    assert default_registry().peek_gauge("ssm/ssd_kernel_heads_per_step") == 2
    np.testing.assert_allclose(got, ssd.ssd_recurrence(*args), atol=2e-5)
    ssd.ssd_scan_xla(*args, chunk=16)
    assert default_registry().peek_gauge("ssm/ssd_kernel_heads_per_step") == 0
    takes = kernels.takes_kernel
    assert takes(64, 64, 8, 128, 128, tpu=True)         # the published layer
    assert takes(64, 64, 1, 128, 128, tpu=True)         # granite: one group
    assert takes(8, 128, 8, 128, 128, tpu=True)
    assert not takes(64, 64, 64, 128, 128, tpu=True)    # one head of 64
    assert not takes(64, 64, 8, 64, 128, tpu=True)      # half a vreg of state
    assert not takes(64, 64, 8, 128, 64, tpu=True)
    assert not takes(64, 32, 8, 128, 128, tpu=True)
    assert takes(4, 8, 2, 16, 16, tpu=False)
    assert not takes(6, 8, 4, 16, 16, tpu=False)        # 6 heads, 4 groups


def test_the_plan_packs_two_heads_of_64_and_keeps_a_state_a_chunk():
    plan = kernels._plan_for(1, 16384, 64, 8, 64, 128, 128)
    assert (plan.hg, plan.pack, plan.W, plan.blocks) == (8, 2, 128, 4)
    assert (plan.cb, plan.nb) == (8, 16)
    kept = kernels._states_shape(plan)
    assert kept.shape == (1, 32, 128, 128, 128) and kept.dtype == jnp.float32
    assert np.prod(kept.shape) * 4 == 268_435_456      # 268 MB a layer
    assert kernels._plan_for(1, 40, 4, 2, 8, 16, 16).pack == 1
    # one head block a group: the grid and the blocks of before the head
    # blocks existed
    assert (plan.head_blocks, plan.programs) == (1, 8)


@pytest.mark.parametrize("heads,want", [
    (64, (8, 8, 8)), (16, (8, 2, 2)), (8, (8, 1, 1)), (24, (8, 3, 3)),
    (12, (6, 2, 2)), (14, (2, 7, 7))], ids=lambda v: str(v))
def test_a_wide_group_is_cut_into_head_blocks(heads, want):
    """One group of ``heads`` heads of 64 (two a lane block): (heads a grid
    step, head blocks a group, programs a batch row). granite-4.0-h-micro
    is the first row: the operand blocks of Nemotron's 8 groups of 8."""
    plan = kernels._plan_for(1, 16384, heads, 1, 64, 128, 128)
    assert (plan.hg, plan.head_blocks, plan.programs) == want
    assert plan.hg % plan.pack == 0 and plan.hg <= 8
    assert kernels._states_shape(plan).shape == (
        1, heads // 2, 128, 128, 128)


def test_the_head_blocks_of_one_group_are_gauged():
    from deepspeed_tpu.telemetry.registry import default_registry
    args = _inputs(1, 16, 16, 8, 1, 8, 1.0)
    got = ssd.ssd_scan(*args, chunk=8)
    peek = default_registry().peek_gauge
    assert peek("ssm/ssd_kernel_heads_per_step") == 8
    assert peek("ssm/ssd_head_blocks_per_group") == 2
    np.testing.assert_allclose(got, ssd.ssd_recurrence(*args), atol=2e-5)
