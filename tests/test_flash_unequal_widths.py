"""The chunked flash kernels at a q·k width that is not the value width
(latent attention), in the interpreter (one kernel family a file:
``tests/test_flash_attention.py``)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.attention import from_head_major, reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from tests.hlo_text import pallas_grids
from tests.flash_cases import _qkv


# ------------------ a q·k width that is not the value width (latent attention)

@pytest.mark.parametrize("H,Hkv,S,D,Dv,causal,blocks,chunk", [
    (2, 2, 128, 192, 128, True, (64, 64), 128),   # the published widths
    (2, 2, 128, 192, 128, True, (64, 64), 64),    # block == chunk
    (3, 3, 256, 48, 32, True, (64, 64), 128),     # small, 1.5 x
    (3, 3, 256, 48, 32, True, (64, 32), None),    # the entry's own chunk
    (2, 2, 128, 48, 32, False, (32, 64), 64),     # nothing masked
    (4, 2, 128, 48, 32, True, (64, 64), 128),     # grouped keys and values
    (2, 2, 128, 32, 48, True, (64, 64), 64),      # values the wider
    (1, 1, 48, 48, 32, True, (None, None), None),  # one block spans S
], ids=lambda v: str(v))
def test_chunked_kernels_take_unequal_qk_and_value_widths(H, Hkv, S, D, Dv,
                                                          causal, blocks,
                                                          chunk):
    """The chunked forward and backward kernels with q and k ``D`` wide and v
    ``Dv`` wide against the reference (scale 1 / sqrt(D)): the output and dv
    are ``Dv`` wide, dq and dk ``D`` wide; every call is the chunked
    family's whatever S."""
    q, k, _ = _qkv((1, H, S, D), seed=D + S)
    k = k[:, :Hkv]
    v = _qkv((1, Hkv, S, Dv), seed=Dv)[2]

    def both(attend):
        return (attend(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(jnp.sin(attend(*a))), argnums=(0, 1, 2))(
            q, k, v)

    flash = functools.partial(flash_attention, causal=causal,
                              block_q=blocks[0], block_k=blocks[1],
                              chunk=chunk, interpret=True)
    got = both(flash)
    want = both(functools.partial(reference_attention, causal=causal))
    assert got[0].shape == (1, H, S, Dv) and got[1].shape == q.shape \
        and got[2].shape == k.shape and got[3].shape == v.shape
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        fwd = name == "out"
        np.testing.assert_allclose(a, b, rtol=2e-4 if fwd else 5e-3,
                                   atol=2e-5 if fwd else 5e-4, err_msg=name)
    # forward and backward: two calls on the chunked family's (B*H, pairs)
    # grid
    grids = pallas_grids(jax.make_jaxpr(jax.grad(
        lambda *a: flash(*a).sum(), argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert len(grids) == 2 and all(len(g) == 2 and g[0] == H for g in grids)


def test_unequal_widths_in_bf16_and_their_gauges():
    from deepspeed_tpu.telemetry.registry import default_registry
    q, k, _ = _qkv((1, 2, 128, 192), dtype=jnp.bfloat16)
    v = _qkv((1, 2, 128, 128), seed=1, dtype=jnp.bfloat16)[2]
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = reference_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (1, 2, 128, 128)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=3e-2)
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["attention/mla_qk_dim"] == 192
    assert gauges["attention/mla_v_dim"] == 128


def test_equal_widths_trace_the_same_calls_as_before_the_value_width():
    """The value width changes nothing where it is the q·k width: the
    traced call's block shapes hold one D, and the ``mla`` gauges are not
    touched."""
    from deepspeed_tpu.telemetry.registry import default_registry
    default_registry().gauge("attention/mla_v_dim").set(-1)
    q, k, v = _qkv((1, 2, 256, 32))
    text = str(jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
        *a, causal=True, chunk=128, interpret=True).sum(),
        argnums=(0, 1, 2)))(q, k, v))
    assert "pallas_call" in text and ",48]" not in text
    assert default_registry().snapshot()["gauges"][
        "attention/mla_v_dim"] == -1


@pytest.mark.parametrize("family", ["whole-row", "whole-row backward",
                                    "column-block", "window", "dispatch",
                                    "dispatch window", "no tiling", "k"])
def test_the_other_kernel_families_refuse_unequal_widths_by_name(family):
    """Unequal widths are the chunked family's alone: the whole-row, the
    column-block and the window kernels raise with the shapes, and nothing
    routes to ``reference_attention`` behind the caller's back."""
    import importlib
    from deepspeed_tpu.ops import attention
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    q, k, _ = _qkv((1, 2, 128, 48))
    v = _qkv((1, 2, 128, 32), seed=1)[2]
    flat = tuple(t.reshape(2, 128, -1) for t in (q, k, v))
    if family == "whole-row":
        with pytest.raises(ValueError, match=r"whole-row.*\(2, 128, 48\).*"
                                             r"\(2, 128, 32\)"):
            fa._flash_fwd(*flat, 0.1, True, 64, 64, True)
    elif family == "whole-row backward":
        with pytest.raises(ValueError, match="whole-row"):
            fa._flash_bwd(*flat, flat[2], None, flat[2], 0.1, True, 64, 64,
                          True)
    elif family == "column-block":
        with pytest.raises(ValueError, match=r"column-block.*96.*64"):
            fa.flash_attention_bse(*(from_head_major(t) for t in (q, k, v)),
                                   heads=2, causal=True, interpret=True)
    elif family == "window":
        with pytest.raises(ValueError, match=r"window.*\(1, 2, 128, 32\)"):
            flash_attention(q, k, v, causal=True, window=16, interpret=True)
    elif family == "dispatch":
        # the reference path takes them (the CPU's path); a k that is not
        # q's width is refused on every path
        out = attention.dot_product_attention(q, k, v, causal=True,
                                              use_flash=False)
        assert out.shape == (1, 2, 128, 32)
    elif family == "dispatch window":
        with pytest.raises(ValueError, match=r"window=16.*48.*32"):
            attention.dot_product_attention(q, k, v, causal=True, window=16,
                                            use_flash=True)
    elif family == "no tiling":
        odd = tuple(t[:, :, :100] for t in (q, k, v))
        with pytest.raises(ValueError, match=r"chunked kernels alone.*"
                                             r"\(1, 2, 100, 48\)"):
            flash_attention(*odd, causal=True, interpret=True, block_q=64,
                            block_k=64)
    else:
        for call in (functools.partial(flash_attention, interpret=True),
                     functools.partial(attention.dot_product_attention,
                                       use_flash=False)):
            with pytest.raises(ValueError, match=r"one head width.*"
                                                 r"\(1, 2, 128, 32\)"):
                call(q, v, v, causal=True)
