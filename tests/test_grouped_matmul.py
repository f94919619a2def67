"""The grouped matmul's tiles (``ops/pallas/grouped_matmul._clip``) and the
kernels at widths that are no powers of two, in the interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul


def _halved(tile, m, k, n):
    """``_clip`` as it was before PR 38: each side halved until it
    divides."""
    out = []
    for t, d in zip(tile, (m, k, n)):
        t = min(t, d)
        while d % t:
            t //= 2
        out.append(t)
    return tuple(out)


# (rows a slab or a layer, K, N) of the gate / up and the down products of
# the three expert cells the benchmark had before PR 38
POWER_OF_TWO_CELLS = [
    (131072, 2048, 1024), (131072, 1024, 2048),     # OLMoE
    (8192, 2048, 512), (8192, 512, 2048),           # Qwen3-Next's slab
    (32768, 2048, 512), (32768, 512, 2048),         # Laguna's slab
]


# the tiles those cells were accepted with, halved until they divided
BEFORE = {"fwd": (256, 2048, 1024), "dlhs": (512, 1024, 1024),
          "drhs": (512, 1024, 1024)}


@pytest.mark.parametrize("m,k,n", POWER_OF_TWO_CELLS, ids=str)
def test_the_divisor_rule_gives_a_power_of_two_what_halving_gave(m, k, n):
    """The caps moved in PR 38 (2,560 and 1,280, for widths of 5 x 512) and
    the rule changed; a power of two keeps the tile it had."""
    for tile, before, dims in (
            (gm.TILE_FWD, BEFORE["fwd"], (m, k, n)),
            (gm.TILE_DLHS, BEFORE["dlhs"], (m, n, k)),
            (gm.TILE_DRHS, BEFORE["drhs"], (m, k, n))):
        assert gm._clip(tile, *dims) == _halved(before, *dims)
        assert gm._clip(before, *dims) == _halved(before, *dims)


@pytest.mark.parametrize("cap,d,want", [
    (2048, 2560, 1280),     # 5 x 512: halving stopped at 512
    (1024, 2560, 640),
    (1024, 768, 768),       # 3 x 256: halving stopped at 256
    (512, 768, 384),
    (256, 49152, 256), (512, 49152, 512),
    (2048, 2048, 2048), (1024, 4096, 1024),
    (256, 24, 24), (512, 200, 200), (2048, 64, 64),     # a test's sizes
    (256, 96, 96), (128, 320, 64),
])
def test_the_tile_is_the_largest_multiple_of_128_that_divides(cap, d, want):
    assert gm._divisor(cap, d) == want
    assert d % want == 0 and want <= max(cap, 1)


def test_smallthinkers_widths_get_whole_divisor_tiles():
    """(K, N) = (2560, 768) and (768, 2560) at the cell's slab of 49,152
    rows: every tile divides its side."""
    m = 49152
    for k, n in ((2560, 768), (768, 2560)):
        for tile, dims in ((gm.TILE_FWD, (m, k, n)),
                           (gm.TILE_DLHS, (m, n, k)),
                           (gm.TILE_DRHS, (m, k, n))):
            got = gm._clip(tile, *dims)
            assert all(d % t == 0 for t, d in zip(got, dims)), (got, dims)
            assert all(t % 128 == 0 for t in got)
            assert all(t <= cap for t, cap in zip(got, tile))
    assert gm._clip((256, 2048, 1024), m, 2560, 768) == (256, 1280, 768)
    assert _halved((256, 2048, 1024), m, 2560, 768) == (256, 512, 768)
    # the swept caps: the whole contraction forward, 1,280 backward
    assert gm._clip(gm.TILE_FWD, m, 2560, 768) == (256, 2560, 768)
    assert gm._clip(gm.TILE_FWD, m, 768, 2560) == (256, 768, 1280)
    assert gm._clip(gm.TILE_DLHS, m, 768, 2560) == (512, 768, 1280)
    assert gm._clip(gm.TILE_DRHS, m, 2560, 768) == (512, 1280, 768)


@pytest.mark.parametrize("k,n", [(640, 384), (384, 640)], ids=str)
def test_kernels_at_widths_that_are_no_powers_of_two(k, n, monkeypatch):
    """5 x 128 and 3 x 128 wide, tiles capped so that the contraction and
    the columns take several tiles each: forward and both gradients against
    a per-expert loop."""
    monkeypatch.setattr(gm, "TILE_FWD", (128, 512, 256))
    monkeypatch.setattr(gm, "TILE_DLHS", (128, 256, 512))
    monkeypatch.setattr(gm, "TILE_DRHS", (128, 512, 256))
    assert gm._clip(gm.TILE_FWD, 256, k, n) == \
        (128, {640: 128, 384: 384}[k], 128)
    sizes = jnp.asarray([100, 0, 156], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(k), 2)
    lhs = jax.random.normal(ks[0], (256, k), jnp.float32)
    rhs = 0.1 * jax.random.normal(ks[1], (3, k, n), jnp.float32)

    def loop(lhs, rhs):
        return jnp.concatenate([lhs[:100] @ rhs[0], lhs[100:] @ rhs[2]])

    def loss(f):
        return lambda a, b: jnp.sum(jnp.sin(f(a, b)))

    with jax.default_matmul_precision("highest"):
        got = grouped_matmul(lhs, rhs, sizes)
        g1 = jax.grad(loss(lambda a, b: grouped_matmul(a, b, sizes)),
                      argnums=(0, 1))(lhs, rhs)
        want, g2 = loop(lhs, rhs), jax.grad(loss(loop), argnums=(0, 1))(
            lhs, rhs)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------- a width no multiple of 128 divides (PR 40)

@pytest.mark.parametrize("d,want", [
    (1856, 1920), (3712, 3712), (2688, 2688), (1920, 1920), (192, 256),
    (2560, 2560), (768, 768), (2048, 2048), (512, 512), (128, 128),
    (64, 64), (24, 24), (96, 96), (200, 256),
])
def test_only_a_width_that_128_does_not_divide_is_padded(d, want):
    """1,856 = 29 x 64 goes to 1,920; powers of two, 768, 2,560, 2,688 and
    3,712 = 29 x 128 stay; a side under 128 (a test's sizes) is left to
    ``_divisor``'s halving."""
    assert gm.lane_padded(d) == want
    assert want % 128 == 0 or want == d < 128


def test_nemotrons_widths_get_whole_tiles_and_every_old_tile_stands():
    """(K, N) = (2688, 1920) and (1920, 2688) — 1,856 padded — at the cell's
    slab of 12,288 rows: every tile is a multiple of 128 that divides its
    side (the unpadded 1,856 would have been halved from 1,280 down to a
    tile of 2); and the tiles of every cell the benchmark had are the ones
    they were."""
    m = 12288
    assert gm._divisor(1280, 1856) == 2                  # why it is padded
    for k, n in ((2688, 1920), (1920, 2688)):
        for tile, dims in ((gm.TILE_FWD, (m, k, n)),
                           (gm.TILE_DLHS, (m, n, k)),
                           (gm.TILE_DRHS, (m, k, n))):
            got = gm._clip(tile, *dims)
            assert all(d % t == 0 and t % 128 == 0
                       for t, d in zip(got, dims)), (got, dims)
    assert gm._clip(gm.TILE_FWD, m, 2688, 1920) == (256, 896, 640)
    assert gm._clip(gm.TILE_FWD, m, 1920, 2688) == (256, 1920, 896)
    assert gm._clip(gm.TILE_DLHS, m, 1920, 2688) == (512, 640, 896)
    assert gm._clip(gm.TILE_DRHS, m, 2688, 1920) == (512, 896, 640)
    # pinned: the accepted cells' tiles
    assert gm._clip(gm.TILE_FWD, 131072, 2048, 1024) == (256, 2048, 1024)
    assert gm._clip(gm.TILE_DLHS, 131072, 1024, 2048) == (512, 1024, 1024)
    assert gm._clip(gm.TILE_DRHS, 131072, 2048, 1024) == (512, 1024, 1024)
    assert gm._clip(gm.TILE_FWD, 8192, 2048, 512) == (256, 2048, 512)
    assert gm._clip(gm.TILE_FWD, 32768, 512, 2048) == (256, 512, 1024)
    assert gm._clip(gm.TILE_FWD, 49152, 2560, 768) == (256, 2560, 768)
    assert gm._clip(gm.TILE_FWD, 49152, 768, 2560) == (256, 768, 1280)
    assert gm._clip(gm.TILE_DLHS, 49152, 768, 2560) == (512, 768, 1280)
    assert gm._clip(gm.TILE_DRHS, 49152, 2560, 768) == (512, 1280, 768)


@pytest.mark.parametrize("k,n", [(256, 1856), (1856, 256)],
                         ids=["N_1856", "K_1856"])
def test_kernels_at_a_width_of_1856(k, n):
    """The published expert width on the columns and on the contraction:
    forward, dlhs and drhs against a per-expert loop, the operands and the
    gradients at 1,856 (the padding is the call's own and is cut again)."""
    sizes = jnp.asarray([100, 0, 156], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(k), 2)
    lhs = jax.random.normal(ks[0], (256, k), jnp.float32)
    rhs = 0.1 * jax.random.normal(ks[1], (3, k, n), jnp.float32)

    def loop(lhs, rhs):
        return jnp.concatenate([lhs[:100] @ rhs[0], lhs[100:] @ rhs[2]])

    def loss(f):
        return lambda a, b: jnp.sum(jnp.sin(f(a, b)))

    with jax.default_matmul_precision("highest"):
        got = grouped_matmul(lhs, rhs, sizes)
        g1 = jax.grad(loss(lambda a, b: grouped_matmul(a, b, sizes)),
                      argnums=(0, 1))(lhs, rhs)
        want, g2 = loop(lhs, rhs), jax.grad(loss(loop), argnums=(0, 1))(
            lhs, rhs)
    assert got.shape == (256, n)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-4)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-4)
