"""``moe/dropless.rows_to_tokens`` on its kernel (ISSUE 36): the segmented sum
that brings the held experts' rows back to their tokens, in Pallas interpret
mode here, against a dense float32 reference — for every k the cells use, for
slabs that are full, half full, empty and that end in one token's k rows, for
float32 rows (the forward call) and bfloat16 rows (the backward call); the
walk's metadata (every row tile up to the last row of a token read once, none
past it); the gauge and the log line that say the kernel took the call."""

import importlib
import logging

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.dropless import DroplessMoE, rows_to_tokens

kernel = importlib.import_module("deepspeed_tpu.ops.pallas.rows_to_tokens")

T, M, H = 256, 384, 128


def dense(rows, tok, n_tokens):
    """``one_hot(tok, T + 1)[:, :T].T @ rows`` in float32; the rows of no
    token zeroed first (they may hold NaN, and 0 x NaN is NaN)."""
    rows = jnp.where((tok < n_tokens)[:, None], rows.astype(jnp.float32), 0.0)
    sel = jax.nn.one_hot(tok, n_tokens + 1, dtype=jnp.float32)[:, :n_tokens]
    return jnp.dot(sel.T, rows, precision=jax.lax.Precision.HIGHEST)


def slab(case, k, seed=0):
    """``tok`` [M] of a slab as ``DroplessMoE`` cuts it: assignments sorted by
    (expert, token), each token in at most k rows, ``T`` past the rows held."""
    rng = np.random.default_rng(seed)
    if case == "one_token":
        return np.full(M, 7, np.int32)
    held = {"all_held": min(M, T * k), "half_held": min(M, T * k) // 2,
            "no_row": 0, "k_rows_at_the_end": min(M, T * k)}[case]
    tail = k if case == "k_rows_at_the_end" else 0
    # the last token's k assignments close the slab, nobody else's
    pool = rng.permutation((T - 1) * k if tail else T * k)[:held - tail]
    expert = rng.integers(0, 8, pool.shape[0])
    pool = pool[np.lexsort((pool, expert))]
    tok = np.full(M, T, np.int32)
    tok[:held - tail] = pool // k
    tok[held - tail:held] = T - 1
    return tok


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case,k", [
    (case, k) for case in ("all_held", "half_held", "no_row",
                           "k_rows_at_the_end") for k in (1, 2, 8, 10)
] + [("one_token", 10)])
def test_rows_reach_their_tokens_as_the_dense_reference_sums_them(case, k,
                                                                  dtype):
    tok = jnp.asarray(slab(case, k))
    rows = jax.random.normal(jax.random.PRNGKey(k), (M, H)).astype(dtype)
    # what the grouped matmul leaves past the rows held goes nowhere
    rows = jnp.where((tok < T)[:, None], rows, jnp.nan)
    got = rows_to_tokens(rows, tok, T, k)
    assert got.shape == (T, H) and got.dtype == dtype
    want = dense(rows, tok, T)
    if case == "no_row":
        assert not np.any(np.asarray(got, np.float32))
    if case == "one_token":         # 384 float32 additions, in another order
        np.testing.assert_allclose(
            got.astype(jnp.float32), want, atol=5e-5,
            rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)
    elif dtype == jnp.bfloat16:     # summed in float32, rounded once
        np.testing.assert_array_equal(got, want.astype(dtype))
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_float32_rows_are_summed_in_float32_and_not_rounded_on_the_way():
    """1 + 2^-15 and -1 are one token's two rows: their float32 sum is 2^-15;
    rounded to bfloat16 before the sum (the MXU's default) it would be 0."""
    rows = jnp.zeros((8, H)).at[0].set(1 + 2.0 ** -15).at[5].set(-1.0)
    tok = jnp.asarray([3, 8, 8, 1, 8, 3, 8, 8])
    got = rows_to_tokens(rows.at[1].set(jnp.inf), tok, 8, 2)
    np.testing.assert_array_equal(got[3], np.full(H, 2.0 ** -15, np.float32))
    assert not np.any(got[:3]) and not np.any(got[4:])


@pytest.mark.parametrize("shape", [
    (10, 16, 4, 3), (300, 520, 192, 8), (128, 128, 256, 10)],
    ids=["T10_M16_H4", "T300_M520_H192", "T128_M128_H256"])
def test_odd_sizes_and_two_column_blocks(shape, monkeypatch):
    """Rows, tokens and columns that fill no whole tile, and rows wider than
    a grid step takes (two column blocks)."""
    n_tokens, n_rows, width, k = shape
    monkeypatch.setattr(kernel, "_COLS", 128)
    rng = np.random.default_rng(1)
    held = n_rows * 3 // 4
    tok = np.full(n_rows, n_tokens, np.int32)
    tok[:held] = rng.permutation(n_tokens * k)[:held] // k
    tok = jnp.asarray(tok)
    rows = jax.random.normal(jax.random.PRNGKey(2), (n_rows, width))
    np.testing.assert_allclose(rows_to_tokens(rows, tok, n_tokens, k),
                               dense(rows, tok, n_tokens), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("held", [0, 1, 128, 129, 300, 384])
def test_the_walk_reads_every_tile_of_rows_held_once_and_none_past(held):
    """The (token block, row tile) pairs of the grid steps: tiles never go
    back (a tile is fetched once), every pair that shares a row is there,
    every block is visited, the steps past the last pair repeat it, and the
    last tile read is the one ``rows_walked`` names."""
    rb, tb = kernel.ROW_TILE, kernel.TOKEN_BLOCK
    n_tiles, n_blocks = M // rb, T // tb
    rng = np.random.default_rng(held)
    seg = np.full(M, T, np.int32)
    seg[:held] = np.sort(rng.permutation(T * 2)[:held] // 2)
    block, tile, (n, with_a_token) = (np.asarray(a) for a in kernel.walk(
        jnp.asarray(seg), n_blocks, n_tiles))
    assert with_a_token == held
    assert block.shape == tile.shape == (n_tiles + n_blocks,)
    assert np.all(np.diff(tile) >= 0) and np.all(np.diff(block) >= 0)
    pairs = set(zip(block[:n].tolist(), tile[:n].tolist()))
    assert len(pairs) == n                                # none twice
    assert {b for b, _ in pairs} == set(range(n_blocks))
    assert pairs >= {(t // tb, r // rb) for r, t in enumerate(seg[:held])}
    assert np.all(block[n:] == block[n - 1])
    assert np.all(tile[n:] == tile[n - 1])
    assert (tile.max() + 1) * rb == int(kernel.rows_walked(held))
    assert int(kernel.rows_walked(held)) < held + rb or held == 0


def _held_layer(boost):
    """One of eight shares of a 32-expert top-4 layer over 2 x 64 tokens, and
    its stats; ``boost`` on the held experts' router columns."""
    layer = DroplessMoE(num_experts=32, k=4, d_ff=32, experts_held=4,
                        expert_share=1, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32)).at[..., 0].set(1)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    params["router"] = params["router"].at[0, 4:8].add(boost)
    _, vs = layer.apply({"params": params}, x, mutable=["stats"])
    return {name: float(v[0]) for name, v in vs["stats"].items()}


@pytest.mark.parametrize("boost,slabs", [(0.0, 1), (20.0, 4)],
                         ids=["first_slab", "every_slab"])
def test_a_held_layer_sows_rows_walked_over_rows_held(boost, slabs):
    """``moe_combine_rows_walked``: what the kernel read in the slabs the
    layer took, over the rows held — whole row tiles of rows held, not the
    slabs' static length."""
    stats = _held_layer(boost)
    assert stats["moe_held_slabs"] == slabs
    cap, rb = 2 * 128 * 4 * 4 // 32, kernel.ROW_TILE
    rows_held = round(stats["moe_rows_held_share"] * 128 * 4)
    walked = sum(max(-(-min(rows_held - s * cap, cap) // rb), 1) * rb
                 for s in range(slabs))
    assert stats["moe_combine_rows_walked"] == pytest.approx(
        walked / rows_held)
    assert 1 <= stats["moe_combine_rows_walked"] < 1 + slabs * rb / rows_held


def test_the_log_names_the_kernel_once_a_shape():
    """One line a distinct shape says which form took the call. (The package
    logger does not propagate: a handler of its own, not caplog.)"""
    from deepspeed_tpu.utils.logging import logger as dlog
    kernel._shapes_logged.clear()
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    dlog.addHandler(handler)
    try:
        for dtype in (jnp.float32, jnp.float32, jnp.bfloat16):
            jax.eval_shape(
                lambda r, t: rows_to_tokens(r, t, 512, 8),
                jax.ShapeDtypeStruct((1024, 256), dtype),
                jax.ShapeDtypeStruct((1024,), jnp.int32))
    finally:
        dlog.removeHandler(handler)
    lines = [r.getMessage() for r in records
             if r.getMessage().startswith("rows_to_tokens [")]
    assert len(lines) == 2, lines
    assert lines[0].startswith(
        "rows_to_tokens [1024, 256] float32 -> [512, 256]: Pallas kernel")
    assert lines[0].endswith("(interpreter)") and "bfloat16" in lines[1]
