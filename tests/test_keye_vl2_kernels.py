"""The learned-sparse-attention kernels
(``ops/pallas/learned_sparse_attention.py``) in the interpreter against the
dense plain-XLA oracle of the same file: output, KL, the kept set and its
bits, and every gradient, at a ragged last tile, at rows shorter than the
top-k, and with scores that tie; the KL's gradient as its causal tiles, and
the gradients of a rematted call that keeps it against one that makes it
again. (The selection's pin under remat is the compiled program's to show:
``tests/test_tpu_compile.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention

from deepspeed_tpu.ops.pallas import learned_sparse_attention as lsa


def _operands(S, H=4, Hkv=2, D=32, J=3, Di=16, seed=0, ties=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    f = jnp.float32
    q, k, v = (jax.random.normal(ks[i], (1, h, S, D), f)
               for i, h in enumerate((H, Hkv, Hkv)))
    iq = jax.random.normal(ks[3], (1, J, S, Di), f)
    ik = jax.random.normal(ks[4], (1, S, Di), f)
    iw = 0.3 * jax.random.normal(ks[5], (1, S, J), f)
    if ties:        # small whole numbers: many scores equal, many exactly 0
        iq, ik, iw = jnp.round(iq), jnp.round(ik), jnp.round(3 * iw)
    return q, k, v, iq, ik, iw


def _both(S, topk, **kw):
    args = _operands(S, **kw)
    scale = args[0].shape[-1] ** -0.5

    def run(fn):
        def loss(*a):
            o, kl, kept, bits = fn(*a)
            w = jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape)
            return jnp.sum(o * w) + jnp.mean(kl), (o, kl, kept, bits)
        return jax.value_and_grad(loss, argnums=tuple(range(6)),
                                  has_aux=True)(*args)
    return (run(lambda *a: lsa.reference_learned_sparse_attention(
        *a, topk, scale)),
        run(lambda *a: lsa.learned_sparse_attention(*a, topk, scale,
                                                    interpret=True)))


@pytest.mark.parametrize("S,topk,kw", [
    (200, 50, {"seed": 1}),    # two tiles of 128, the last ragged (200 rows)
    (128, 300, {"seed": 3}),            # every row shorter than the top-k
    (256, 40, {"seed": 2, "ties": True}),
], ids=["ragged", "rows-under-topk", "ties"])
def test_the_kernels_equal_the_dense_oracle(S, topk, kw):
    ((l0, (o0, kl0, n0, b0)), g0), ((l1, (o1, kl1, n1, b1)), g1) = \
        _both(S, topk, **kw)
    assert int(n0.sum()) == int(n1.sum()) == lsa.selected_pairs(S, topk)
    assert np.array_equal(np.asarray(n0), np.asarray(n1))
    # the kept sets are the same sets: a tie went to the smaller key
    assert b0.shape == b1.shape == (1, -(-S // 8), S)
    assert np.array_equal(np.asarray(b0), np.asarray(b1))
    assert float(l1) == pytest.approx(float(l0), abs=1e-4)
    assert np.allclose(o1, o0, atol=5e-6) and np.allclose(kl1, kl0, atol=1e-5)
    for name, a, b in zip(("q", "k", "v", "iq", "ik", "iw"), g1, g0):
        assert np.allclose(a, b, atol=1e-5, rtol=1e-4), name


@pytest.mark.parametrize("S,topk", [(200, 50), (384, 40), (128, 300)],
                         ids=["padded", "three-tiles", "rows-under-topk"])
def test_the_kl_gradient_is_written_as_the_causal_tiles_alone(S, topk):
    """``_kl_call``'s GT is [B, n (n + 1) / 2, tile, tile]: tile (i, c) of
    the dense [key, query] gradient — the oracle's KL differentiated in the
    scores, on the kernels' own kept set — at i (i + 1) / 2 + c, for every
    key block c at or under query block i; nothing above the diagonal
    exists."""
    q, k, v, iq, ik, iw = _operands(S, seed=5)
    H, Hkv, D = q.shape[1], k.shape[1], q.shape[-1]
    tile, scale = lsa._tile(True), D ** -0.5
    q, k, v, iq = (lsa._pad_rows(x, 2, tile) for x in (q, k, v, iq))
    ik, iw = (lsa._pad_rows(x, 1, tile) for x in (ik, iw))
    Sp = q.shape[2]
    n = Sp // tile

    @jax.jit
    def both():
        it = lsa._index_scores(iq, ik, iw, tile, True)
        mt, lse_i, _ = lsa._select_call(it, topk, True)
        _, lse = lsa._masked_attention(
            q.reshape(H, Sp, D), k.reshape(Hkv, Sp, D),
            v.reshape(Hkv, Sp, D), mt, scale, *lsa._plan(Sp, D, 4, True),
            True, H, Hkv)
        _, gt = lsa._kl_call(it, lse_i, q, k, lse, mt, scale, tile, True,
                             jnp.float32)
        dense = jax.grad(lambda s: lsa.reference_index_kl(
            s, q, k, lse.reshape(1, H, Sp), jnp.swapaxes(mt, 1, 2) != 0,
            scale).sum())(jnp.swapaxes(it, 1, 2))
        return gt, jnp.swapaxes(dense, 1, 2)

    gt, dense = (np.asarray(x) for x in both())
    assert gt.shape == (1, lsa.tiles_walked(S, tile), tile, tile)
    assert n * (n + 1) // 2 == gt.shape[1] and np.abs(dense).max() > 1e-3
    for i in range(n):
        for c in range(i + 1):
            assert int(lsa.packed_tile(i, c)) == i * (i + 1) // 2 + c
            assert np.allclose(
                gt[0, i * (i + 1) // 2 + c],
                dense[0, c * tile:(c + 1) * tile, i * tile:(i + 1) * tile],
                atol=2e-6), (i, c)
        assert int(lsa.packed_tile(i, n)) == int(lsa.packed_tile(i, i))


def test_a_kept_kl_gradient_gives_the_recomputed_ones_gradients_exactly():
    """Under a ``jax.checkpoint`` whose policy keeps ``KL_GRAD_NAME`` the
    recomputation runs neither the indexer nor the KL pass — three forward
    kernels fewer than under one that does not — and the six gradients are
    the other's to the bit, and the dense oracle's within this file's
    limits."""
    from tests.hlo_text import pallas_calls
    S, topk = 200, 50
    args = _operands(S, seed=1)
    scale = args[0].shape[-1] ** -0.5
    w = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)) \
        .reshape(args[0].shape)

    def loss(fn):
        def f(*a):
            o, kl, _, _ = fn(*a)
            return jnp.sum(o * w) + jnp.mean(kl)
        return f

    def rematted(*names):
        grads = jax.jit(jax.grad(jax.checkpoint(
            loss(lambda *a: lsa.learned_sparse_attention(
                *a, topk, scale, interpret=True)),
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse", lsa.SELECTION_NAME, *names)),
            argnums=tuple(range(6))))
        kernels = [eqn.params["jaxpr"].debug_info.func_name for eqn in
                   pallas_calls(jax.make_jaxpr(grads)(*args).jaxpr)]
        return grads(*args), kernels

    (kept, fewer), (again, more) = rematted(lsa.KL_GRAD_NAME), rematted()
    assert sorted(fewer) == sorted(
        ["_indexer_kernel", "_select_kernel", "_masked_fwd_kernel",
         "_kl_kernel", "_masked_bwd_kernel", "_indexer_bwd_kernel"])
    assert sorted(more) == sorted(fewer + ["_indexer_kernel", "_kl_kernel"])
    oracle = jax.jit(jax.grad(loss(
        lambda *a: lsa.reference_learned_sparse_attention(*a, topk, scale)),
        argnums=tuple(range(6))))(*args)
    for name, a, b, c in zip(("q", "k", "v", "iq", "ik", "iw"), kept, again,
                             oracle):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert np.allclose(a, c, atol=1e-5, rtol=1e-4), name
    assert float(jnp.abs(kept[3]).max()) > 0


def test_the_selection_is_the_largest_scores_and_the_mask_packs():
    S, topk = 256, 48
    _, _, _, iq, ik, iw = _operands(S, seed=4, ties=True)
    scores = lsa.reference_index_scores(iq, ik, iw)
    kept = np.asarray(lsa.reference_select(scores, topk))[0]
    scores = np.asarray(scores)[0]
    for t in (0, 10, 47, 48, 100, 255):
        row = np.where(np.arange(S) <= t, scores[t], -np.inf)
        # a stable sort by (-score, key): the first ``topk`` causal keys
        order = np.lexsort((np.arange(S), -row))[:min(t + 1, topk)]
        assert set(np.flatnonzero(kept[t])) == set(order), t
    mt = jnp.asarray(kept.T[None].astype(np.int8))
    assert np.array_equal(np.asarray(lsa.unpack_mask(lsa.pack_mask(mt))), mt)
    assert lsa.pack_mask(mt).shape == (1, S // 8, S)
    assert lsa.selected_pairs(16384, 2048) == 31_458_304
    assert lsa.causal_pairs(16384) == 134_225_920
    assert lsa.tiles_walked(16384, 512) == 528
    assert lsa.tile_overcompute(16384, 2048, 512) == pytest.approx(4.40, 0.01)


def test_the_entry_routes_and_documents_its_families():
    q, k, v, iq, ik, iw = _operands(64)
    out = attention.learned_sparse_attention(q, k, v, iq, ik, iw, 16,
                                             use_flash=False)
    want = lsa.reference_learned_sparse_attention(q, k, v, iq, ik, iw, 16,
                                                  q.shape[-1] ** -0.5)
    for a, b in zip(out, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for family in ("causal / full", "band", "block-diffusion",
                   "learned-sparse"):
        assert family in attention.DISPATCH
    with pytest.raises(ValueError, match="one head width"):
        attention.learned_sparse_attention(q, k[..., :16], v, iq, ik, iw, 16)
