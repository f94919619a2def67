"""The learned-sparse-attention kernels
(``ops/pallas/learned_sparse_attention.py``) in the interpreter against the
dense plain-XLA oracle of the same file: output, KL, the kept set and its
bits, and every gradient, at a ragged last tile, at rows shorter than the
top-k, and with scores that tie. (The selection's pin under remat is the
compiled program's to show: ``tests/test_tpu_compile.py``.)"""

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
