"""Continuous-batching serving engine tests (deepspeed_tpu/serving).

Covers the acceptance surface of the paged-KV subsystem:

- the paged attention kernel matches the dense stacked kernels when the
  pool blocks are laid out to mirror a contiguous cache (both storages);
- end-to-end paged serving reproduces the static-batch fused decode
  paths token-for-token (greedy) for GPT-2 (bf16 + int8w/int8kv) and
  LLaMA (GQA, int8 weights, both cache storages);
- slot/page reuse: admitting a request into a slot just freed by a
  LONGER request must not read stale K/V codes or stale int8
  per-position scale arrays;
- the host-side page allocator's accounting and the `serving` config
  block's validation.
"""

import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu.serving as serving
from deepspeed_tpu.serving.paged_cache import (PagedCacheSpec, PagedKVCache,
                                               TRASH_BLOCK)


@pytest.fixture
def rs():
    return np.random.RandomState(0)


# ------------------------------------------------------- kernel parity


def test_paged_attention_matches_dense_fp(rs):
    from deepspeed_tpu.ops.pallas.decode import (
        decode_attention_paged, decode_attention_fp_stacked)
    Lyr, NB, H, P, D = 2, 9, 4, 16, 64
    B, R, MAXP = 3, 2, 4
    L = MAXP * P
    kp = jnp.asarray(rs.randn(Lyr, NB, H, P, D), jnp.float32) * 0.3
    vp = jnp.asarray(rs.randn(Lyr, NB, H, P, D), jnp.float32) * 0.3
    q = jnp.asarray(rs.randn(B, H, R, D), jnp.float32) * 0.3
    pt = np.zeros((B, MAXP), np.int32)
    pt[0, :2] = [3, 5]
    pt[1, :4] = [1, 2, 7, 8]
    pt[2, :1] = [6]
    pos = np.array([20, 60, -1], np.int32)   # slot 2 idle
    got = decode_attention_paged(q, kp, vp, pos, jnp.asarray(pt), 1)
    k_dense = np.zeros((Lyr, B, H, L, D), np.float32)
    v_dense = np.zeros((Lyr, B, H, L, D), np.float32)
    for b in range(B):
        for p in range(MAXP):
            k_dense[:, b, :, p * P:(p + 1) * P] = np.asarray(kp)[:, pt[b, p]]
            v_dense[:, b, :, p * P:(p + 1) * P] = np.asarray(vp)[:, pt[b, p]]
    for b in range(B):
        if pos[b] < 0:
            # idle slots must emit zeros, not stale/garbage context
            np.testing.assert_array_equal(np.asarray(got[b]), 0.0)
            continue
        ref = decode_attention_fp_stacked(
            q[b:b + 1], jnp.asarray(k_dense[:, b:b + 1]),
            jnp.asarray(v_dense[:, b:b + 1]), int(pos[b]), 1)
        np.testing.assert_allclose(np.asarray(got[b:b + 1]),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_paged_attention_matches_dense_int8(rs):
    from deepspeed_tpu.ops.pallas.decode import (
        decode_attention_paged, decode_attention_int8_stacked)
    Lyr, NB, H, P, D = 2, 7, 2, 16, 32
    B, MAXP = 2, 3
    L = MAXP * P
    kc = jnp.asarray(rs.randint(-127, 128, (Lyr, NB, H, P, D)), jnp.int8)
    vc = jnp.asarray(rs.randint(-127, 128, (Lyr, NB, H, P, D)), jnp.int8)
    ks = jnp.asarray(np.abs(rs.randn(Lyr, NB, H, 1, P)) * 0.01 + 1e-3,
                     jnp.float32)
    vs = jnp.asarray(np.abs(rs.randn(Lyr, NB, H, 1, P)) * 0.01 + 1e-3,
                     jnp.float32)
    q = jnp.asarray(rs.randn(B, H, 1, D), jnp.float32) * 0.3
    pt = np.zeros((B, MAXP), np.int32)
    pt[0, :3] = [2, 4, 6]
    pt[1, :2] = [1, 5]
    pos = np.array([40, 17], np.int32)
    got = decode_attention_paged(q, kc, vc, pos, jnp.asarray(pt), 0,
                                 k_scale=ks, v_scale=vs)
    kcd = np.zeros((Lyr, B, H, L, D), np.int8)
    vcd = np.zeros((Lyr, B, H, L, D), np.int8)
    ksd = np.zeros((Lyr, B, H, 1, L), np.float32)
    vsd = np.zeros((Lyr, B, H, 1, L), np.float32)
    for b in range(B):
        for p in range(MAXP):
            kcd[:, b, :, p * P:(p + 1) * P] = np.asarray(kc)[:, pt[b, p]]
            vcd[:, b, :, p * P:(p + 1) * P] = np.asarray(vc)[:, pt[b, p]]
            ksd[:, b, :, 0, p * P:(p + 1) * P] = \
                np.asarray(ks)[:, pt[b, p], :, 0]
            vsd[:, b, :, 0, p * P:(p + 1) * P] = \
                np.asarray(vs)[:, pt[b, p], :, 0]
    for b in range(B):
        ref = decode_attention_int8_stacked(
            q[b:b + 1], jnp.asarray(kcd[:, b:b + 1]),
            jnp.asarray(ksd[:, b:b + 1]), jnp.asarray(vcd[:, b:b + 1]),
            jnp.asarray(vsd[:, b:b + 1]), int(pos[b]), 0)
        np.testing.assert_allclose(np.asarray(got[b:b + 1]),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="runs on the chip only (compiled paged kernel, interpret "
           "off). conftest.py pins the suite to the CPU backend, so run "
           "it through the chip tool without conftest, in the same call "
           "as chip_smoke.py: `python -m pytest --noconftest -m slow -k "
           "real_chip tests/test_serving.py` (self-contained — no "
           "conftest fixtures). chip_smoke.py's serve phase drives the "
           "same kernel through the engine.")
def test_paged_attention_real_chip_matches_dense(rs):
    """First-real-chip parity for ``decode_attention_paged`` with
    ``interpret=False``: the page-table gathers live in Pallas BLOCK
    INDEX MAPS (pt[b, pb] indexing inside a scalar-prefetch closure),
    which interpret mode never lowers through Mosaic — a lowering bug
    there (e.g. dynamic block indices on the pool dim) would pass every
    CPU test and crash or corrupt on hardware. Same layout as
    test_paged_attention_matches_dense_fp, interpret forced OFF."""
    from deepspeed_tpu.ops.pallas.decode import (
        decode_attention_paged, decode_attention_fp_stacked)
    Lyr, NB, H, P, D = 2, 9, 4, 16, 64
    B, R, MAXP = 3, 2, 4
    L = MAXP * P
    kp = jnp.asarray(rs.randn(Lyr, NB, H, P, D), jnp.float32) * 0.3
    vp = jnp.asarray(rs.randn(Lyr, NB, H, P, D), jnp.float32) * 0.3
    q = jnp.asarray(rs.randn(B, H, R, D), jnp.float32) * 0.3
    pt = np.zeros((B, MAXP), np.int32)
    pt[0, :2] = [3, 5]
    pt[1, :4] = [1, 2, 7, 8]
    pt[2, :1] = [6]
    pos = np.array([20, 60, -1], np.int32)
    got = decode_attention_paged(q, kp, vp, pos, jnp.asarray(pt), 1,
                                 interpret=False)
    k_dense = np.zeros((Lyr, B, H, L, D), np.float32)
    v_dense = np.zeros((Lyr, B, H, L, D), np.float32)
    for b in range(B):
        for p in range(MAXP):
            k_dense[:, b, :, p * P:(p + 1) * P] = np.asarray(kp)[:, pt[b, p]]
            v_dense[:, b, :, p * P:(p + 1) * P] = np.asarray(vp)[:, pt[b, p]]
    for b in range(B):
        if pos[b] < 0:
            np.testing.assert_array_equal(np.asarray(got[b]), 0.0)
            continue
        ref = decode_attention_fp_stacked(
            q[b:b + 1], jnp.asarray(k_dense[:, b:b + 1]),
            jnp.asarray(v_dense[:, b:b + 1]), int(pos[b]), 1,
            interpret=False)
        # compiled, the MXU takes fp32 operands in bf16 passes, and the
        # paged kernel rescales its softmax per 16-row page where the
        # dense one does per 64-row block, so the probabilities round
        # differently: agreement is to bf16 resolution (2^-8 of outputs
        # up to ~0.12; first chip run, PR 21: max |diff| 2.4e-4), where
        # a wrong page gather is off by the outputs' own size
        np.testing.assert_allclose(np.asarray(got[b:b + 1]),
                                   np.asarray(ref), rtol=1e-2, atol=1e-3)


# ----------------------------------------------------------- allocator


def test_page_allocator_accounting():
    spec = PagedCacheSpec(n_layers=1, kv_heads=1, head_dim=8,
                          page_size=4, slots=2, max_pages_per_slot=4,
                          num_blocks=6)       # undersubscribed pool
    cache = PagedKVCache(spec)
    total = cache.free_pages
    assert total == spec.resolved_num_blocks() - 1   # trash reserved
    pages = cache.admit(0, total_tokens=9)           # 3 pages of 4
    assert len(pages) == 3 and TRASH_BLOCK not in pages
    assert cache.free_pages == total - 3
    assert list(cache.page_table[0][:3]) == pages
    # exhaust: slot 1 wants 3 pages but only 2 remain in the pool
    left = cache.free_pages
    assert left == 2
    assert cache.admit(1, total_tokens=9) is None
    assert cache.free_pages == left                  # nothing leaked
    cache.release(0)
    assert cache.free_pages == total
    assert all(cache.page_table[0] == TRASH_BLOCK)


def test_serving_config_block_validation():
    from deepspeed_tpu.config.config import (ServingConfig,
                                             DeepSpeedConfigError)
    sc = ServingConfig({"serving": {"slots": 4, "page_size": 64,
                                    "kv_cache_bits": 8}})
    assert sc.enabled and sc.slots == 4 and sc.page_size == 64
    assert sc.kv_cache_bits == 8
    assert not ServingConfig({}).enabled
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig({"serving": {"kv_cache_bits": 4}})
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig({"serving": {"slots": 0}})
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig({"serving": {"slots": 8, "num_blocks": 4}})


# --------------------------------------------------------- GPT-2 e2e


# Engines are built through a MODULE-scoped adapter factory: compiled
# tick/prefill programs live on the adapter (per-adapter cache — see
# adapters.py), so tests sharing a geometry share its compiles instead
# of re-paying interpret-mode compilation per test (tier-1 wall
# budget). The slot-reuse test keeps its own page-8 geometry on purpose
# (stale rows must span pages).


def _gpt2_cfg():
    from deepspeed_tpu.models.gpt2 import GPT2Config
    return GPT2Config(vocab_size=256, n_positions=128, n_embd=128,
                      n_layer=2, n_head=4, dtype=jnp.float32,
                      param_dtype=jnp.float32, scan_layers=True)


def _gpt2_params(cfg):
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    return jax.jit(GPT2LMHeadModel(cfg).init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]


@pytest.fixture(scope="module")
def gpt2_serving():
    """(cfg, params, qparams, make): make(**serving_kw) returns a fresh
    engine whose adapter (and compiled programs) is shared per distinct
    serving geometry across the module's tests."""
    from deepspeed_tpu.models.gpt2_inference import (
        convert_gpt2_params, quantize_gpt2_inference_params)
    cfg = _gpt2_cfg()
    params = _gpt2_params(cfg)
    qparams = quantize_gpt2_inference_params(
        convert_gpt2_params(params, cfg))
    adapters = {}

    def make(int8=False, **kw):
        sv = {"slots": 2, "page_size": 16, "max_pages_per_slot": 6, **kw}
        key = (int8, tuple(sorted(sv.items())))
        if key not in adapters:
            eng = serving.build_engine(
                "gpt2", cfg, qparams if int8 else params,
                config={"serving": sv})
            adapters[key] = eng.adapter
            return eng
        return serving.ContinuousBatcher(adapters[key])

    return cfg, params, qparams, make


def test_gpt2_paged_serving_matches_generate(rs, gpt2_serving):
    from deepspeed_tpu.models.gpt2_inference import generate
    cfg, params, _, make = gpt2_serving
    eng = make()
    lens = (7, 19, 30)
    news = (12, 5, 9)
    prompts = [rs.randint(0, 256, size=(s,)).astype(np.int32)
               for s in lens]
    res = eng.serve([serving.Request(i, p, max_new_tokens=n)
                     for i, (p, n) in enumerate(zip(prompts, news))])
    for i, (p, n) in enumerate(zip(prompts, news)):
        ref = np.asarray(generate(cfg, params, p[None], max_new_tokens=n,
                                  max_out_tokens=128)[0])
        np.testing.assert_array_equal(res[i].tokens(), ref)
    # all three served through the same compiled tick
    assert eng.stats["prefills"] == 3
    assert eng.stats["decode_tokens"] == sum(news) - 3


def test_gpt2_paged_serving_int8_matches_generate(rs, gpt2_serving):
    from deepspeed_tpu.models.gpt2_inference import generate
    cfg, _, qparams, make = gpt2_serving
    eng = make(int8=True, kv_cache_bits=8)
    p = rs.randint(0, 256, size=(13,)).astype(np.int32)
    res = eng.serve([serving.Request(0, p, max_new_tokens=8)])
    ref = np.asarray(generate(cfg, qparams, p[None], max_new_tokens=8,
                              max_out_tokens=128, quantize_bits=8,
                              kv_cache_bits=8)[0])
    np.testing.assert_array_equal(res[0].tokens(), ref)


@pytest.mark.parametrize("arrivals", [None, (0.0, 0.0, 0.01, 0.03, 0.6)],
                         ids=["all_queued", "on_an_arrival_clock"])
def test_gpt2_more_requests_than_slots(rs, gpt2_serving, arrivals):
    """5 requests through 2 slots: freed slots re-admit mid-flight and
    every request still matches a solo run — queued together, or each due
    at its ``arrival_time`` (``respect_arrival_times``: the last arrives
    after the engine has gone idle; none is lost, and the run lasts at
    least until the last is due). The oracle is a fresh paged
    engine serving each request ALONE (dense-path parity is pinned by
    test_gpt2_paged_serving_matches_generate; the property here is
    scheduler correctness under slot contention — and the solo engine
    shares every compiled program, where generate() would compile one
    decode program per distinct length)."""
    _, _, _, make = gpt2_serving
    eng = make()
    lens = (5, 21, 11, 3, 17)
    news = (9, 2, 6, 11, 4)
    prompts = [rs.randint(0, 256, size=(s,)).astype(np.int32)
               for s in lens]
    reqs = [serving.Request(i, p, max_new_tokens=n,
                            arrival_time=arrivals[i] if arrivals else 0.0)
            for i, (p, n) in enumerate(zip(prompts, news))]
    t0 = time.monotonic()
    res = eng.serve(reqs, respect_arrival_times=arrivals is not None)
    assert len(res) == 5
    if arrivals:
        assert time.monotonic() - t0 >= arrivals[-1]
        assert eng.metrics_snapshot()["ttft_s"]["count"] == 5
    # a second batcher over the SAME adapter shares its compiled
    # tick/prefill programs (fresh cache, fresh scheduler state)
    solo = serving.ContinuousBatcher(eng.adapter)
    for i, (p, n) in enumerate(zip(prompts, news)):
        ref = solo.serve([serving.Request("s", p, max_new_tokens=n)])
        np.testing.assert_array_equal(res[i].tokens(),
                                      ref["s"].tokens())


def test_eos_frees_slot_early(rs, gpt2_serving):
    _, _, _, make = gpt2_serving

    def run(eos):
        eng = make()
        p = rs.randint(0, 256, size=(9,)).astype(np.int32)
        return eng.serve([serving.Request("r", p, max_new_tokens=12,
                                          eos_token_id=eos)])["r"]

    rs = np.random.RandomState(7)
    full = run(eos=None)
    assert full.finish_reason == "length"
    assert len(full.generated) == 12
    # declare a later generated token the "eos": generation must stop at
    # its FIRST occurrence and report the eos finish reason
    rs = np.random.RandomState(7)
    eos_tok = int(full.generated[3])
    first = full.generated.index(eos_tok)
    stopped = run(eos=eos_tok)
    assert stopped.finish_reason == "eos"
    assert stopped.generated == full.generated[:first + 1]


# --------------------------------------------------- slot-reuse / stale


@pytest.mark.parametrize("kv_bits", [0, 8])
def test_slot_reuse_no_stale_kv(rs, kv_bits, gpt2_serving):
    """Admit short request B into the slot (and pages — the free list is
    LIFO) a LONGER request A just released: B's tokens and final-step
    logits must match a fresh-cache engine that only ever saw B. Catches
    stale K/V rows AND stale int8 per-position scale arrays beyond B's
    length (kv_bits=8)."""
    _, _, _, make = gpt2_serving
    pb = rs.randint(0, 256, size=(6,)).astype(np.int32)
    pa = rs.randint(0, 256, size=(40,)).astype(np.int32)

    used = make(slots=1, page_size=8, max_pages_per_slot=8,
                kv_cache_bits=kv_bits)
    res_a = used.serve([serving.Request("a", pa, max_new_tokens=14)])
    assert used.cache.free_pages == \
        used.cache.spec.resolved_num_blocks() - 1
    res_b = used.serve([serving.Request("b", pb, max_new_tokens=5)])
    logits_b = np.asarray(used.last_logits[0])

    fresh = serving.ContinuousBatcher(used.adapter)   # fresh pool+pages
    ref_b = fresh.serve([serving.Request("b", pb, max_new_tokens=5)])
    ref_logits = np.asarray(fresh.last_logits[0])

    np.testing.assert_array_equal(res_b["b"].tokens(), ref_b["b"].tokens())
    np.testing.assert_allclose(logits_b, ref_logits, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------- LLaMA e2e


def _llama_cfg():
    from deepspeed_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=256, hidden_size=128, n_layers=2,
                       n_heads=4, n_kv_heads=2, intermediate_size=256,
                       max_seq_len=128, dtype=jnp.float32,
                       param_dtype=jnp.float32)


@pytest.mark.parametrize("kv_bits", [
    # the fp-cache variant rides the slow tier: its unique surface (GQA
    # query rows through the fp paged kernel) is pinned fast by
    # test_paged_attention_matches_dense_fp, and the int8 e2e keeps the
    # whole LLaMA serving stack in tier-1
    pytest.param(0, marks=pytest.mark.slow),
    8,
])
def test_llama_paged_serving_matches_fast_generate(rs, kv_bits):
    from deepspeed_tpu.models.llama_inference import (
        llama_fast_generate, random_int8_serving_params)
    cfg = _llama_cfg()
    sparams = random_int8_serving_params(cfg)
    eng = serving.build_engine(
        "llama", cfg, sparams,
        config={"serving": {"slots": 2, "page_size": 16,
                            "max_pages_per_slot": 6,
                            "kv_cache_bits": kv_bits}})
    lens = (21, 9)
    news = (6, 10)
    prompts = [rs.randint(0, 256, size=(s,)).astype(np.int32)
               for s in lens]
    res = eng.serve([serving.Request(i, p, max_new_tokens=n)
                     for i, (p, n) in enumerate(zip(prompts, news))])
    for i, (p, n) in enumerate(zip(prompts, news)):
        ref = np.asarray(llama_fast_generate(
            cfg, sparams, p[None], max_new_tokens=n, max_out_tokens=128,
            kv_cache_bits=kv_bits)[0])
        np.testing.assert_array_equal(res[i].tokens(), ref)
