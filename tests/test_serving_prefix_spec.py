"""Prefix-sharing paged KV cache + speculative decoding (ISSUE 9).

Covers the two serving optimizations end to end:

- multi-query paged attention (the speculative verify kernel variant):
  per-row position masking vs stepping the single-query kernel;
- prefix index + refcounted allocator: hash-chain matching, COW
  partial-page sharing, eviction under pool pressure, the refcount-0
  sweep (leak fence);
- engine admission through the prefix cache reproduces the unshared
  engine token-for-token (incl. the COW mid-page divergence case and
  shared-page slot reuse with int8 scale pools);
- speculative greedy decoding is token-for-token identical to the
  plain engine for BOTH families and both drafters (n-gram + model);
  sampled requests fall back to the normal tick.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu.serving as serving
from deepspeed_tpu.serving.paged_cache import PagedCacheSpec, PagedKVCache
from deepspeed_tpu.serving.drafter import NGramDrafter, ModelDrafter


@pytest.fixture
def rs():
    return np.random.RandomState(0)


# ------------------------------------------------- multi-query kernel


def _mq_vs_stepped(rs, quantized, R=1):
    """MQ kernel vs the single-query kernel advanced one position per
    step over the SAME pool (no appends needed: all rows pre-exist)."""
    from deepspeed_tpu.ops.pallas.decode import decode_attention_paged
    Lyr, NB, H, P, D = 2, 9, 2, 16, 32
    B, MAXP, K = 3, 4, 4
    if quantized:
        kp = jnp.asarray(rs.randint(-127, 128, (Lyr, NB, H, P, D)),
                         jnp.int8)
        vp = jnp.asarray(rs.randint(-127, 128, (Lyr, NB, H, P, D)),
                         jnp.int8)
        ks = jnp.asarray(np.abs(rs.randn(Lyr, NB, H, 1, P)) * .01 + 1e-3,
                         jnp.float32)
        vs = jnp.asarray(np.abs(rs.randn(Lyr, NB, H, 1, P)) * .01 + 1e-3,
                         jnp.float32)
        kw = dict(k_scale=ks, v_scale=vs)
    else:
        kp = jnp.asarray(rs.randn(Lyr, NB, H, P, D), jnp.float32) * .3
        vp = jnp.asarray(rs.randn(Lyr, NB, H, P, D), jnp.float32) * .3
        kw = {}
    pt = np.zeros((B, MAXP), np.int32)
    pt[0, :3] = [2, 4, 6]
    pt[1, :4] = [1, 5, 7, 8]
    pt[2, :1] = [3]
    pos = np.array([20, 33, -1], np.int32)      # slot 2 idle
    q = jnp.asarray(rs.randn(B, H, K * R, D), jnp.float32) * .3
    got = decode_attention_paged(q, kp, vp, pos, jnp.asarray(pt), 1,
                                 rows_per_step=R, **kw)
    for step in range(K):
        rows = q[:, :, step * R:(step + 1) * R, :]
        ref = decode_attention_paged(rows, kp, vp, pos + step,
                                     jnp.asarray(pt), 1, **kw)
        for b in range(B):
            if pos[b] < 0:
                np.testing.assert_array_equal(np.asarray(got[b]), 0.0)
                continue
            np.testing.assert_allclose(
                np.asarray(got[b, :, step * R:(step + 1) * R]),
                np.asarray(ref[b]), rtol=2e-5, atol=2e-5)


def test_mq_paged_attention_matches_stepped_fp(rs):
    _mq_vs_stepped(rs, quantized=False)


@pytest.mark.slow
def test_mq_paged_attention_matches_stepped_int8(rs):
    """Slow tier: the fp/GQA kernel pins cover the masking machinery
    fast, and the int8 scale path is driven end-to-end by the int8
    speculative parity tests."""
    _mq_vs_stepped(rs, quantized=True)


def test_mq_paged_attention_matches_stepped_gqa_rows(rs):
    # grouped-query rows per step (the LLaMA verify layout: step-major)
    _mq_vs_stepped(rs, quantized=False, R=2)


@pytest.mark.slow
@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="runs on the chip only (compiled multi-query paged kernel, "
           "interpret off). conftest.py pins the suite to the CPU "
           "backend, so run it through the chip tool without conftest, "
           "in the same call as chip_smoke.py: `python -m pytest "
           "--noconftest -m slow -k real_chip "
           "tests/test_serving_prefix_spec.py`")
def test_decode_attention_multiquery_real_chip_parity(rs):
    """First-real-chip parity for the speculative verify variant of
    ``decode_attention_paged`` with ``interpret=False`` — same layout
    as the fast MQ test, the per-row masking and the widened page
    participation window (`pos + max_step`) lowered through Mosaic."""
    from deepspeed_tpu.ops.pallas.decode import decode_attention_paged
    Lyr, NB, H, P, D = 2, 9, 2, 16, 32
    B, MAXP, K, R = 3, 4, 4, 2
    kp = jnp.asarray(rs.randn(Lyr, NB, H, P, D), jnp.float32) * .3
    vp = jnp.asarray(rs.randn(Lyr, NB, H, P, D), jnp.float32) * .3
    pt = np.zeros((B, MAXP), np.int32)
    pt[0, :3] = [2, 4, 6]
    pt[1, :4] = [1, 5, 7, 8]
    pt[2, :1] = [3]
    pos = np.array([20, 33, -1], np.int32)
    q = jnp.asarray(rs.randn(B, H, K * R, D), jnp.float32) * .3
    got = decode_attention_paged(q, kp, vp, pos, jnp.asarray(pt), 1,
                                 rows_per_step=R, interpret=False)
    for step in range(K):
        rows = q[:, :, step * R:(step + 1) * R, :]
        ref = decode_attention_paged(rows, kp, vp, pos + step,
                                     jnp.asarray(pt), 1,
                                     interpret=False)
        for b in range(B):
            if pos[b] < 0:
                np.testing.assert_array_equal(np.asarray(got[b]), 0.0)
                continue
            np.testing.assert_allclose(
                np.asarray(got[b, :, step * R:(step + 1) * R]),
                np.asarray(ref[b]), rtol=2e-5, atol=2e-5)


# -------------------------------------------------- allocator / index


def _toy_cache(num_blocks=12, page=4, slots=3, maxp=8):
    spec = PagedCacheSpec(n_layers=1, kv_heads=1, head_dim=8,
                          page_size=page, slots=slots,
                          max_pages_per_slot=maxp, num_blocks=num_blocks)
    c = PagedKVCache(spec)
    c.enable_prefix_sharing()
    return c


def test_prefix_index_match_refcount_and_sweep():
    c = _toy_cache()
    total = c.free_pages
    prompt = np.arange(11, dtype=np.int32)          # 2 full pages + 3
    plan = c.admit_prefix(0, prompt, total_tokens=13)
    assert plan.start_pos == 0 and plan.cow is None
    c.register_prefix(0, prompt)
    # identical prompt: both full pages shared + COW on the partial
    plan2 = c.admit_prefix(1, prompt, total_tokens=13)
    assert [b for b in plan2.pages[:2]] == plan.pages[:2]
    assert plan2.cow is not None
    src, dst, r = plan2.cow
    assert src == plan.pages[2] and r == 2      # 3 partial tokens -> 2
    assert plan2.start_pos == 2 * 4 + 2         # always >=1 suffix token
    assert c._refcount[plan.pages[0]] == 2
    c.register_prefix(1, prompt)
    # release decrefs; shared pages stay resident (registered)
    c.release(0)
    assert c._refcount[plan.pages[0]] == 1
    c.release(1)
    assert c._refcount[plan.pages[0]] == 0
    assert c.free_pages < total                 # resident, not free
    assert c.cached_pages > 0
    assert c.available_pages == total
    n = c.sweep_prefix_cache()
    assert n == c.cached_pages + n              # cached drained
    assert c.free_pages == total                # leak fence


def test_prefix_page_content_verified_not_just_hashed():
    c = _toy_cache()
    p1 = np.arange(8, dtype=np.int32)
    plan = c.admit_prefix(0, p1, 10)
    c.register_prefix(0, p1)
    # different first page must NOT match (walk breaks at page 0)
    p2 = p1.copy()
    p2[0] += 1
    m = c.match_prefix(p2)
    assert m.shared_blocks == [] and m.start_pos == 0
    # same first page, different continuation: share page 0 only
    p3 = np.concatenate([p1[:4], p1[4:] + 5]).astype(np.int32)
    m3 = c.match_prefix(p3)
    assert m3.shared_blocks == [plan.pages[0]]


def test_prefix_eviction_under_pool_pressure():
    c = _toy_cache(num_blocks=7, maxp=6)        # 6 allocatable pages
    pa = np.arange(9, dtype=np.int32)
    c.admit_prefix(0, pa, 12)                   # 3 pages
    c.register_prefix(0, pa)
    c.release(0)                                # 3 resident cached
    assert c.cached_pages == 3 and c.free_pages == 3
    # an unrelated request needing 5 pages forces LRU eviction
    pb = (np.arange(17) + 40).astype(np.int32)
    plan = c.admit_prefix(1, pb, 20)
    assert plan is not None and len(plan.pages) == 5
    assert c.prefix_stats["evictions"] >= 2
    # and a request that cannot fit even after eviction is refused
    assert c.admit_prefix(2, pb, 20) is None
    assert c.free_pages + c.cached_pages + 5 == 6   # nothing leaked


# ------------------------------------------------------ engine fixture


def _gpt2_cfg():
    from deepspeed_tpu.models.gpt2 import GPT2Config
    return GPT2Config(vocab_size=256, n_positions=128, n_embd=128,
                      n_layer=2, n_head=4, dtype=jnp.float32,
                      param_dtype=jnp.float32, scan_layers=True)


@pytest.fixture(scope="module")
def gpt2_px():
    """(cfg, params, qparams, make): engines over shared per-geometry
    adapters (compiled programs live on the adapter — tier-1 budget)."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.models.gpt2_inference import (
        convert_gpt2_params, quantize_gpt2_inference_params)
    cfg = _gpt2_cfg()
    params = jax.jit(GPT2LMHeadModel(cfg).init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    qparams = quantize_gpt2_inference_params(
        convert_gpt2_params(params, cfg))
    adapters = {}

    def make(int8=False, **kw):
        sv = {"slots": 2, "page_size": 16, "max_pages_per_slot": 6}
        sv.update(kw.pop("serving", {}))
        key = (int8, tuple(sorted(sv.items())))
        if key not in adapters:
            eng = serving.build_engine(
                "gpt2", cfg, qparams if int8 else params,
                config={"serving": sv})
            adapters[key] = eng.adapter
        return serving.ContinuousBatcher(adapters[key], **kw)

    return cfg, params, qparams, make


# ------------------------------------------------- prefix-sharing e2e


def test_prefix_admission_matches_unshared(rs, gpt2_px):
    _, _, _, make = gpt2_px
    eng = make(prefix_cache=True)
    plain = make()
    pa = rs.randint(0, 256, size=(40,)).astype(np.int32)
    res_a = eng.serve([serving.Request("a", pa, max_new_tokens=10)])
    ref_a = plain.serve([serving.Request("a", pa, max_new_tokens=10)])
    np.testing.assert_array_equal(res_a["a"].tokens(),
                                  ref_a["a"].tokens())
    free_before = eng.cache.free_pages
    # identical prompt: 2 full pages aliased + COW, only suffix pages
    # fresh — and outputs unchanged
    res_b = eng.serve([serving.Request("b", pa, max_new_tokens=10)])
    plain_b = make()
    ref_b = plain_b.serve([serving.Request("b", pa, max_new_tokens=10)])
    np.testing.assert_array_equal(res_b["b"].tokens(),
                                  ref_b["b"].tokens())
    st = eng.cache.prefix_stats
    assert st["hit_pages"] == 2 and st["cow_hits"] == 1
    assert st["cow_rows"] == 7          # 8 partial tokens, 1 left over
    snap = eng.metrics_snapshot()["prefix_cache"]
    assert snap["pages_saved"] == 2
    assert snap["hit_rate"] == pytest.approx(39 / 80)
    # the second admission took only fresh pages for suffix+generation
    assert free_before - eng.cache.free_pages <= 0  # B reused resident
    #   pages then released; resident set unchanged or larger


def test_prefix_cow_divergence_mid_page(rs, gpt2_px):
    """Two requests share 36 of 40 tokens (divergence INSIDE the 3rd
    page): the sharer must COW the partial page and reproduce its solo
    output exactly."""
    _, _, _, make = gpt2_px
    eng = make(prefix_cache=True)
    pa = rs.randint(0, 256, size=(40,)).astype(np.int32)
    pc = pa.copy()
    pc[36:] = (pc[36:] + 7) % 256
    eng.serve([serving.Request("a", pa, max_new_tokens=10)])
    res_c = eng.serve([serving.Request("c", pc, max_new_tokens=10)])
    plain = make()
    ref_c = plain.serve([serving.Request("c", pc, max_new_tokens=10)])
    np.testing.assert_array_equal(res_c["c"].tokens(),
                                  ref_c["c"].tokens())
    st = eng.cache.prefix_stats
    assert st["cow_hits"] == 1 and st["cow_rows"] == 4   # matched 36..39


@pytest.mark.parametrize("kv_bits", [
    # the fp-pool variant rides the slow tier: the int8 variant covers
    # the same shared-page lifecycle PLUS the scale pools, and the
    # fp surface is pinned fast by test_prefix_admission_matches_unshared
    pytest.param(0, marks=pytest.mark.slow),
    8,
])
def test_prefix_shared_slot_reuse_no_stale_kv(rs, kv_bits, gpt2_px):
    """Two concurrent requests share a prefix; the first finishes and
    its slot is IMMEDIATELY reused by an unrelated longer request; the
    survivor's continuation (tokens + final logits) must match a solo
    run — shared pages must not be reaped or overwritten while the
    survivor still holds a reference (incl. int8 scale pools)."""
    _, _, _, make = gpt2_px
    sv = {"kv_cache_bits": kv_bits} if kv_bits else {}
    eng = make(int8=bool(kv_bits), serving=sv, prefix_cache=True)
    shared = rs.randint(0, 256, size=(36,)).astype(np.int32)
    pz = rs.randint(0, 256, size=(60,)).astype(np.int32)
    # short sharer finishes first; long sharer keeps decoding; then an
    # unrelated request takes the freed slot while the survivor runs
    res = eng.serve([
        serving.Request("short", shared, max_new_tokens=2),
        serving.Request("long", shared, max_new_tokens=10),
        serving.Request("other", pz, max_new_tokens=8),
    ])
    solo = make(int8=bool(kv_bits), serving=sv, prefix_cache=True)
    ref = solo.serve([serving.Request("long", shared,
                                      max_new_tokens=10)])
    np.testing.assert_array_equal(res["long"].tokens(),
                                  ref["long"].tokens())


def test_prefix_cow_disabled_page_aligned_only(rs, gpt2_px):
    """cow: false shares only FULL pages — the cache never matches
    partial pages (no phantom cow_hits stats, no device page copy) and
    outputs are unchanged."""
    _, _, _, make = gpt2_px
    eng = make(prefix_cache=True, prefix_cow=False)
    pa = rs.randint(0, 256, size=(40,)).astype(np.int32)
    eng.serve([serving.Request("a", pa, max_new_tokens=10)])
    res = eng.serve([serving.Request("b", pa, max_new_tokens=10)])
    ref = make().serve([serving.Request("b", pa, max_new_tokens=10)])
    np.testing.assert_array_equal(res["b"].tokens(), ref["b"].tokens())
    st = eng.cache.prefix_stats
    assert st["cow_hits"] == 0 and st["cow_rows"] == 0
    assert st["hit_pages"] == 2     # page-aligned share still happened


def test_prefix_pool_occupancy_returns_to_baseline(rs, gpt2_px):
    """Leak fence (ISSUE 9 satellite): a full hot-prefix workload
    drains, every refcount returns to 0, and the refcount-0 sweep
    restores the whole pool to the free list."""
    _, _, _, make = gpt2_px
    eng = make(prefix_cache=True)
    base = eng.cache.free_pages
    sysp = rs.randint(0, 256, size=(36,)).astype(np.int32)
    reqs = [serving.Request(i, np.concatenate(
        [sysp, rs.randint(0, 256, size=(4,)).astype(np.int32)]),
        max_new_tokens=6) for i in range(6)]
    res = eng.serve(reqs)
    assert len(res) == 6
    assert all(not s.active for s in eng.slots)
    assert int(eng.cache._refcount.sum()) == 0
    assert eng.cache.free_pages + eng.cache.cached_pages == base
    eng.cache.sweep_prefix_cache()
    assert eng.cache.free_pages == base
    assert eng.metrics_snapshot()["prefix_cache"]["hit_rate"] > 0.5


# --------------------------------------------------- speculative e2e


def test_spec_greedy_parity_gpt2(rs, gpt2_px):
    _, _, _, make = gpt2_px
    eng = make(drafter=NGramDrafter(2), spec_tokens=3)
    plain = make()
    lens, news = (7, 19, 30), (24, 9, 17)
    prompts = [rs.randint(0, 256, size=(s,)).astype(np.int32)
               for s in lens]
    res = eng.serve([serving.Request(i, p, max_new_tokens=n)
                     for i, (p, n) in enumerate(zip(prompts, news))])
    ref = plain.serve([serving.Request(i, p, max_new_tokens=n)
                       for i, (p, n) in enumerate(zip(prompts, news))])
    for i in range(3):
        np.testing.assert_array_equal(res[i].tokens(), ref[i].tokens())
    assert eng.stats["spec_rounds"] > 0
    snap = eng.metrics_snapshot()["speculative"]
    assert snap["proposed"] > 0 and 0.0 <= snap["accept_rate"] <= 1.0


def test_spec_greedy_parity_gpt2_eos(rs, gpt2_px):
    """EOS inside a committed window must stop at its FIRST occurrence
    exactly like the plain engine (commits past EOS discarded)."""
    _, _, _, make = gpt2_px
    plain = make()
    p = rs.randint(0, 256, size=(9,)).astype(np.int32)
    full = plain.serve([serving.Request("r", p, max_new_tokens=16)])["r"]
    eos = int(full.generated[5])
    ref = make().serve([serving.Request("r", p, max_new_tokens=16,
                                        eos_token_id=eos)])["r"]
    got = make(drafter=NGramDrafter(2), spec_tokens=3).serve(
        [serving.Request("r", p, max_new_tokens=16,
                         eos_token_id=eos)])["r"]
    assert got.finish_reason == ref.finish_reason
    assert got.generated == ref.generated


def test_spec_greedy_parity_gpt2_int8(rs, gpt2_px):
    _, _, _, make = gpt2_px
    sv = {"kv_cache_bits": 8}
    eng = make(int8=True, serving=sv, drafter=NGramDrafter(2),
               spec_tokens=3)
    plain = make(int8=True, serving=sv)
    p = rs.randint(0, 256, size=(13,)).astype(np.int32)
    res = eng.serve([serving.Request(0, p, max_new_tokens=20)])
    ref = plain.serve([serving.Request(0, p, max_new_tokens=20)])
    np.testing.assert_array_equal(res[0].tokens(), ref[0].tokens())


@pytest.fixture(scope="module")
def gpt2_drafter():
    """(dcfg, dparams, adapter): the small drafter model shared by the
    model-drafter tests (compiled programs live on the adapter —
    tier-1 budget)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.serving.adapters import GPT2ServingAdapter
    from deepspeed_tpu.serving.paged_cache import PagedCacheSpec
    dcfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=64,
                      n_layer=1, n_head=2, dtype=jnp.float32,
                      param_dtype=jnp.float32, scan_layers=True)
    dparams = jax.jit(GPT2LMHeadModel(dcfg).init)(
        jax.random.PRNGKey(1), np.zeros((1, 8), np.int32))["params"]
    dspec = PagedCacheSpec(n_layers=1, kv_heads=2, head_dim=32,
                           page_size=16, max_pages_per_slot=6, slots=2,
                           dtype=jnp.float32)
    return dcfg, dparams, GPT2ServingAdapter(dcfg, dparams, dspec)


def test_spec_model_drafter_parity(rs, gpt2_px, gpt2_drafter):
    """A REAL (smaller) drafter model through its own paged cache:
    outputs identical, drafter rollback tracked by pointer moves. The
    target rides the module's shared adapter; build_engine's model-
    drafter wiring is asserted separately (construction is compile-
    free) to keep the compile budget on the drafter alone."""
    cfg, params, _, make = gpt2_px
    dcfg, dparams, dadapter = gpt2_drafter
    built = serving.build_engine(
        "gpt2", cfg, params,
        config={"serving": {"slots": 2, "page_size": 16,
                            "max_pages_per_slot": 6,
                            "speculative": {"tokens": 3,
                                            "drafter": "model"}}},
        drafter_model_config=dcfg, drafter_params=dparams)
    assert isinstance(built.drafter, ModelDrafter)
    assert built.drafter.cache.num_blocks == 2 * 6 + 1  # fully provisioned
    eng = make(drafter=ModelDrafter(dadapter), spec_tokens=3)
    plain = make()
    lens, news = (7, 19), (18, 9)
    prompts = [rs.randint(0, 256, size=(s,)).astype(np.int32)
               for s in lens]
    res = eng.serve([serving.Request(i, p, max_new_tokens=n)
                     for i, (p, n) in enumerate(zip(prompts, news))])
    ref = plain.serve([serving.Request(i, p, max_new_tokens=n)
                       for i, (p, n) in enumerate(zip(prompts, news))])
    for i in range(2):
        np.testing.assert_array_equal(res[i].tokens(), ref[i].tokens())
    # drafter cache drained with the requests
    assert all(p == -1 for p in eng.drafter.pos)
    assert eng.drafter.cache.free_pages == \
        eng.drafter.cache.num_blocks - 1


def test_spec_drafter_realigns_after_plain_tick_fallback(rs, gpt2_px,
                                                         gpt2_drafter):
    """Plain-tick fallbacks (here: a sampled sibling) commit tokens the
    drafter never drafted; observe_plain must teacher-force them
    through the ModelDrafter's own cache so its pos/KV stay aligned and
    spec rounds resume cleanly once the sibling drains — without it the
    drafter attends unwritten rows and accept rate silently collapses
    for the rest of the request."""
    _, _, _, make = gpt2_px
    _, _, dadapter = gpt2_drafter
    eng = make(drafter=ModelDrafter(dadapter), spec_tokens=3)
    p_g = rs.randint(0, 256, size=(9,)).astype(np.int32)
    p_s = rs.randint(0, 256, size=(12,)).astype(np.int32)
    eng.submit(serving.Request("g", p_g, max_new_tokens=12))
    eng.submit(serving.Request("s", p_s, max_new_tokens=4,
                               temperature=0.7))
    done = {}
    for _ in range(64):
        for r in eng.step():
            done[r.rid] = r
        g_slot = next((i for i, s in enumerate(eng.slots)
                       if s.active and s.request.rid == "g"), None)
        if g_slot is not None:
            assert eng.drafter.pos[g_slot] == eng.slots[g_slot].pos
        if len(done) == 2:
            break
    assert len(done) == 2
    # the sampled sibling forced plain ticks, then spec rounds resumed
    assert eng.stats["spec_rounds"] > 0
    assert eng.stats["ticks"] > eng.stats["spec_rounds"]
    ref = make().serve([serving.Request("g", p_g, max_new_tokens=12)])
    np.testing.assert_array_equal(done["g"].tokens(), ref["g"].tokens())


def test_spec_verify_window_honors_tokens(gpt2_px):
    """The verify window is exactly tokens+1 in steady state — no pow2
    rounding-down of the configured K — and pow2-clamps only when the
    min remaining budget is smaller (compile-free white-box check)."""
    _, _, _, make = gpt2_px
    eng = make(spec_tokens=4)
    eng.slots[0].request = serving.Request(
        0, np.arange(4, dtype=np.int32), max_new_tokens=20)
    eng.slots[0].pos = 4
    assert eng._pick_verify_rows() == 5          # exact tokens + 1
    eng.slots[0].request.generated = [1] * 17    # rem = 3 clamps
    assert eng._pick_verify_rows() == 2
    eng.slots[0].request.generated = [1] * 19    # rem = 1: no window
    assert eng._pick_verify_rows() == 1


def test_spec_llama_parity_both_storages(rs):
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.models.llama_inference import \
        random_int8_serving_params
    cfg = LlamaConfig(vocab_size=256, hidden_size=128, n_layers=2,
                      n_heads=4, n_kv_heads=2, intermediate_size=256,
                      max_seq_len=128, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    sparams = random_int8_serving_params(cfg)
    # int8 KV fast; the fp-cache variant's unique surface (GQA rows
    # through the fp MQ kernel) is pinned by the fast kernel test
    for kv_bits in (8,):
        eng = serving.build_engine(
            "llama", cfg, sparams,
            config={"serving": {"slots": 2, "page_size": 16,
                                "max_pages_per_slot": 6,
                                "kv_cache_bits": kv_bits,
                                "speculative": {"tokens": 3}}})
        plain = serving.ContinuousBatcher(eng.adapter)
        p = rs.randint(0, 256, size=(21,)).astype(np.int32)
        res = eng.serve([serving.Request(0, p, max_new_tokens=14)])
        ref = plain.serve([serving.Request(0, p, max_new_tokens=14)])
        np.testing.assert_array_equal(res[0].tokens(), ref[0].tokens())


def test_prefix_llama_parity(rs):
    """LLaMA prefix-cache hit parity: the suffix prefill's GQA prefix
    K/V gather + RoPE at absolute positions (the LLaMA twin of the
    GPT-2 prefix e2e tests) — a second request sharing 2 full pages +
    a COW partial page decodes token-for-token like an unshared run."""
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.models.llama_inference import \
        random_int8_serving_params
    cfg = LlamaConfig(vocab_size=256, hidden_size=128, n_layers=2,
                      n_heads=4, n_kv_heads=2, intermediate_size=256,
                      max_seq_len=128, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    sparams = random_int8_serving_params(cfg)
    eng = serving.build_engine(
        "llama", cfg, sparams,
        config={"serving": {"slots": 2, "page_size": 16,
                            "max_pages_per_slot": 6,
                            "prefix_cache": {"cow": True}}})
    plain = serving.ContinuousBatcher(eng.adapter)
    shared = rs.randint(0, 256, size=(40,)).astype(np.int32)
    pa = np.concatenate([shared, rs.randint(0, 256, size=(3,))
                         .astype(np.int32)])
    pb = np.concatenate([shared, rs.randint(0, 256, size=(3,))
                         .astype(np.int32)])
    res = eng.serve([serving.Request("a", pa, max_new_tokens=10)])
    ref = plain.serve([serving.Request("a", pa, max_new_tokens=10)])
    np.testing.assert_array_equal(res["a"].tokens(), ref["a"].tokens())
    res_b = eng.serve([serving.Request("b", pb, max_new_tokens=10)])
    ref_b = plain.serve([serving.Request("b", pb, max_new_tokens=10)])
    np.testing.assert_array_equal(res_b["b"].tokens(),
                                  ref_b["b"].tokens())
    assert eng.cache.prefix_stats["hit_pages"] >= 2
    assert eng.cache.prefix_stats["cow_hits"] >= 1


@pytest.mark.slow
def test_spec_llama_parity_fp_cache(rs):
    """fp-cache LLaMA spec parity (slow tier: the int8 sibling keeps
    the whole LLaMA spec stack in tier-1; this pins the fp MQ kernel
    e2e)."""
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.models.llama_inference import \
        random_int8_serving_params
    cfg = LlamaConfig(vocab_size=256, hidden_size=128, n_layers=2,
                      n_heads=4, n_kv_heads=2, intermediate_size=256,
                      max_seq_len=128, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    sparams = random_int8_serving_params(cfg)
    eng = serving.build_engine(
        "llama", cfg, sparams,
        config={"serving": {"slots": 2, "page_size": 16,
                            "max_pages_per_slot": 6,
                            "speculative": {"tokens": 3}}})
    plain = serving.ContinuousBatcher(eng.adapter)
    p = rs.randint(0, 256, size=(21,)).astype(np.int32)
    res = eng.serve([serving.Request(0, p, max_new_tokens=14)])
    ref = plain.serve([serving.Request(0, p, max_new_tokens=14)])
    np.testing.assert_array_equal(res[0].tokens(), ref[0].tokens())


def test_spec_temperature_falls_back_to_plain_tick(rs, gpt2_px):
    """Sampled requests make every decode step take the normal tick
    (greedy-only verify): same rng stream => identical outputs."""
    _, _, _, make = gpt2_px
    p = rs.randint(0, 256, size=(11,)).astype(np.int32)
    req = lambda: serving.Request(0, p, max_new_tokens=8,  # noqa: E731
                                  temperature=0.8)
    eng = make(drafter=NGramDrafter(2), spec_tokens=3)
    plain = make()
    res = eng.serve([req()])
    ref = plain.serve([req()])
    np.testing.assert_array_equal(res[0].tokens(), ref[0].tokens())
    assert eng.stats["spec_rounds"] == 0


def test_ngram_drafter_propose():
    d = NGramDrafter(1, ngram_max=3, ngram_min=1)
    d.admit(0, np.array([5, 6, 7, 5, 6], np.int32), 7, 32)
    # history ...5 6 7 5 6 7 — trailing [6, 7] matched at 1: continue 5 6
    np.testing.assert_array_equal(d.draft([0], 2)[0], [5, 6])
    d.commit(0, [9], 0, 9)               # history now ends ... 7 9: no
    np.testing.assert_array_equal(      # n-gram hit -> repeat-last
        d.draft([0], 3)[0], [9, 9, 9])
    # plain-tick realignment: committed tokens append to the history
    d.observe_plain([0], np.array([[9], [1]], np.int32),
                    np.array([[1], [2]], np.int32))
    np.testing.assert_array_equal(d._hist[0][-2:], [1, 2])


def test_serving_subblock_config_validation():
    from deepspeed_tpu.config.config import (ServingConfig,
                                             DeepSpeedConfigError)
    sc = ServingConfig({"serving": {
        "prefix_cache": {}, "speculative": {"tokens": 4}}})
    assert sc.prefix_cache.enabled and sc.prefix_cache.cow
    assert sc.speculative.enabled and sc.speculative.tokens == 4
    assert sc.speculative.drafter == "ngram"
    off = ServingConfig({"serving": {}})
    assert not off.prefix_cache.enabled and not off.speculative.enabled
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig({"serving": {"speculative": {"tokens": 0}}})
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig({"serving": {"speculative": {"drafter": "oracle"}}})
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig({"serving": {"speculative": {
            "ngram_max": 1, "ngram_min": 2}}})
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig({"serving": {"prefix_cache": "yes"}})
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig({"serving": {"speculative": 8}})
    with pytest.raises(ValueError, match="drafter_model_config"):
        from deepspeed_tpu.models.gpt2 import GPT2Config
        serving.build_engine(
            "gpt2", _gpt2_cfg(), {},
            config={"serving": {"speculative": {"drafter": "model"}}})
