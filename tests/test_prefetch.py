"""ZeRO-3 layer-wise parameter-gather prefetch pipeline tests
(parallel/prefetch.py + the engine's ``stage3_prefetch`` train path).

The numerics contract: the double-buffered per-layer gather scan (and
its reverse re-gather + reduce-scatter backward) must reproduce the
fused GSPMD stage-3 path at fp32 rounding tolerance — losses AND
updated (sharded-at-rest) params, across layer counts, mesh shapes,
gather modes, and gradient accumulation. Plus: the functional
``prefetch_apply`` twin pins to ``model.apply`` exactly, the gating
falls back where the pipeline can't run, and the live gathered-param
accounting (the ``stage3_max_live_parameters`` observable) reports the
structural 2-layer double buffer.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu as dstpu
from deepspeed_tpu.parallel import prefetch
from jax import shard_map
from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

N = 8


def _mesh():
    devs = jax.devices()
    assert len(devs) >= N
    return Mesh(np.asarray(devs[:N]), ("data",))


# ---------------------------------------------------------------------------
# plan + packing units
# ---------------------------------------------------------------------------

def test_plan_from_specs():
    leaves = [jnp.zeros((4, 16, 32)), jnp.zeros((4, 8)), jnp.zeros((3,))]
    specs = [P(None, None, "data"), P(None, "data"), P()]
    plan = prefetch.plan_from_specs(leaves, specs, "data", N)
    assert plan == [(2, 4), (1, 1), None]


def test_build_layer_plan_rejects_layer_dim_shard():
    leaves = [jnp.zeros((8, 4))]
    with pytest.raises(AssertionError):
        prefetch.build_layer_plan(leaves, [(0, 1)], N)


def test_chunk_major_roundtrip():
    full = jnp.arange(2 * 24).reshape(2, 24).astype(jnp.float32)
    chunks = prefetch._chunks_from_full(full, 1, N)
    assert chunks.shape == (N, 2, 3)
    back = prefetch._full_from_chunks(chunks, 1)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(full))


@pytest.mark.parametrize("mode", ["ring", "fused"])
def test_gather_scatter_leaf_roundtrip(mode):
    """gather_leaf rebuilds the full leaf from per-device shards, and
    scatter_grad of a replicated cotangent returns each device n x its
    own chunk (the SUM-over-axis contract)."""
    mesh = _mesh()
    full = jnp.asarray(
        np.random.RandomState(0).randn(6, N * 4).astype(np.float32))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=P(None, "data"),
                       out_specs=(P(None, None, "data"), P(None, "data")),
                       check_vma=False)
    def run(shard):
        g = prefetch.gather_leaf(shard, (1, 4), "data", N, mode)
        s = prefetch.scatter_grad(g, (1, 4), "data", N, mode)
        return g[:, :, None], s

    gathered, scattered = run(full)
    for dev in range(N):
        np.testing.assert_allclose(np.asarray(gathered[:, :, dev]),
                                   np.asarray(full), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(scattered),
                               np.asarray(full) * N, rtol=1e-5)


# ---------------------------------------------------------------------------
# the prefetched scan vs a plain scan (grads included)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["ring", "fused"])
def test_prefetched_scan_matches_plain_scan(mode):
    L, D = 3, 16
    mesh = _mesh()
    r = np.random.RandomState(0)
    W = jnp.asarray(r.randn(L, D, D).astype(np.float32)) * 0.3
    B = jnp.asarray(r.randn(L, D).astype(np.float32)) * 0.1
    x0 = jnp.asarray(r.randn(4, D).astype(np.float32))

    def body(x, lt):
        return jnp.tanh(x @ lt["w"] + lt["b"])

    def ref_loss(params, x):
        def step(c, wb):
            return body(c, {"w": wb[0], "b": wb[1]}), None
        y, _ = jax.lax.scan(step, x, (params["w"], params["b"]))
        return jnp.sum(y ** 2)

    ref_g = jax.grad(ref_loss)({"w": W, "b": B}, x0)
    plan = [None, (2, D // N)]        # leaves order: b, w

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({"b": P(), "w": P(None, None, "data")}, P()),
        out_specs=(P(), {"b": P(), "w": P(None, None, "data")}),
        check_vma=False)
    def run(shards, x):
        sfn = prefetch.make_prefetched_scan(body, plan, "data", N,
                                            mode=mode)
        loss, g = jax.value_and_grad(
            lambda sh: jnp.sum(sfn(x, sh) ** 2))(shards)
        return loss, g

    loss, g = run({"w": W, "b": B}, x0)
    np.testing.assert_allclose(float(loss),
                               float(ref_loss({"w": W, "b": B}, x0)),
                               rtol=1e-5)
    # x replicated here, so every device computed the full loss: sharded
    # leaves come back as the SUM over the axis (N x), replicated local
    np.testing.assert_allclose(np.asarray(g["w"]),
                               np.asarray(ref_g["w"]) * N,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g["b"]), np.asarray(ref_g["b"]),
                               rtol=1e-4, atol=1e-5)


def test_prefetched_scan_all_replicated_degenerate():
    """Persistence threshold can leave every layer leaf replicated — the
    scan must degrade to a plain gather-free scan with local grads."""
    L, D = 2, 8
    mesh = _mesh()
    r = np.random.RandomState(1)
    W = jnp.asarray(r.randn(L, D, D).astype(np.float32)) * 0.3
    x0 = jnp.asarray(r.randn(2, D).astype(np.float32))

    def body(x, lt):
        return jnp.tanh(x @ lt["w"])

    def ref_loss(w, x):
        y, _ = jax.lax.scan(lambda c, wi: (body(c, {"w": wi}), None), x, w)
        return jnp.sum(y ** 2)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def run(w, x):
        sfn = prefetch.make_prefetched_scan(body, [None], "data", N)
        return jax.value_and_grad(
            lambda sh: jnp.sum(sfn(x, sh) ** 2))({"w": w})

    loss, g = run(W, x0)
    np.testing.assert_allclose(float(loss), float(ref_loss(W, x0)),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g["w"]),
                               np.asarray(jax.grad(ref_loss)(W, x0)),
                               rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# the functional model twin
# ---------------------------------------------------------------------------

def _naive_scan(body, x, h):
    def step(c, lp):
        return body(c, lp), None
    y, _ = jax.lax.scan(step, x, h)
    return y


@pytest.mark.parametrize("tie,chunk", [(True, 0), (False, 0), (True, 16)])
def test_prefetch_apply_matches_model_apply(tie, chunk):
    cfg = GPT2Config(vocab_size=512, n_positions=128, n_embd=64, n_layer=2,
                     n_head=2, dtype=jnp.float32, param_dtype=jnp.float32,
                     scan_layers=True, tie_word_embeddings=tie,
                     loss_chunk=chunk)
    model = GPT2LMHeadModel(cfg)
    ids = np.random.RandomState(0).randint(0, 512, (2, 32)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    labels = ids if chunk else None
    ref = model.apply({"params": params}, ids, labels=labels)
    got = model.prefetch_apply(params, ids, _naive_scan, labels=labels)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-6, atol=1e-6)
    assert model.prefetch_layer_subtree == "h"


def test_prefetch_contract_gated_off():
    # unrolled layers / MoE / dropout cannot offer the layered contract
    assert GPT2LMHeadModel(GPT2Config(scan_layers=False)) \
        .prefetch_layer_subtree is None
    assert GPT2LMHeadModel(GPT2Config(moe_experts=4)) \
        .prefetch_layer_subtree is None
    assert GPT2LMHeadModel(GPT2Config(dropout=0.1)) \
        .prefetch_layer_subtree is None


# ---------------------------------------------------------------------------
# engine integration: stage3_prefetch == fused GSPMD stage 3
# ---------------------------------------------------------------------------

def _gpt2_tiny(n_layer=2, **kw):
    base = dict(vocab_size=512, n_positions=64, n_embd=64, n_layer=n_layer,
                n_head=2, dtype=jnp.float32, param_dtype=jnp.float32,
                scan_layers=True)
    base.update(kw)
    return GPT2Config(**base)


def _train(prefetch_on, data=N, n_layer=2, steps=3, gas=1, mode="ring",
           optimizer=None, bf16=False, model=None, cm=None):
    cfg = {
        "train_batch_size": 8 * gas,
        "gradient_accumulation_steps": gas,
        "zero_optimization": {"stage": 3, "stage3_prefetch": prefetch_on,
                              "stage3_prefetch_gather": mode,
                              "stage3_param_persistence_threshold": 0,
                              **({"collective_matmul": cm} if cm else {})},
        "optimizer": optimizer or {"type": "AdamW",
                                   "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }
    if bf16:
        cfg["bf16"] = {"enabled": True}
        cfg["data_types"] = {"grad_dtype": "bf16"}
    mesh = make_mesh(MeshConfig(data=data), devices=jax.devices()[:data])
    model = model if model is not None \
        else GPT2LMHeadModel(_gpt2_tiny(n_layer, dtype=(
            jnp.bfloat16 if bf16 else jnp.float32)))
    engine, _, _, _ = dstpu.initialize(config=cfg, model=model, mesh=mesh)
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 512, (8 * gas, 64)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(steps)]
    params = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32),
                                    engine.state.params)
    return engine, losses, params


_BASELINE = {}


def _fused_baseline(data=N, n_layer=2, gas=1, bf16=False):
    key = (data, n_layer, gas, bf16)
    if key not in _BASELINE:
        eng, losses, params = _train(False, data=data, n_layer=n_layer,
                                     gas=gas, bf16=bf16)
        assert not eng._prefetch_active()
        _BASELINE[key] = (losses, params)
    return _BASELINE[key]


def _assert_matches(got, want, rtol=2e-5, atol=1e-5):
    loss_g, params_g = got
    loss_w, params_w = want
    np.testing.assert_allclose(loss_g, loss_w, rtol=rtol)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(params_g),
            jax.tree_util.tree_leaves_with_path(params_w)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(pa))


def test_engine_prefetch_matches_fused_dp8():
    loss_b, params_b = _fused_baseline()
    eng, loss_p, params_p = _train(True)
    assert eng._prefetch_active()
    _assert_matches((loss_p, params_p), (loss_b, params_b))
    # the stage3_max_live_parameters observable: exactly the structural
    # 2-layer double buffer + the step-persistent outer gathers
    stats = eng.prefetch_live_param_stats()
    leaves = jax.tree_util.tree_leaves_with_path(params_p)
    h_elems = sum(int(np.prod(l.shape[1:])) for p, l in leaves
                  if "h" == getattr(p[0], "key", None))
    outer_elems = sum(int(np.prod(l.shape)) for p, l in leaves
                      if getattr(p[0], "key", None) != "h")
    assert stats["layers"] == 2
    assert stats["live_param_elements"] == 2 * h_elems + outer_elems
    from deepspeed_tpu.utils import memory as memory_lib
    assert memory_lib.live_gathered_param_bytes() == \
        stats["live_param_bytes"]


def test_engine_prefetch_fused_matmul_matches_ring_dp8():
    """ISSUE 8 engine-parity pin: ``stage3_prefetch_gather:
    fused_matmul`` — the dominant projection kernels streamed through
    the tile-granular fused all-gather+matmul / matmul+reduce-scatter
    path — reproduces the fused-GSPMD baseline (and hence ring mode,
    pinned against the same baseline above) to fp32 rounding: losses
    AND updated sharded-at-rest params over 3 Adam steps."""
    loss_b, params_b = _fused_baseline()
    eng, loss_p, params_p = _train(True, mode="fused_matmul",
                                   cm={"backend": "lax",
                                       "min_shard_bytes": 0})
    assert eng._prefetch_active()
    stats = eng.prefetch_live_param_stats()
    # the 4 projection kernels (c_attn/c_proj/c_fc/c_proj) stream;
    # their full weights never materialize in the live window
    assert stats["fused_leaves_per_layer"] == 4
    assert stats["fused_stream_bytes"] > 0
    _assert_matches((loss_p, params_p), (loss_b, params_b))


def test_engine_fused_matmul_below_threshold_falls_back_to_ring():
    """min_shard_bytes gating: when no layer leaf qualifies (the tiny
    model's shards are far below the default 64 KiB threshold) the
    mode degrades to the packed ring gather — same numerics, fallback
    logged, zero fused leaves in the stats."""
    loss_b, params_b = _fused_baseline()
    eng, loss_p, params_p = _train(True, mode="fused_matmul")
    assert eng._prefetch_active()
    assert eng.prefetch_live_param_stats()["fused_leaves_per_layer"] == 0
    _assert_matches((loss_p, params_p), (loss_b, params_b))


@pytest.mark.slow
def test_engine_prefetch_matches_fused_dp2_l3_fused_gather():
    """Different mesh shape, odd layer count, fused-collective mode
    (slow: the dp8 ring test is the tier-1 engine-parity pin; this
    variant re-pays two full engine compiles for mesh/mode coverage)."""
    loss_b, params_b = _fused_baseline(data=2, n_layer=3)
    eng, loss_p, params_p = _train(True, data=2, n_layer=3, mode="fused")
    assert eng._prefetch_active()
    _assert_matches((loss_p, params_p), (loss_b, params_b))


@pytest.mark.slow
def test_engine_prefetch_matches_fused_gas2():
    """Gradient accumulation: sharded grads accumulate in shard space
    across microbatches (per-micro reduce-scatter inside the scan)."""
    loss_b, params_b = _fused_baseline(gas=2)
    eng, loss_p, params_p = _train(True, gas=2)
    assert eng._prefetch_active()
    _assert_matches((loss_p, params_p), (loss_b, params_b))


@pytest.mark.slow
def test_engine_prefetch_bf16_grads_trains():
    """grad_dtype=bf16 (the headline-bench recipe): gathers move bf16
    bytes, the step stays finite and close to the fused bf16 path."""
    loss_b, _ = _fused_baseline(bf16=True)
    eng, loss_p, _ = _train(True, bf16=True)
    assert eng._prefetch_active()
    assert np.isfinite(loss_p).all()
    np.testing.assert_allclose(loss_p, loss_b, rtol=5e-2)


@pytest.mark.slow
def test_engine_fused_matmul_bf16_grads_trains():
    """fused_matmul under grad_dtype=bf16 — the configuration where
    fused-leaf dW comes back in the PARAM dtype (one bf16 rounding of
    the kernel's fp32 accumulation; make_prefetched_scan docstring):
    the step stays finite and tracks the fused bf16 baseline."""
    loss_b, _ = _fused_baseline(bf16=True)
    eng, loss_p, _ = _train(True, bf16=True, mode="fused_matmul",
                            cm={"backend": "lax", "min_shard_bytes": 0})
    assert eng._prefetch_active()
    assert eng.prefetch_live_param_stats()["fused_leaves_per_layer"] == 4
    assert np.isfinite(loss_p).all()
    np.testing.assert_allclose(loss_p, loss_b, rtol=5e-2)


def test_engine_prefetch_gating():
    # single-device data axis → nothing sharded, fused path
    eng, losses, _ = _train(True, data=1, steps=1)
    assert not eng._prefetch_active()
    assert np.isfinite(losses).all()
    # LAMB's per-tensor trust ratio is not elementwise → fused fallback
    eng, _, _ = _train(True, steps=1, optimizer={
        "type": "Lamb", "params": {"lr": 1e-3}})
    assert not eng._prefetch_active()
    # a model without the layered contract (unrolled layers) → fallback
    eng, _, _ = _train(True, steps=1, model=GPT2LMHeadModel(
        _gpt2_tiny(scan_layers=False)))
    assert not eng._prefetch_active()


def test_prefetch_config_validation():
    from deepspeed_tpu.config.config import (DeepSpeedConfig,
                                             DeepSpeedConfigError)
    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "zero_optimization": {"stage": 3, "stage3_prefetch": True,
                              "stage3_prefetch_gather": "fused"}},
        world_size=1)
    assert cfg.zero_config.stage3_prefetch
    assert cfg.zero_config.stage3_prefetch_gather == "fused"
    assert "stage3_prefetch" in cfg.zero_config.repr_dict()
    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "zero_optimization": {
            "stage": 3, "stage3_prefetch": True,
            "stage3_prefetch_gather": "fused_matmul",
            "collective_matmul": {"backend": "lax", "tile_m": 64,
                                  "min_shard_bytes": 1024,
                                  "vmem_budget_bytes": 4 << 20}}},
        world_size=1)
    assert cfg.zero_config.stage3_prefetch_gather == "fused_matmul"
    assert cfg.zero_config.collective_matmul_backend == "lax"
    assert cfg.zero_config.collective_matmul_tile_m == 64
    assert cfg.zero_config.collective_matmul_min_shard_bytes == 1024
    assert cfg.zero_config.collective_matmul_vmem_budget_bytes == 4 << 20
    assert cfg.zero_config.repr_dict()["collective_matmul"][
        "backend"] == "lax"
    assert cfg.zero_config.repr_dict()["collective_matmul"][
        "vmem_budget_bytes"] == 4 << 20
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "zero_optimization": {
                             "stage": 3, "stage3_prefetch_gather": "tree"}},
                        world_size=1)
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "zero_optimization": {
                             "stage": 3,
                             "collective_matmul": {"backend": "mosaic"}}},
                        world_size=1)
    # the sub-block must be a dict (a bare backend string is a plausible
    # shorthand mistake), and the numeric knobs are range-checked
    for bad_cm in ("lax",
                   {"min_shard_bytes": -1},
                   {"vmem_budget_bytes": 0}):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig({"train_batch_size": 8,
                             "zero_optimization": {
                                 "stage": 3,
                                 "collective_matmul": bad_cm}},
                            world_size=1)
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "zero_optimization": {"stage": 2,
                                               "stage3_prefetch": True}},
                        world_size=1)
