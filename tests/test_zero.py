"""ZeRO tests — the role of the reference's test_zero.py: every stage
trains, stages agree numerically with stage 0, and state is actually
sharded over the data axis (8 virtual CPU devices)."""

import numpy as np
import jax
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig, DATA_AXIS
from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner, shard_spec_for_leaf
from jax.sharding import PartitionSpec as P

from tests.simple_model import SimpleModel, random_batch, base_config


def make_engine(stage, mesh=None, extra=None, model=None, threshold=0):
    cfg = base_config(train_batch_size=8)
    # tiny test params sit below the default persistence threshold
    # (reference ZERO_PARAM_PERSISTENCE_THRESHOLD) — force sharding
    cfg["zero_optimization"] = {
        "stage": stage, "stage3_param_persistence_threshold": threshold}
    if extra:
        cfg.update(extra)
    mesh = mesh or make_mesh(MeshConfig(data=8))
    engine, _, _, _ = dstpu.initialize(
        config=cfg, model=model or SimpleModel(hidden_dim=32), mesh=mesh)
    return engine


def tiny_gpt2(**kw):
    """A scanned, rematted GPT-2 small enough for the CPU mesh, and a
    batch for it."""
    from deepspeed_tpu.models.gpt2 import gpt2_tiny, GPT2LMHeadModel
    ids = np.random.RandomState(0).randint(0, 512, (8, 64)).astype(np.int32)
    return (GPT2LMHeadModel(gpt2_tiny(scan_layers=True, remat=True, **kw)),
            {"input_ids": ids})


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stage_trains(stage):
    engine = make_engine(stage)
    batch = random_batch(batch_size=8)
    l0 = float(engine.train_batch(batch))
    for _ in range(15):
        l1 = float(engine.train_batch(batch))
    assert l1 < l0, f"stage {stage}: loss did not decrease"


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_stage_matches_stage0(stage):
    """Loss and PARAMETERS after five Adam steps agree with stage 0 on the
    plain MLP. The transformer families are held in
    tests/test_zero_matrix_fp32*.py and tests/test_zero_matrix_bf16*.py by
    one step's GRADIENTS, leaf by leaf, then losses and the gradient norm
    (there Adam turns the rounding noise of the key bias's zero gradient
    into full-size updates, so parameters are no yardstick), and the
    compiled stage-3 step's collectives in tests/test_zero_matrix.py."""
    batch = random_batch(batch_size=8)
    e0, es = make_engine(0), make_engine(stage)
    for _ in range(5):
        l0 = e0.train_batch(batch)
        ls = es.train_batch(batch)
    np.testing.assert_allclose(float(l0), float(ls), rtol=1e-4)
    for a, b in zip(*(jax.tree_util.tree_leaves(jax.device_get(e.state.params))
                      for e in (e0, es))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert e0._gather_edge is None


def test_zero1_opt_state_is_sharded():
    engine = make_engine(1)
    engine.train_batch(random_batch(batch_size=8))
    # the big Dense kernel moments should be sharded over 'data'
    m = engine.state.opt_state["exp_avg"]
    leaves = jax.tree_util.tree_leaves(m)
    sharded = [l for l in leaves
               if any(DATA_AXIS in (ax if isinstance(ax, tuple) else (ax,))
                      for ax in l.sharding.spec if ax is not None)]
    assert sharded, "no optimizer-state leaf is sharded over the data axis"
    # params remain replicated at stage 1
    for p in jax.tree_util.tree_leaves(engine.state.params):
        assert all(ax is None for ax in p.sharding.spec), p.sharding


def test_zero3_params_sharded():
    engine = make_engine(3)
    engine.train_batch(random_batch(batch_size=8))
    leaves = jax.tree_util.tree_leaves(engine.state.params)
    sharded = [l for l in leaves
               if any(ax is not None for ax in l.sharding.spec)]
    assert sharded, "stage 3 should shard parameters at rest"


def test_shard_spec_for_leaf():
    # largest divisible dim gets the data axis
    assert shard_spec_for_leaf((16, 64), 8) == P(None, "data")
    assert shard_spec_for_leaf((64, 16), 8) == P("data", None)
    # indivisible → replicated
    assert shard_spec_for_leaf((3, 5), 8) == P(None, None)
    # respects existing TP axis
    assert shard_spec_for_leaf((64, 64), 8, base_spec=P(None, "model")) == \
        P("data", "model")
    # below persistence threshold → untouched
    assert shard_spec_for_leaf((64,), 8, min_size=1000) == P(None)


@pytest.mark.parametrize("shape,spec", [
    ((48, 6400), P(None, None)),               # a layer's bias: 6,400 < 1e5
    ((48, 1600), P(None, None)),               # LayerNorm scale
    ((48, 1600, 6400), P(None, None, "data")),  # a layer's kernel: sharded
    ((64, 8, 32), P(None, None, "data")),      # never along the layer dim
    ((48,), P(None)),                          # one scalar a layer
])
def test_layer_stacked_leaf_is_judged_per_layer(shape, spec):
    """stage3_param_persistence_threshold is the reference's per-PARAMETER
    rule: a layer-stacked ``[L, ...]`` leaf is compared by one layer's
    elements, and a scan's layer dim is never the sharded one. Judged as
    a whole, ``[48, 6400]`` (307,200 elements) would rest sharded."""
    min_size = 100_000 if shape[0] == 48 else 0
    assert shard_spec_for_leaf(shape, 4, min_size=min_size,
                               layer_stacked=True) == spec
    if shape == (48, 6400):
        assert shard_spec_for_leaf(shape, 4, min_size=min_size) == \
            P(None, "data")


def test_llama_stacked_tree_is_judged_per_layer():
    """LLaMA names its scanned subtree (``layer_stacked_subtree``); with
    it the partitioner keeps every RMSNorm scale whole and shards every
    projection kernel off the layer dim, and the gather edge covers
    exactly the leaves that rest sharded, as one layer's compute spec."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny
    model = LlamaForCausalLM(llama_tiny(n_layers=8))
    assert model.layer_stacked_subtree == "layers"
    shapes = jax.eval_shape(
        lambda r: model.init(r, np.zeros((1, 8), np.int32))["params"],
        jax.random.PRNGKey(0))
    mesh = make_mesh(MeshConfig(data=8))
    hidden = model.config.hidden_size
    part = ZeroPartitioner(mesh, 3, param_persistence_threshold=hidden + 1,
                           layer_stacked_prefixes=("layers",))
    specs = part.param_specs(shapes)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    edge = part.gather_edge(shapes)
    for path, spec in flat:
        keys = tuple(k.key for k in path)
        if keys[0] != "layers":
            continue
        assert spec[0] is None, (keys, spec)
        if keys[-1] == "scale":
            assert DATA_AXIS not in spec and keys not in edge.specs
        else:
            assert DATA_AXIS in spec, (keys, spec)
            assert edge.specs[keys] == P(None, None)   # one layer's slice
    # whole-leaf judgement (no stacked prefix) shards the stacked scales
    whole = ZeroPartitioner(mesh, 3, param_persistence_threshold=hidden + 1)
    assert DATA_AXIS in whole.param_specs(shapes)["layers"]["blk"][
        "input_norm"]["scale"]
    # nothing to gather at stages 0-2 or on a data axis of one
    assert ZeroPartitioner(mesh, 2).gather_edge(shapes) is None
    one = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    assert ZeroPartitioner(one, 3).gather_edge(shapes) is None


def test_checkpoint_with_sharded_biases_loads_into_per_layer_layout(tmp_path):
    """A checkpoint written with the layer-stacked biases sharded at
    rest (as they were while the threshold was compared with the whole
    stack) loads into a layout that keeps them whole: the engine's own
    shardings, the same next loss."""
    model, batch = tiny_gpt2()

    def bias(engine):
        return engine.state.params["h"]["blk"]["mlp"]["c_fc"]["bias"]

    old = make_engine(3, model=model, threshold=0)
    for _ in range(2):
        old.train_batch(batch)
    assert DATA_AXIS in bias(old).sharding.spec
    old.save_checkpoint(str(tmp_path))
    want = float(old.train_batch(batch))

    # over a layer's largest small leaf (c_fc bias, 256), under a kernel
    new = make_engine(3, model=model, threshold=300)
    new.train_batch(batch)                     # builds state and programs
    new.load_checkpoint(str(tmp_path))
    assert DATA_AXIS not in bias(new).sharding.spec
    for moment in ("exp_avg", "exp_avg_sq"):
        leaf = new.state.opt_state[moment]["h"]["blk"]["mlp"]["c_fc"]["bias"]
        assert DATA_AXIS not in leaf.sharding.spec
    kernel = new.state.params["h"]["blk"]["mlp"]["c_fc"]["kernel"]
    assert DATA_AXIS in kernel.sharding.spec
    np.testing.assert_allclose(float(new.train_batch(batch)), want,
                               rtol=1e-5)


def test_partitioner_stage_rules():
    mesh = make_mesh(MeshConfig(data=8))
    params = {"w": np.zeros((64, 32), np.float32), "b": np.zeros((32,), np.float32)}

    z0 = ZeroPartitioner(mesh, 0)
    assert all(all(a is None for a in s)
               for s in jax.tree_util.tree_leaves(
                   z0.param_specs(params),
                   is_leaf=lambda x: isinstance(x, P)))

    z3 = ZeroPartitioner(mesh, 3)
    specs = z3.param_specs(params)
    assert specs["w"] == P("data", None)

    z2 = ZeroPartitioner(mesh, 2)
    # stage 2: params replicated, grads sharded
    assert z2.param_specs(params)["w"] == P(None, None)
    assert z2.grad_specs(params)["w"] == P("data", None)


def test_zero_offload_cpu_optimizer_config():
    engine = make_engine(2, extra={
        "zero_optimization": {"stage": 2,
                              "offload_optimizer": {"device": "cpu"}}})
    assert engine._config.zero_config.offload_optimizer.enabled
    batch = random_batch(batch_size=8)
    l0 = float(engine.train_batch(batch))
    assert np.isfinite(l0)


def test_fully_specified_batch_config_multi_device():
    """Reference-style config with all three batch params + dp=8 mesh
    (regression: pre-config used world_size=1 and failed the triangle)."""
    import deepspeed_tpu as dstpu
    mesh = make_mesh(MeshConfig(data=8))
    cfg = {"train_batch_size": 16, "train_micro_batch_size_per_gpu": 1,
           "gradient_accumulation_steps": 2,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}}
    engine, _, _, _ = dstpu.initialize(config=cfg, model=SimpleModel(),
                                       mesh=mesh)
    batch = random_batch(batch_size=16)
    assert np.isfinite(float(engine.train_batch(batch)))


def test_mesh_from_config_section():
    """Mesh built from the json 'mesh' section when none is passed."""
    import deepspeed_tpu as dstpu
    cfg = {"train_batch_size": 8, "mesh": {"data": 4, "model": 2},
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}}
    engine, _, _, _ = dstpu.initialize(config=cfg, model=SimpleModel(hidden_dim=32))
    assert engine.mesh.shape["data"] == 4 and engine.mesh.shape["model"] == 2
    assert np.isfinite(float(engine.train_batch(random_batch(batch_size=8))))


@pytest.mark.slow
def test_stage3_persistence_threshold_sweep():
    """SURVEY §7's stage-3 'hard part' knob: sweeping
    stage3_param_persistence_threshold moves leaves between sharded and
    replicated monotonically, and classification follows leaf size
    exactly (reference stage3.py:287-310 keeps small params resident).

    Slow (ISSUE 8 tier-1 wall consolidation): one engine compile per
    sweep point, ~14 s. Tier-1 keeps the knob's two sides pinned by
    test_zero3_params_sharded (threshold 0 shards) and
    tests/test_zero_matrix.py's partition invariants (the default
    threshold keeps small leaves replicated); the monotonic sweep
    re-runs with -m slow."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import gpt2_tiny, GPT2LMHeadModel
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    if len(jax.devices()) < 4:
        pytest.skip("need 4 devices")

    def sharded_leaves(threshold):
        cfg = {
            "train_batch_size": 8,
            "zero_optimization": {
                "stage": 3,
                "stage3_param_persistence_threshold": threshold},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "steps_per_print": 1000,
        }
        mesh = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
        engine, _, _, _ = dstpu.initialize(
            config=cfg, model=GPT2LMHeadModel(gpt2_tiny()), mesh=mesh)
        batch = {"input_ids": np.random.RandomState(0).randint(
            0, 512, (8, 64)).astype(np.int32)}
        engine.train_batch(batch)
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                engine.state.params)[0]:
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            specs = leaf.sharding.spec if hasattr(leaf.sharding, "spec") \
                else ()
            # a layer-stacked leaf (the scanned "h" subtree) is judged by
            # ONE layer's elements
            unit = leaf.shape[1:] if name.startswith("h/") else leaf.shape
            out[name] = (int(np.prod(unit)),
                         any(s is not None for s in specs))
        return out

    # 6000 sits between one layer of attn/c_proj's kernel (64 x 64) and
    # its two-layer stack (8192): judged per layer it stays whole
    by_thresh = {t: sharded_leaves(t) for t in (0, 4096, 6000, 10**9)}
    counts = {t: sum(sharded for _, sharded in v.values())
              for t, v in by_thresh.items()}
    # monotone: lower threshold → more leaves sharded; huge → none
    assert counts[0] >= counts[4096] >= counts[6000] >= counts[10**9] == 0, \
        counts
    assert counts[0] > counts[4096] > counts[6000], counts
    # classification is exactly by size at the midpoints (divisibility
    # permitting: leaves the partitioner cannot split stay replicated)
    for t in (4096, 6000):
        for name, (numel, sharded) in by_thresh[t].items():
            if numel >= t and by_thresh[0][name][1]:
                assert sharded, (t, name, numel)
            if numel < t:
                assert not sharded, (t, name, numel)
    assert not by_thresh[6000]["h/blk/attn/c_proj/kernel"][1]
