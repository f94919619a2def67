"""ZeRO over the families the repo trains, on the one path every cell runs:
the GSPMD step with the stage-3 gather edge (zero/partition.GatherEdge).

The partition invariants read metadata only (``jax.eval_shape`` trees, no
engine). The collective census compiles the stage-3 step on the CPU's eight
devices, family by family. The trajectory parity against stage 0 builds
two engines a case (``tests/zero_matrix.py``) and has a file for each
precision and model class, ``tests/test_zero_matrix_fp32*.py`` and
``tests/test_zero_matrix_bf16*.py``, so that no file holds an xdist worker
for much over two minutes: they are the last files ``--dist loadfile``
deals, and the last file's seconds are the run's tail.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.config.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.config import constants as C
from deepspeed_tpu.models import bert, gpt2, llama
from deepspeed_tpu.parallel.mesh import DATA_AXIS, MeshConfig, make_mesh
from deepspeed_tpu.runtime.zero.partition import (ZeroPartitioner,
                                                  plan_from_specs)
from tests import hlo_text, zero_matrix


def test_plan_from_specs():
    leaves = [jnp.zeros((4, 16, 32)), jnp.zeros((4, 8)), jnp.zeros((3,))]
    specs = [P(None, None, "data"), P(None, "data"), P()]
    assert plan_from_specs(leaves, specs, "data", 8) == \
        [(2, 4), (1, 1), None]


# ------------------------------------------------ partition invariants

# widths chosen so that the default persistence threshold (1e5 elements)
# falls between a layer's leaves: some rest sharded, some whole, and a
# stacked leaf's whole stack is over it where one layer is not
SHAPE_FAMILIES = {
    "gpt2-scanned": lambda: gpt2.GPT2LMHeadModel(gpt2.GPT2Config(
        vocab_size=500, n_positions=128, n_embd=256, n_layer=3, n_head=4,
        scan_layers=True)),
    "gpt2-unrolled": lambda: gpt2.GPT2LMHeadModel(gpt2.GPT2Config(
        vocab_size=500, n_positions=128, n_embd=256, n_layer=3, n_head=4,
        scan_layers=False)),
    "llama": lambda: llama.LlamaForCausalLM(llama.llama_tiny(
        hidden_size=256, intermediate_size=704, n_layers=3)),
    "olmoe": lambda: llama.LlamaForCausalLM(llama.llama_tiny(
        hidden_size=256, intermediate_size=128, n_layers=3, n_kv_heads=0,
        num_experts=8, num_experts_per_tok=2, qk_norm=True)),
    "bert": lambda: bert.BertForPreTraining(bert.bert_tiny(
        hidden_size=256, intermediate_size=1024, num_hidden_layers=3)),
}
SETTINGS = {"stage1": (1, 0), "stage2": (2, 0), "stage3-thr0": (3, 0),
            "stage3-default": (
                3, int(C.ZERO_PARAM_PERSISTENCE_THRESHOLD_DEFAULT))}


@functools.lru_cache(maxsize=None)
def _shapes(family):
    model = SHAPE_FAMILIES[family]()
    shapes = jax.eval_shape(
        lambda r: model.init(r, np.zeros((1, 8), np.int32))["params"],
        jax.random.PRNGKey(0))
    return model, shapes


def _data_dims(spec):
    return [d for d, ax in enumerate(spec)
            if DATA_AXIS in (ax if isinstance(ax, tuple) else (ax,))]


@pytest.mark.parametrize("setting", SETTINGS)
@pytest.mark.parametrize("dp", [2, 4, 8])
@pytest.mark.parametrize("family", SHAPE_FAMILIES)
def test_partition_invariants(family, dp, setting):
    """What the partitioner promises every step builder, leaf by leaf:
    a sharded dim divides by the data axis; a layer-stacked leaf never
    shards its layer dim and is held against the threshold one layer at a
    time; parameters, gradients and moments carry the data axis from
    stage 3, 2 and 1 on; the moments' shardings follow the parameter-like
    specs; ``explicit_shard_plan`` says the same as those specs; and the
    gather edge covers exactly the leaves that rest sharded."""
    stage, threshold = SETTINGS[setting]
    model, shapes = _shapes(family)
    stacked = getattr(model, "layer_stacked_subtree", None)
    mesh = make_mesh(MeshConfig(data=dp), devices=jax.devices()[:dp])
    part = ZeroPartitioner(
        mesh, stage, param_persistence_threshold=threshold,
        layer_stacked_prefixes=(stacked,) if stacked else ())

    def flat(tree):
        return jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, P))[0]

    leaves = flat(shapes)
    zero = [s for _, s in flat(part.opt_param_like_specs(shapes))]
    n_sharded = 0
    for (path, leaf), spec in zip(leaves, zero):
        is_stacked = path[0].key == stacked
        unit = leaf.shape[1:] if is_stacked else leaf.shape
        dims = _data_dims(spec)
        assert len(dims) <= 1, (path, spec)
        can = [d for d in range(1 if is_stacked else 0, leaf.ndim)
               if leaf.shape[d] % dp == 0]
        want = bool(can) and int(np.prod(unit or (1,))) >= max(threshold, dp)
        assert bool(dims) == want, (path, leaf.shape, spec)
        if dims:
            n_sharded += 1
            assert leaf.shape[dims[0]] % dp == 0
            assert not (is_stacked and dims[0] == 0), (path, spec)
            assert leaf.shape[dims[0]] == max(leaf.shape[d] for d in can)
    assert n_sharded, "a matrix case where nothing shards checks nothing"
    if threshold:
        assert n_sharded < len(leaves)

    # which trees carry the data axis at this stage, and that they agree
    for min_stage, specs in ((3, part.param_specs(shapes)),
                             (2, part.grad_specs(shapes))):
        got = [s for _, s in flat(specs)]
        if stage >= min_stage:
            assert got == zero
        else:
            assert not any(_data_dims(s) for s in got)

    # optimizer state: parameter-like fields follow, the rest is whole
    sh = part.opt_state_shardings(
        {"exp_avg": shapes, "step": jax.ShapeDtypeStruct((), jnp.int32)},
        shapes, ("exp_avg",))
    assert [s.spec for s in jax.tree_util.tree_leaves(sh["exp_avg"])] == zero
    assert sh["step"].spec == P()

    plan = part.explicit_shard_plan(shapes)
    assert len(plan) == len(zero)
    for (path, leaf), spec, entry in zip(leaves, zero, plan):
        dims = _data_dims(spec)
        assert entry == ((dims[0], leaf.shape[dims[0]] // dp) if dims
                         else None), (path, spec, entry)

    edge = part.gather_edge(shapes)
    if stage < 3:
        assert edge is None
        return
    resting = {tuple(k.key for k in path): leaf
               for (path, leaf), spec in zip(leaves, zero)
               if _data_dims(spec)}
    assert set(edge.specs) == set(resting)
    for keys, leaf in resting.items():
        compute = edge.specs[keys]
        assert not _data_dims(compute)
        assert len(compute) == leaf.ndim - (keys[0] == stacked)


# -------------------------- collective census of the compiled step

CENSUS_MESHES = {"data8": MeshConfig(data=8),
                 "data4-model2": MeshConfig(data=4, model=2)}


def stage3_census(family, mesh):
    """Compile ``family``'s stage-3 step over ``mesh`` and hold what every
    model owes: no all-to-all (no activation is re-laid), weight
    all-gathers are there, and the gather edge reports the leaves it
    pinned. For scanned layers, returns the forward and the backward layer
    scan's bodies, the all-gathers and the shapes of the whole stacks."""
    engine = zero_matrix.engine(family, 3,
                                mesh=make_mesh(CENSUS_MESHES[mesh]))
    batch = zero_matrix.batch()
    engine.train_batch(batch)
    text = engine.lower_train_step(batch).compile().as_text()
    lines = text.splitlines()
    assert not hlo_text.instructions(lines, "all-to-all")
    gathers = hlo_text.instructions(lines, "all-gather")
    assert gathers
    pinned = engine.telemetry.peek_gauge("zero/gather_edge_leaves")
    # scanned GPT-2 over data 8: all twelve leaves of a layer rest sharded
    assert pinned == 12 if (family, mesh) == ("gpt2-scanned", "data8") \
        else pinned > 0
    stacked = getattr(engine.module, "layer_stacked_subtree", None)
    if stacked is None:
        return None
    blk = {name: body for name, body in hlo_text.loop_bodies(text).items()
           if any("/blk/" in ln for ln in body)}
    # a loop inside the block (the grouped matmul's own) is reached from
    # the layer scan's body: keep the outermost
    layer_scans = [body for name, body in blk.items() if not any(
        re.search(rf"body=%?{re.escape(name)}\b", ln)
        for other, lines in blk.items() if other != name for ln in lines)]
    assert len(layer_scans) == 2, len(layer_scans)     # forward, backward
    stacks = {tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(
        engine.state.params[stacked])}
    return layer_scans, gathers, stacks


def gathers_layer_by_layer(layer_scans, gathers, stacks):
    """PR 25's rule: the weight all-gathers sit inside the forward and the
    backward layer scan's bodies, and none brings a whole stack at once."""
    return (all(hlo_text.instructions(body, "all-gather")
                for body in layer_scans)
            and not any(hlo_text.result_shape(ln) in stacks
                        for ln in gathers))


@pytest.mark.parametrize("mesh", CENSUS_MESHES)
@pytest.mark.parametrize("family", zero_matrix.FAMILIES)
def test_stage3_collective_census(family, mesh):
    census = stage3_census(family, mesh)
    assert census is None or gathers_layer_by_layer(*census)


def test_stage3_collective_census_without_remat():
    """ROADMAP S15, held as an expected failure: the gather edge's promise
    (gathered in the forward scan, gathered again in the backward's, never
    a saved residual) holds inside a rematted block only. Without remat
    the forward scan's residuals are the gathered weights of every layer
    and the backward scan gathers nothing: stage 3's memory saving given
    back. No cell runs it; the repair belongs in ``gather_edge_block``."""
    census = stage3_census("llama-no-remat", "data8")
    if gathers_layer_by_layer(*census):
        pytest.fail("S15 is repaired: make llama-no-remat a case of "
                    "test_stage3_collective_census and close it in ROADMAP")
    pytest.xfail("ROADMAP S15: an unrematted scanned block saves its "
                 "gathered weights")


# ------------------------------------------------------- removed keys

def _config(zero):
    return DeepSpeedConfig({"train_batch_size": 8, "zero_optimization":
                            {"stage": 3, **zero}}, world_size=1)


@pytest.mark.parametrize("key,value", [
    ("stage3_prefetch", True), ("stage3_prefetch_gather", "ring"),
    ("collective_matmul", {"backend": "auto"})])
def test_removed_prefetch_keys_are_refused(key, value):
    """The keys of the explicit layer-gather prefetch step are refused by
    name: a config that asks for the path that is gone must not train on
    another one in silence."""
    assert key in C.ZERO_REMOVED_KEYS
    with pytest.raises(DeepSpeedConfigError, match=key):
        _config({key: value})


def test_reference_stage3_tuning_keys_still_load():
    """The reference's own stage-3 knobs stay accepted (and have no
    effect: XLA schedules the gather edge's all-gathers)."""
    cfg = _config({"stage3_prefetch_bucket_size": 1000,
                   "stage3_max_live_parameters": 2000})
    assert cfg.zero_config.stage == 3
