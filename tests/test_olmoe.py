"""OLMoE on the normal path (``dstpu.initialize`` -> the engine's step)
against its plain reference: loss, every gradient leaf, the engine's
``losses`` and ``stats`` collections. The check that sees an omission:
``tests/test_olmoe_check.py``; the dropless expert layer, the grouped matmul
and LLaMA itself, which must not have moved: ``tests/test_olmoe_layer.py``.

Sizes are the benchmark configuration's rehearsal sizes (hidden 64, 2 layers,
4 heads, 8 experts top-2 of width 32, 128 positions, vocabulary 512), the
model in float32 so that system and reference agree to float32 rounding.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from benchmark import manifest
from benchmark.families import olmoe as family
from deepspeed_tpu.models import llama
from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh

with open(os.path.join(manifest.HERE, "configs",
                       "olmoe-1b-7b-0125-depth1.json")) as f:
    CONFIG = json.load(f)
BATCH, SEQ = 8, 128


def _ids(seed=0, rows=BATCH):
    return np.random.default_rng(seed).integers(
        0, 512, (rows, SEQ), dtype=np.int32)


def _model_config(**over):
    cfg = family.model_config(CONFIG, rehearse=True)
    return dataclasses.replace(cfg, dtype=jnp.float32, **over)


def _engine(cfg, stage, gas=1):
    ds = {"train_batch_size": BATCH * gas, "gradient_accumulation_steps": gas,
          "zero_optimization": {"stage": stage,
                                "stage3_param_persistence_threshold": 0},
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "steps_per_print": 10 ** 9, "seed": 3}
    engine, _, _, _ = dstpu.initialize(
        config=ds, model=llama.LlamaForCausalLM(cfg),
        mesh=make_mesh(MeshConfig(data=8)))
    return engine


def _reference(params, ids):
    """(loss, detail, gradients in the program's tree, their norm)."""
    loss, detail, gnorm, grads = family.reference_run(
        CONFIG, params, ids, jax.devices()[0], True)
    return float(loss), detail, grads, float(gnorm)


@pytest.mark.parametrize("stage", [0, 3])
@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_the_engines_step_equals_the_reference(scan, stage):
    """Loss, EVERY gradient leaf and the two auxiliary terms, through
    ``dstpu.initialize`` on the CPU's 8 devices."""
    cfg = _model_config(scan_layers=scan)
    engine = _engine(cfg, stage)
    batch = {"input_ids": _ids()}
    loss = float(engine.forward(batch))
    grads = jax.device_get(engine._pending_micro[1])
    params = jax.device_get(engine.state.params)
    want_loss, detail, want, _ = _reference(params, batch["input_ids"])
    assert abs(loss - want_loss) < 2e-5, (loss, want_loss)
    want = jax.device_get(want)
    flat = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert set(flat) == set(flat_want)
    for path, g in flat.items():
        w = flat_want[path]
        assert np.allclose(g, w, rtol=2e-3, atol=2e-6 + 1e-4 * np.abs(w).max()), \
            (jax.tree_util.keystr(path), np.abs(g - w).max(), np.abs(w).max())
    # the step's own loss is that sum, and its gauges the two terms
    assert abs(float(engine.train_batch(batch)) - want_loss) < 2e-5
    gauges = engine.telemetry_flush()["gauges"]
    layers = cfg.n_layers
    assert gauges["moe/aux_loss"] * layers == pytest.approx(
        float(detail["balance"]), rel=1e-4)
    assert gauges["moe/z_loss"] * layers == pytest.approx(
        float(detail["z"]), rel=1e-4)
    assert gauges["moe/dropped_rows"] == 0
    assert 1.0 <= gauges["moe/rows_max_over_mean"] <= cfg.num_experts
    ce = float(detail["ce"])
    assert want_loss == pytest.approx(
        ce + 0.01 * float(detail["balance"]) + 0.001 * float(detail["z"]),
        abs=1e-6)


def test_gradient_accumulation_carries_the_models_statistics_too():
    cfg = _model_config()
    engine = _engine(cfg, 0, gas=2)
    loss = float(engine.train_batch({"input_ids": _ids(rows=2 * BATCH)}))
    assert np.isfinite(loss)
    gauges = engine.telemetry_flush()["gauges"]
    assert gauges["moe/dropped_rows"] == 0 and gauges["moe/z_loss"] > 0


def test_a_forward_only_loss_leaves_no_tracer_behind():
    """The statistics leave the loss function as a value: a forward-only
    caller that drops them leaks nothing."""
    engine = _engine(_model_config(), 0)
    batch = {"input_ids": jnp.asarray(_ids())}
    engine.train_batch(batch)
    loss_fn = engine._resolve_loss_fn()
    with jax.checking_leaks():
        loss = jax.jit(lambda p, b: loss_fn(
            p, b, jax.random.PRNGKey(0), jnp.float32(1.0)))(
                engine.state.params, batch)
    assert np.isfinite(float(loss))
    _, stats = engine._loss_and_stats_fn()(
        engine.state.params, batch, jax.random.PRNGKey(0), jnp.float32(1.0))
    assert set(stats) == set(engine.module.stat_gauges)
