"""Engine end-to-end tests — the role of the reference's test_fp16.py /
simple-model training tests: loss decreases, GAS paths agree, fp16 scaler
behaves, checkpoint roundtrips."""

import numpy as np
import jax
import jax.numpy as jnp
import time

import pytest

import deepspeed_tpu as dstpu
from tests.simple_model import (SimpleModel, random_batch, random_dataset,
                                base_config, token_batch)


def one_device_mesh():
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    return make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


def make_engine(config=None, model=None, **kw):
    model = model or SimpleModel()
    kw.setdefault("mesh", one_device_mesh())
    engine, _, _, _ = dstpu.initialize(config=config or base_config(),
                                       model=model, **kw)
    return engine


def test_train_batch_loss_decreases():
    engine = make_engine()
    batch = random_batch(batch_size=8)
    first = float(engine.train_batch(batch))
    for _ in range(30):
        last = float(engine.train_batch(batch))
    assert last < first, f"loss did not decrease: {first} -> {last}"


def test_forward_backward_step_equals_train_batch():
    cfg = base_config(train_batch_size=8, gradient_accumulation_steps=2)
    e1 = make_engine(cfg)
    e2 = make_engine(cfg)
    x, y = random_batch(batch_size=8)

    # path A: fused train_batch over the full batch
    lossA = e1.train_batch((x, y))

    # path B: forward/backward per micro batch + step
    for i in range(2):
        mb = (x[i * 4:(i + 1) * 4], y[i * 4:(i + 1) * 4])
        loss = e2.forward(mb)
        e2.backward(loss)
    e2.step()

    pa = jax.tree_util.tree_leaves(e1.state.params)
    pb = jax.tree_util.tree_leaves(e2.state.params)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    assert e1.global_steps == e2.global_steps == 1


def test_gradient_accumulation_boundary():
    cfg = base_config(train_batch_size=8, gradient_accumulation_steps=2,
                      train_micro_batch_size_per_gpu=4)
    engine = make_engine(cfg)
    mb = random_batch(batch_size=4)
    assert engine.is_gradient_accumulation_boundary() is False
    loss = engine.forward(mb)
    engine.backward(loss)
    engine.step()  # not a boundary: no optimizer step yet
    assert engine.global_steps == 0
    loss = engine.forward(mb)
    engine.backward(loss)
    engine.step()
    assert engine.global_steps == 1


def test_train_batch_with_data_iter():
    cfg = base_config(train_batch_size=8, gradient_accumulation_steps=2,
                      train_micro_batch_size_per_gpu=4)
    engine = make_engine(cfg)
    data = random_dataset(n=32)
    loader = engine.deepspeed_io(data)
    it = iter(dstpu.runtime.dataloader.RepeatingLoader(loader))
    loss = engine.train_batch(data_iter=it)
    assert np.isfinite(float(loss))
    assert engine.global_steps == 1


def test_lr_schedule_applied():
    cfg = base_config()
    cfg["scheduler"] = {"type": "WarmupLR",
                        "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.1,
                                   "warmup_num_steps": 10, "warmup_type": "linear"}}
    engine = make_engine(cfg)
    batch = random_batch()
    engine.train_batch(batch)
    lr1 = engine.get_lr()[0]
    for _ in range(5):
        engine.train_batch(batch)
    lr2 = engine.get_lr()[0]
    assert lr2 > lr1


def test_gradient_clipping_reduces_norm():
    cfg = base_config(gradient_clipping=1e-4)
    engine = make_engine(cfg)
    batch = random_batch()
    engine.train_batch(batch)
    # with aggressive clipping, params barely move
    engine2 = make_engine(base_config())
    engine2.train_batch(batch)
    assert float(engine.get_global_grad_norm()) == pytest.approx(
        float(engine2.get_global_grad_norm()), rel=1e-4)


def test_fp16_dynamic_loss_scale_starts_high():
    cfg = base_config()
    cfg["fp16"] = {"enabled": True, "initial_scale_power": 16}
    engine = make_engine(cfg)
    batch = random_batch()
    engine.train_batch(batch)
    assert engine.loss_scale in (2.0 ** 16, 2.0 ** 17)


def test_fp16_overflow_skips_step():
    cfg = base_config()
    cfg["fp16"] = {"enabled": True, "initial_scale_power": 4, "hysteresis": 1}
    engine = make_engine(cfg)
    x, y = random_batch()
    x_bad = x.copy()
    x_bad[0, 0] = np.inf
    engine.train_batch((x, y))
    params_before = jax.device_get(engine.state.params)
    scale_before = engine.loss_scale
    engine.train_batch((x_bad, y))
    params_after = jax.device_get(engine.state.params)
    # step skipped: params unchanged, scale halved
    for a, b in zip(jax.tree_util.tree_leaves(params_before),
                    jax.tree_util.tree_leaves(params_after)):
        np.testing.assert_array_equal(a, b)
    assert engine.loss_scale == scale_before / 2


def test_bf16_training():
    cfg = base_config()
    cfg["bf16"] = {"enabled": True}
    engine = make_engine(cfg)
    batch = random_batch()
    l0 = float(engine.train_batch(batch))
    for _ in range(20):
        l1 = float(engine.train_batch(batch))
    assert l1 < l0


def test_checkpoint_roundtrip(tmp_path):
    engine = make_engine()
    batch = random_batch()
    for _ in range(3):
        engine.train_batch(batch)
    engine.save_checkpoint(str(tmp_path), client_state={"note": "hi"})

    engine2 = make_engine()
    engine2.train_batch(batch)  # init state differently
    tag, client = engine2.load_checkpoint(str(tmp_path))
    assert engine2.global_steps == 3
    assert client.get("note") == "hi"
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(engine.state.params)),
                    jax.tree_util.tree_leaves(jax.device_get(engine2.state.params))):
        np.testing.assert_array_equal(a, b)
    # resumed training continues identically
    la = float(engine.train_batch(batch))
    lb = float(engine2.train_batch(batch))
    assert la == pytest.approx(lb, rel=1e-5)


def test_gpt2_tiny_trains():
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny
    cfg = base_config(train_batch_size=4)
    cfg["optimizer"]["params"]["lr"] = 1e-3
    model = GPT2LMHeadModel(gpt2_tiny(dtype=jnp.float32))
    engine = make_engine(cfg, model=model)
    batch = token_batch(batch_size=4, seq=16, vocab=512)
    l0 = float(engine.train_batch(batch))
    for _ in range(10):
        l1 = float(engine.train_batch(batch))
    assert l1 < l0


def test_lamb_optimizer():
    cfg = base_config()
    cfg["optimizer"] = {"type": "Lamb", "params": {"lr": 1e-2}}
    engine = make_engine(cfg)
    batch = random_batch()
    l0 = float(engine.train_batch(batch))
    for _ in range(20):
        l1 = float(engine.train_batch(batch))
    assert l1 < l0


def test_sgd_optimizer():
    cfg = base_config()
    cfg["optimizer"] = {"type": "SGD", "params": {"lr": 1e-2, "momentum": 0.9}}
    engine = make_engine(cfg)
    batch = random_batch()
    l0 = float(engine.train_batch(batch))
    for _ in range(20):
        l1 = float(engine.train_batch(batch))
    assert l1 < l0


def test_tensorboard_monitor_writes_scalars(tmp_path):
    """Monitor subsystem: scalar stream lands in TB event files (or the
    JSONL fallback) under output_path/job_name (reference engine.py:162,
    1095-1105)."""
    import os
    cfg = base_config()
    cfg["tensorboard"] = {"enabled": True,
                          "output_path": str(tmp_path),
                          "job_name": "job1"}
    engine = make_engine(cfg)
    batch = random_batch()
    for _ in range(3):
        engine.train_batch(batch)
    log_dir = os.path.join(str(tmp_path), "job1")
    assert os.path.isdir(log_dir) and os.listdir(log_dir)
    assert len(engine.scalar_history) == 3
    assert {"loss", "lr", "loss_scale", "grad_norm"} <= \
        set(engine.scalar_history[0][1].keys())


def test_flops_profiler_detailed_breakdown():
    """detailed mode emits the per-module table (reference
    print_model_profile role)."""
    from deepspeed_tpu.profiling.flops_profiler import (
        module_breakdown, get_model_profile)
    from deepspeed_tpu.models.gpt2 import gpt2_tiny, GPT2LMHeadModel
    import numpy as np
    model = GPT2LMHeadModel(gpt2_tiny())
    table = module_breakdown(model, np.zeros((1, 8), np.int32), depth=2)
    assert "GPT2LMHeadModel" in table and "flops" in table
    flops, macs, n_params = get_model_profile(model, (1, 8))
    assert flops > 0 and n_params > 0


def test_wall_clock_breakdown_fused_path():
    """wall_clock_breakdown instruments the real train_batch (reference
    engine.py:1028-1047): per-phase fwd/bwd/step timers populate, and the
    instrumented step matches the fused step numerically."""
    cfg = base_config(train_batch_size=8, gradient_accumulation_steps=2)
    cfg["wall_clock_breakdown"] = True
    e_inst = make_engine(cfg)
    e_fused = make_engine(base_config(train_batch_size=8,
                                      gradient_accumulation_steps=2))
    batch = random_batch(batch_size=8)
    for _ in range(3):
        l_inst = float(e_inst.train_batch(batch))
        l_fused = float(e_fused.train_batch(batch))
    assert l_inst == pytest.approx(l_fused, rel=1e-4)
    times = e_inst.wall_clock_times()
    # 'fence' is the measured per-phase readback cost that the phase
    # numbers are reported NET of
    assert set(times) == {"forward", "backward", "step", "fence"}
    assert times["forward"] > 0 and times["step"] > 0
    # uninstrumented engine reports no phase timers
    assert e_fused.wall_clock_times() == {}
    # the phases' span events lie on span()'s clock like every other: the
    # start of the program each was timed in, in the order they ran
    phases = [e for e in e_inst.flight_recorder.events()
              if e["kind"] == "span" and e["tag"] in (
                  "train/forward", "train/backward", "train/optimizer",
                  "train/fence")][-4:]
    assert [e["tag"].split("/")[1] for e in phases] == [
        "forward", "backward", "optimizer", "fence"]
    starts = [e["t0_mono"] for e in phases]
    assert starts == sorted(starts) and starts[-1] <= time.monotonic()


class _FakeMpu:
    def __init__(self, mp):
        self._mp = mp

    def get_model_parallel_world_size(self):
        return self._mp


def test_mpu_adopted_into_mesh():
    """initialize(mpu=...) maps the client TP object onto the mesh 'model'
    axis (reference engine.py:636-641 adopts mpu groups) instead of
    silently ignoring it."""
    if len(jax.devices()) < 2:
        pytest.skip("need 2 devices")
    from deepspeed_tpu.models.sharding import gpt2_tp_specs
    from deepspeed_tpu.models.gpt2 import gpt2_tiny, GPT2LMHeadModel
    model = GPT2LMHeadModel(gpt2_tiny(dtype=jnp.float32))
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
    engine, _, _, _ = dstpu.initialize(config=cfg, model=model,
                                       mpu=_FakeMpu(2))
    assert dict(engine.mesh.shape)["model"] == 2
    batch = {"input_ids": np.random.RandomState(0)
             .randint(0, 512, (8, 32)).astype(np.int32)}
    assert np.isfinite(float(engine.train_batch(batch)))


def test_mpu_mesh_mismatch_raises():
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="model_parallel_world_size"):
        dstpu.initialize(config=base_config(), model=SimpleModel(),
                         mesh=mesh, mpu=_FakeMpu(2))


def test_mpu_without_interface_raises():
    with pytest.raises(ValueError, match="get_model_parallel_world_size"):
        dstpu.initialize(config=base_config(), model=SimpleModel(),
                         mpu=object())
