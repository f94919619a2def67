"""End-to-end convergence tests — the reference's tests/model tier
(Megatron_GPT2 run_func_test.py compares loss curves across parallelism
configs; test_pipe.py compares pipeline vs DP convergence). Here: the same
tiny GPT-2 trained under different mesh/ZeRO configurations must produce
matching loss trajectories, since ZeRO/DP/TP re-sharding is mathematically
a no-op."""

import numpy as np
import pytest
import jax

import deepspeed_tpu as dstpu
from deepspeed_tpu.models.gpt2 import gpt2_tiny, GPT2LMHeadModel
from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig


def _train(mesh_cfg, zero_stage, steps=8, n_devices=1, seed=7):
    devs = jax.devices()[:n_devices]
    if len(devs) < n_devices:
        pytest.skip(f"need {n_devices} devices")
    mesh = make_mesh(mesh_cfg, devices=devs)
    cfg = {
        "train_batch_size": 8,
        "zero_optimization": {"stage": zero_stage},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 1000,
        "seed": seed,
    }
    model = GPT2LMHeadModel(gpt2_tiny())
    engine, _, _, _ = dstpu.initialize(config=cfg, model=model, mesh=mesh)
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 512, (8, 64)).astype(np.int32)}
    return [float(engine.train_batch(batch)) for _ in range(steps)]


def test_gpt2_converges():
    losses = _train(MeshConfig(data=1), zero_stage=0, steps=15)
    assert losses[-1] < losses[0] - 0.5, losses


def test_bf16_grad_accum_matches_fp32():
    """bf16 accumulation buffers (data_types.grad_accum_dtype) track the
    fp32-accumulated trajectory within bf16 rounding noise."""
    def run(accum):
        mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
        cfg = {
            "train_batch_size": 8,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "bf16": {"enabled": True},
            "data_types": {"grad_accum_dtype": accum},
            "steps_per_print": 1000, "seed": 3,
        }
        model = GPT2LMHeadModel(gpt2_tiny())
        engine, _, _, _ = dstpu.initialize(config=cfg, model=model, mesh=mesh)
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.randint(0, 512, (8, 64)).astype(np.int32)}
        return [float(engine.train_batch(batch)) for _ in range(6)]

    base = run("fp32")
    got = run("bf16")
    assert got[-1] < got[0] - 0.3, got
    np.testing.assert_allclose(got, base, rtol=3e-2, atol=3e-2)


@pytest.mark.slow
def test_bf16_grad_dtype_matches_fp32():
    """grad_dtype=bf16 (params cast once inside the differentiated fn, all
    cotangents bf16) tracks the fp32-grad trajectory within rounding."""
    def run(gd):
        mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
        cfg = {
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "bf16": {"enabled": True},
            "data_types": {"grad_dtype": gd},
            "steps_per_print": 1000, "seed": 5,
        }
        model = GPT2LMHeadModel(gpt2_tiny())
        engine, _, _, _ = dstpu.initialize(config=cfg, model=model, mesh=mesh)
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.randint(0, 512, (8, 64)).astype(np.int32)}
        return [float(engine.train_batch(batch)) for _ in range(6)]

    base = run("fp32")
    got = run("bf16")
    assert got[-1] < got[0] - 0.3, got
    np.testing.assert_allclose(got, base, rtol=3e-2, atol=3e-2)


@pytest.mark.slow
def test_bf16_moment_dtype_converges():
    """moment_dtype=bf16 (half-storage Adam moments) still converges and
    tracks fp32 moments closely over a short horizon."""
    def run(md):
        mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
        cfg = {
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-2, "moment_dtype": md}},
            "steps_per_print": 1000, "seed": 5,
        }
        model = GPT2LMHeadModel(gpt2_tiny())
        engine, _, _, _ = dstpu.initialize(config=cfg, model=model, mesh=mesh)
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.randint(0, 512, (8, 64)).astype(np.int32)}
        return [float(engine.train_batch(batch)) for _ in range(8)]

    base = run("fp32")
    got = run("bf16")
    assert got[-1] < got[0] - 0.5, got
    np.testing.assert_allclose(got, base, rtol=5e-2, atol=5e-2)


def test_chunked_lm_loss_matches_full():
    """The fused chunked head+loss must equal lm_loss(logits) — value AND
    gradients — including a pad remainder and ignore_index masking."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import (
        GPT2Config, GPT2LMHeadModel, lm_loss)
    cfg = dict(vocab_size=512, n_positions=96, n_embd=64, n_layer=2,
               n_head=2, dtype=jnp.float32)
    full = GPT2LMHeadModel(GPT2Config(**cfg))
    # chunk=40 does not divide B*(S-1)=3*95=285 → exercises padding
    fused = GPT2LMHeadModel(GPT2Config(**cfg, loss_chunk=40))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 512, (3, 96)).astype(np.int32)
    labels = np.where(rng.rand(3, 96) < 0.1, -100, ids).astype(np.int32)
    params = full.init(jax.random.PRNGKey(0), ids)["params"]

    def loss_full(p):
        return lm_loss(full.apply({"params": p}, ids), labels)

    def loss_fused(p):
        return fused.apply({"params": p}, ids, labels=labels)

    v1, g1 = jax.value_and_grad(loss_full)(params)
    v2, g2 = jax.value_and_grad(loss_fused)(params)
    np.testing.assert_allclose(v1, v2, rtol=1e-6)
    for k in g1:
        np.testing.assert_allclose(
            jax.tree_util.tree_leaves(g1[k])[0],
            jax.tree_util.tree_leaves(g2[k])[0], rtol=2e-4, atol=1e-6,
            err_msg=k)


def _checkpointed_chunked_lm_loss(hidden, wte, labels, chunk,
                                  ignore_index=-100):
    """``chunked_lm_loss`` as it stood before PR 51 (each chunk under
    ``jax.checkpoint``, JAX's own transpose of the scan): what the new
    rule's reduced-precision gradients are held to."""
    import jax.numpy as jnp
    E = hidden.shape[-1]
    xs = hidden[:, :-1, :].reshape(-1, E)
    tgt = labels[:, 1:].reshape(-1)
    pad = (-xs.shape[0]) % chunk
    xs = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, chunk, E)
    tgt = jnp.pad(tgt, (0, pad), constant_values=ignore_index).reshape(
        -1, chunk)

    @jax.checkpoint
    def chunk_nll(h, t):
        logits = (h @ wte.T).astype(jnp.float32)
        valid = t != ignore_index
        lse = jax.nn.logsumexp(logits, axis=-1)
        g = jnp.take_along_axis(
            logits, jnp.where(valid, t, 0)[:, None], axis=-1)[:, 0]
        return (jnp.sum(jnp.where(valid, lse - g, 0.0)),
                jnp.sum(valid.astype(jnp.int32)))

    def body(carry, xt):
        ds, dc = chunk_nll(*xt)
        return (carry[0] + ds, carry[1] + dc), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (xs, tgt))
    return total / jnp.maximum(count, 1)


# rows = 4 * 32 = 128; a case names what differs from
# (float32, chunk 32, a tenth of the labels ignored, g = 1, untied, no mesh)
_HEAD_RULE_CASES = {
    "f32": {},
    "f32_chunk_does_not_divide": {"chunk": 40},
    "f32_one_chunk_all_ignored": {"ignore": "chunk"},
    "f32_every_label_ignored": {"ignore": "all"},
    "f32_times_3": {"scale": 3.0},
    "f32_two_step_accumulation": {"accum": 2},
    "f32_tied_head": {"tied": True},
    "f32_tied_head_times_3_pad": {"tied": True, "scale": 3.0, "chunk": 24},
    "f32_mesh_4_batch_sharded": {"mesh": 4},
    "bf16": {"dtype": "bfloat16"},
    "bf16_chunk_does_not_divide_times_3": {"dtype": "bfloat16", "chunk": 40,
                                           "scale": 3.0},
    "bf16_two_step_accumulation": {"dtype": "bfloat16", "accum": 2},
    "bf16_tied_head": {"dtype": "bfloat16", "tied": True},
    "bf16_mesh_4_batch_sharded": {"dtype": "bfloat16", "mesh": 4},
    # float16's smallest normal is above softmax / count: the loss scale
    # reaches the gradient before the narrow cast, as it did
    "f16_loss_scale_4096": {"dtype": "float16", "scale": 4096.0},
}


@pytest.mark.parametrize("case", list(_HEAD_RULE_CASES))
def test_chunked_lm_loss_forms_its_gradient_in_the_forward_chunk(case):
    """PR 51's rule: value AND the gradients of ``hidden`` and ``wte``.
    float32 against ``lm_loss`` on the full logits to 1e-6; bfloat16 and
    float16 against the checkpointed form of before, at the dtype's
    rounding, and against float32's ``lm_loss`` at a few of its roundings."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.models.gpt2 import chunked_lm_loss, lm_loss
    c = {"dtype": "float32", "chunk": 32, "ignore": "tenth", "scale": 1.0,
         "accum": 1, "tied": False, "mesh": 0, **_HEAD_RULE_CASES[case]}
    dtype = jnp.dtype(c["dtype"])
    B, S, E, V = 4, 33, 16, 96
    rng = np.random.RandomState(3)
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    labels = np.where(rng.rand(B, S) < 0.1, -100, ids).astype(np.int32)
    if c["ignore"] == "chunk":      # rows 32 .. 63 are batch row 1's targets
        labels[1, :] = -100
    elif c["ignore"] == "all":
        labels[:] = -100
    h0 = jnp.asarray(rng.randn(B, S, E), dtype)
    w0 = jnp.asarray(rng.randn(V, E) * 0.3, dtype)

    def loss(head, h, w, rows=slice(None), wide=False):
        # a tied head also embeds: wte's gradient sums both uses
        hid = (h + w[ids] if c["tied"] else h)[rows]
        if head is lm_loss:
            if wide:
                hid, w = hid.astype(jnp.float32), w.astype(jnp.float32)
            out = lm_loss(jnp.einsum("bse,ve->bsv", hid, w), labels[rows])
        else:
            out = head(hid, w, labels[rows], c["chunk"])
        return out * c["scale"] / c["accum"]

    def value_and_grads(head, h, w, **kw):
        """Summed over the micro-batches, as an accumulating step sums."""
        outs = [jax.value_and_grad(
            lambda hh, ww: loss(head, hh, ww, rows, **kw), (0, 1))(h, w)
            for rows in np.split(np.arange(B), c["accum"])]
        return [sum(np.asarray(x, np.float32) for x in xs)
                for xs in zip(*((v, *g) for v, g in outs))]

    if c["mesh"]:
        devs = jax.devices()[:c["mesh"]]
        if len(devs) < c["mesh"]:
            pytest.skip(f"need {c['mesh']} devices")
        mesh = Mesh(np.array(devs), ("data",))
        h0 = jax.device_put(h0, NamedSharding(mesh, P("data")))
        w0 = jax.device_put(w0, NamedSharding(mesh, P()))
        got_v, (got_h, got_w) = jax.jit(jax.value_and_grad(
            lambda hh, ww: loss(chunked_lm_loss, hh, ww), (0, 1)))(h0, w0)
        got = (np.float32(got_v), np.asarray(got_h, np.float32),
               np.asarray(got_w, np.float32))
    else:
        got = value_and_grads(chunked_lm_loss, h0, w0)
    if c["ignore"] == "all":        # count 0: a loss of 0 that moves nothing
        assert got[0] == 0 and not got[1].any() and not got[2].any()
    full = value_and_grads(lm_loss, h0, w0, wide=True)
    if dtype == jnp.float32:
        for a, b in zip(got, full):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
        return
    # one rounding of the dtype on dlogits, the products and the sum's carry
    eps = float(jnp.finfo(dtype).eps)
    before = value_and_grads(_checkpointed_chunked_lm_loss, h0, w0)
    for a, b, f in zip(got, before, full):
        np.testing.assert_allclose(a, b, rtol=2 * eps,
                                   atol=eps * np.abs(b).max())
        np.testing.assert_allclose(a, f, rtol=0,
                                   atol=8 * eps * np.abs(f).max())


def test_chunked_lm_loss_sums_dw_in_the_transposed_scans_order():
    """``dW`` is carried through the chunks in the operands' dtype, one
    rounding a chunk at the partial sum's size; a causal model's first
    tokens share a direction (attention's running mean), so theirs is the
    largest term. The transposed scan of before added it last; the forward
    rule walks the chunks in that order (PR 51: first chunk first,
    Kanana-2's head leaf read 0.599 % off its float32 reference on the chip
    where the limit is 0.59 and this order reads 0.566)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import chunked_lm_loss, lm_loss
    rng = np.random.RandomState(3)
    S, E, V = 257, 64, 96
    t = np.arange(1, S + 1, dtype=np.float32)[None, :, None]
    h = jnp.asarray(rng.randn(1, S, E) + 20 / np.sqrt(t) * rng.randn(1, 1, E),
                    jnp.bfloat16)
    w = jnp.asarray(rng.randn(V, E) * 0.05, jnp.bfloat16)
    labels = rng.randint(0, V, (1, S)).astype(np.int32)
    want = np.asarray(jax.grad(lambda ww: lm_loss(jnp.einsum(
        "bse,ve->bsv", h.astype(jnp.float32), ww), labels))(
            w.astype(jnp.float32)))

    def off(head):
        got = jax.grad(lambda ww: head(h, ww, labels, 32))(w)
        return np.linalg.norm(np.asarray(got, np.float32) - want) \
            / np.linalg.norm(want)

    assert off(chunked_lm_loss) <= 1.01 * off(_checkpointed_chunked_lm_loss)


@pytest.mark.slow
def test_zero_stages_match_single_device():
    base = _train(MeshConfig(data=1), zero_stage=0)
    for stage in (1, 2, 3):
        got = _train(MeshConfig(data=1), zero_stage=stage)
        # step 1 must match to float precision; later steps may drift by
        # reduction-order noise amplified through training (chaotic)
        np.testing.assert_allclose(got[0], base[0], rtol=1e-5,
                                   err_msg=f"stage {stage}")
        np.testing.assert_allclose(got, base, rtol=1e-2, atol=1e-2,
                                   err_msg=f"stage {stage}")


@pytest.mark.slow
def test_dp_zero_matches_single_device():
    """ZeRO sharding over a real data axis must not change the math
    (the reference's DP-vs-pipe convergence methodology).

    Slow (ISSUE 8 tier-1 wall consolidation): 4 engine compiles,
    ~21 s. Tier-1 keeps the same subsystem pinned by
    tests/test_zero_matrix.py::test_stage_trajectory_matches_stage0
    (dp-mesh stage parity per family and stage); the
    single-device-vs-dp4 drift bound re-runs with -m slow."""
    if len(jax.devices()) < 4:
        pytest.skip("need 4 devices")
    base = _train(MeshConfig(data=1), zero_stage=0)
    for stage in (0, 2, 3):
        got = _train(MeshConfig(data=4), zero_stage=stage, n_devices=4)
        np.testing.assert_allclose(got[0], base[0], rtol=1e-4,
                                   err_msg=f"dp=4 stage {stage}")
        np.testing.assert_allclose(got, base, rtol=2e-2, atol=2e-2,
                                   err_msg=f"dp=4 stage {stage}")


@pytest.mark.slow
def test_tp_matches_single_device():
    if len(jax.devices()) < 4:
        pytest.skip("need 4 devices")
    base = _train(MeshConfig(data=1), zero_stage=0)
    got = _train(MeshConfig(data=2, model=2), zero_stage=0, n_devices=4)
    np.testing.assert_allclose(got[0], base[0], rtol=1e-4)
    np.testing.assert_allclose(got, base, rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_bert_tp_matches_single_device():
    """BERT gets Megatron specs from the sharding registry (VERDICT: TP
    derivation must not be GPT-2-only) — tp run matches single-device."""
    if len(jax.devices()) < 4:
        pytest.skip("need 4 devices")
    import jax.numpy as jnp
    from deepspeed_tpu.models.bert import (
        BertConfig, BertForSequenceClassification)

    def run(mesh_cfg, n_dev):
        cfg_m = BertConfig(vocab_size=512, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           intermediate_size=128, max_position_embeddings=64,
                           dtype=jnp.float32)
        model = BertForSequenceClassification(cfg_m, num_labels=4)

        def loss_fn(params, batch):
            x, y = batch
            import jax.numpy as jnp
            logits = model.apply({"params": params}, x,
                                 jnp.ones_like(x))
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

        cfg = {"train_batch_size": 8,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "steps_per_print": 1000, "seed": 7}
        mesh = make_mesh(mesh_cfg, devices=jax.devices()[:n_dev])
        engine, _, _, _ = dstpu.initialize(config=cfg, model=model,
                                           loss_fn=loss_fn, mesh=mesh)
        rng = np.random.RandomState(0)
        batch = (rng.randint(0, 512, (8, 32)).astype(np.int32),
                 rng.randint(0, 4, (8,)).astype(np.int32))
        losses = [float(engine.train_batch(batch)) for _ in range(6)]
        return losses, engine

    base, _ = run(MeshConfig(data=1), 1)
    got, engine = run(MeshConfig(data=2, model=2), 4)
    assert engine._param_tp_specs is not None, "registry gave BERT no specs"
    np.testing.assert_allclose(got[0], base[0], rtol=1e-4)
    np.testing.assert_allclose(got, base, rtol=2e-2, atol=2e-2)


def test_tp_without_rules_warns():
    """A model-axis mesh with a rule-less model must announce the TP no-op
    loudly instead of silently replicating. (The package logger doesn't
    propagate to root, so attach a handler directly instead of caplog.)"""
    if len(jax.devices()) < 2:
        pytest.skip("need 2 devices")
    import logging
    from deepspeed_tpu.utils.logging import logger as dlog
    from tests.simple_model import SimpleModel, random_batch, base_config
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    dlog.addHandler(handler)
    try:
        cfg = base_config()
        cfg["train_batch_size"] = 8
        mesh = make_mesh(MeshConfig(model=2), devices=jax.devices()[:2])
        engine, _, _, _ = dstpu.initialize(config=cfg, model=SimpleModel(),
                                           mesh=mesh)
        engine.train_batch(random_batch())
    finally:
        dlog.removeHandler(handler)
    assert any("REPLICATED across the model axis" in r.getMessage()
               for r in records), [r.getMessage() for r in records]


@pytest.mark.slow
def test_elastic_checkpoint_across_mesh_resize(tmp_path):
    """Save under one parallel layout, restore under another, training must
    continue identically — the reference's elastic-checkpoint contract
    (zero/stage1.py:854 merge/re-split across changed dp;
    state_dict_factory.py:272 TP resharding). GSPMD arrays make this a
    device_put onto the new mesh's shardings."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import gpt2_tiny, GPT2LMHeadModel

    if len(jax.devices()) < 4:
        pytest.skip("need 4 devices")

    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 512, (8, 64)).astype(np.int32)}

    def make(mesh_cfg, stage, n_dev):
        mesh = make_mesh(mesh_cfg, devices=jax.devices()[:n_dev])
        cfg = {"train_batch_size": 8,
               "zero_optimization": {"stage": stage},
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "steps_per_print": 1000, "seed": 11}
        engine, _, _, _ = dstpu.initialize(
            config=cfg, model=GPT2LMHeadModel(gpt2_tiny()), mesh=mesh)
        return engine

    # train 3 steps on dp=1/stage0, save
    e1 = make(MeshConfig(data=1), 0, 1)
    for _ in range(3):
        e1.train_batch(batch)
    e1.save_checkpoint(str(tmp_path), tag="t")
    ref = [float(e1.train_batch(batch)) for _ in range(4)]

    # restore on dp=4/stage3 and on dp=2×tp=2, continue: same losses
    for mesh_cfg, stage, n in ((MeshConfig(data=4), 3, 4),
                               (MeshConfig(data=2, model=2), 1, 4)):
        e2 = make(mesh_cfg, stage, n)
        e2.load_checkpoint(str(tmp_path), tag="t")
        got = [float(e2.train_batch(batch)) for _ in range(4)]
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3,
                                   err_msg=f"{mesh_cfg} stage{stage}")


# ----------------------------------------------------- loss-curve matrix

# First-5-step goldens for the flagship tiny-GPT-2 config, generated on the
# CPU backend with fixed seeds. The tripwire against cross-feature numerics
# drift — the role of the reference's Megatron GPT-2 loss-curve matrix
# (tests/model/Megatron_GPT2/run_func_test.py). Offload rows differ from
# fused rows in step >=1 because the offload tier rests device params in
# compute dtype (bf16/fp16 roundtrip after each update) while the fused
# path keeps fp32 params; both are pinned.
#
# The goldens are host-μarch sensitive: XLA's CPU codegen vectorizes
# reductions differently per ISA (an AVX-512 box drifts every bf16/fp16
# cell up to ~1.2% from these AVX2-era values by step 5), so they are an
# ENVELOPE at _GOLDEN_ENVELOPE_RTOL, not a tight pin. The tight pin is
# in-process: every (stage, offload) cell must match its cell's stage-0
# trajectory computed on THIS host at _CROSS_STAGE_RTOL — resharding and
# the offload tier must be numerical no-ops regardless of ISA.
_MATRIX_GOLDENS = {
    # (dtype, stage, offload): losses
    ("bf16", 0, False): [6.24387, 5.84568, 5.66218, 5.42843, 5.57283],
    ("bf16", 0, True):  [6.24387, 5.84643, 5.66272, 5.42983, 5.57112],
    ("bf16", 2, False): [6.24387, 5.84568, 5.66218, 5.42843, 5.57283],
    ("bf16", 2, True):  [6.24387, 5.84643, 5.66272, 5.42983, 5.57112],
    ("bf16", 3, False): [6.24387, 5.84568, 5.66216, 5.42868, 5.57227],
    ("bf16", 3, True):  [6.24387, 5.84643, 5.66278, 5.42994, 5.57109],
    ("fp16", 0, False): [6.24387, 5.84568, 5.66218, 5.42843, 5.57283],
    ("fp16", 0, True):  [6.24383, 5.84774, 5.68697, 5.46854, 5.58664],
    ("fp16", 2, False): [6.24387, 5.84568, 5.66218, 5.42843, 5.57283],
    ("fp16", 2, True):  [6.24383, 5.84774, 5.68697, 5.46854, 5.58664],
    ("fp16", 3, False): [6.24387, 5.84568, 5.66216, 5.42868, 5.57227],
    ("fp16", 3, True):  [6.24383, 5.84774, 5.68693, 5.46832, 5.58652],
}


_GOLDEN_ENVELOPE_RTOL = 2.5e-2
_CROSS_STAGE_RTOL = 2e-3

# stage-0 trajectories per (dtype, offload), computed once on this host —
# the reference every stage-2/3 cell is tightly compared against
_matrix_stage0_cache = {}


def _matrix_train(dtype, stage, offload):
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    cfg = {
        "train_batch_size": 8,
        "zero_optimization": {"stage": stage},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "steps_per_print": 1000, "seed": 11,
    }
    if dtype == "bf16":
        cfg["bf16"] = {"enabled": True}
    else:
        # scale_power 8: 2^16 overflows real fp16 grads for several steps
        # (correct dynamic-loss-scale behavior, but the matrix wants the
        # trajectory, not the warmup skips)
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 8}
    if offload:
        cfg["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}
    model = GPT2LMHeadModel(gpt2_tiny())
    engine, _, _, _ = dstpu.initialize(config=cfg, model=model, mesh=mesh)
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 512, (8, 64)).astype(np.int32)}
    return [float(engine.train_batch(batch)) for _ in range(5)]


@pytest.mark.parametrize("dtype", ["bf16", "fp16"])
@pytest.mark.parametrize("stage", [0, 2, 3])
@pytest.mark.parametrize("offload", [False, True])
@pytest.mark.slow
def test_flagship_loss_matrix(dtype, stage, offload):
    """VERDICT r3 item 10: every {stage} x {dtype} x {offload} cell of the
    flagship config reproduces its pinned 5-step trajectory (as a cross-host
    envelope), and ZeRO stages within a (dtype, offload) cell agree tightly
    with the stage-0 trajectory computed on this host."""
    got = _matrix_train(dtype, stage, offload)
    golden = _MATRIX_GOLDENS[(dtype, stage, offload)]
    np.testing.assert_allclose(got, golden, rtol=_GOLDEN_ENVELOPE_RTOL,
                               err_msg=f"{dtype} stage{stage} offload={offload}")
    # cross-stage consistency: resharding must be a numerical no-op
    if (dtype, offload) not in _matrix_stage0_cache:
        _matrix_stage0_cache[(dtype, offload)] = (
            got if stage == 0 else _matrix_train(dtype, 0, offload))
    base = _matrix_stage0_cache[(dtype, offload)]
    np.testing.assert_allclose(got, base, rtol=_CROSS_STAGE_RTOL,
                               err_msg=f"stage{stage} vs stage0 drift")
