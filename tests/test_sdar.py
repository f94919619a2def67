"""SDAR's block-diffusion training step (``models/llama.py`` with
``block_length`` > 0) against its plain reference
(``benchmark/reference/sdar.py``): what the mask lets a noised block's logits
see, the noise, the engine's key, the controls and the share. The mask
kernels against the dense-mask oracle: ``tests/test_sdar_kernels.py``.

Sizes are the benchmark configuration's rehearsal sizes (hidden 64, 2 layers,
8 / 2 heads of 16, 2 of 8 experts held top-2 of width 32, 128 clean tokens =
256 rows, block length 4, vocabulary 512), the model in float32 so that
system and reference agree to float32 rounding.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from benchmark import manifest
from benchmark.families import sdar as family
from benchmark.reference import sdar as ref
from deepspeed_tpu.models import llama
from deepspeed_tpu.ops.pallas import block_diffusion_attention as bd
from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh

with open(os.path.join(manifest.HERE, "configs",
                       "sdar-30b-a3b-chat-ep8-depth6.json")) as f:
    CONFIG = json.load(f)
F32_CONFIG = dict(CONFIG, rehearse_cpu=dict(
    CONFIG["rehearse_cpu"], model={"loss_chunk": 128, "dtype": "float32"},
    train=dict(CONFIG["rehearse_cpu"]["train"], engine=dict(
        CONFIG["rehearse_cpu"]["train"]["engine"], bf16={"enabled": False},
        data_types={"grad_dtype": "fp32"}))))
SEQ = 128
KEY = jax.random.PRNGKey(11)


def _ids(seed=0, rows=2):
    return np.random.default_rng(seed).integers(
        0, 511, (rows, SEQ), dtype=np.int32)


@pytest.fixture(scope="module")
def weights():
    model = family._model(F32_CONFIG, True)
    return jax.jit(lambda: model.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 16), jnp.int32)))()["params"]


@pytest.fixture(scope="module")
def compared(weights):
    """(system, (reference loss, gradient norm, differences)) in float32."""
    system = family.system_step(F32_CONFIG, weights, _ids(), jax.devices()[0],
                                True, key=KEY)
    return system, family.compare(F32_CONFIG, weights, _ids(),
                                  jax.devices()[0], True, system)


# ------------------------------------------------ system against reference

def test_the_step_equals_the_reference_loss_branches_and_every_leaf(compared):
    (loss, layers, _, noise), (want, _, diffs) = compared
    assert abs(float(loss) - want) < 2e-5, (float(loss), want)
    assert diffs["routing_differs"] == 0
    assert diffs["attn_out_noised_rel"] < 1e-5
    assert diffs["attn_out_clean_rel"] < 1e-5
    assert diffs["ffn_out_rel"] < 1e-5
    leaves = diffs["grad_leaf_rel"]
    assert set(leaves) == set(CONFIG["train"]["tolerance"]["grad_leaf_rel"])
    assert max(leaves.values()) < 2e-4, leaves
    assert len(layers) == 2 and layers[0]["attn_out"].shape == (2, 2 * SEQ, 64)
    assert 0.2 < diffs["masked_share"] < 0.8 and noise[0].shape == (2, SEQ)


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_reference_with_one_fault_is_told_apart(weights, compared, control):
    """Each of the five controls on the mathematics moves the loss or a
    branch or a leaf by far more than float32 rounding."""
    system, (want, _, honest) = compared
    loss, _, diffs = family.compare(F32_CONFIG, weights, _ids(),
                                    jax.devices()[0], True, system,
                                    control=control)
    moved = max(abs(loss - want) / 2e-5,
                diffs["attn_out_noised_rel"] / 1e-5,
                diffs["attn_out_clean_rel"] / 1e-5,
                max(diffs["grad_leaf_rel"].values()) / 2e-4)
    assert moved > 50, (control, loss, want, diffs)
    if control in ("own_block_leaked", "causal_in_block"):
        # the mask's faults show in the attention branch itself
        assert max(diffs["attn_out_noised_rel"],
                   diffs["attn_out_clean_rel"]) > 1e-2
    else:
        # the loss's faults leave the branches alone (shifted targets hardly
        # move a loss of ln V at random weights: the head's gradient does)
        assert diffs["attn_out_noised_rel"] < 1e-5
        assert abs(loss - want) > 1e-2 or diffs["grad_leaf_rel"]["lm_head"] > 0.1


def test_the_step_in_a_lower_precision_is_told_apart(weights, compared):
    """The sixth control: every weight matrix rounded to fp8
    (``benchmark/tools/precision_control.py``) reads far over the honest
    float32 step on the branches and the leaves."""
    from benchmark.tools.precision_control import fp8_matrices
    system = family.system_step(F32_CONFIG, fp8_matrices(weights), _ids(),
                                jax.devices()[0], True, key=KEY)
    _, _, diffs = family.compare(F32_CONFIG, weights, _ids(),
                                 jax.devices()[0], True, system)
    assert diffs["attn_out_noised_rel"] > 1e-2
    assert min(diffs["grad_leaf_rel"].values()) > 1e-3


@pytest.mark.parametrize("which", ["sdar", "keye_vl2"])
def test_the_eight_shares_add_up_to_the_uncut_layer(which):
    """The share tied to the model: the partial expert sums of the 4 ranks
    (the rehearsal's expert_parallel_size) add up to the reference's layer
    with all 8 experts held — for every configuration that is one rank's
    share of this expert layer (SDAR's, and since PR 65 Keye-VL-2.0's)."""
    if which == "sdar":
        cfg = family.model_config(F32_CONFIG, True)
    else:
        from benchmark.families import keye_vl2
        with open(os.path.join(
                manifest.HERE, "configs",
                "keye-vl-2.0-30b-a3b-ep8-depth6.json")) as f:
            cfg = keye_vl2.model_config(json.load(f), True)
        assert cfg.index_topk and cfg.experts_held == 2
    E, held, H, F = cfg.num_experts, cfg.experts_held, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    full = {"router": 0.5 * jax.random.normal(ks[0], (H, E)),
            "gate": 0.2 * jax.random.normal(ks[1], (E, H, F)),
            "up": 0.2 * jax.random.normal(ks[2], (E, H, F)),
            "down": 0.2 * jax.random.normal(ks[3], (E, F, H))}
    x = jax.random.normal(ks[4], (1, 2 * SEQ, H))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(x.reshape(-1, H), full, cfg.num_experts_per_tok, 0)
    from deepspeed_tpu.moe.dropless import DroplessMoE
    total = 0.0
    for rank in range(E // held):
        layer = DroplessMoE(E, cfg.num_experts_per_tok, F, norm_topk_prob=True,
                            balance_coeff=0.0, z_coeff=0.0, dtype=jnp.float32,
                            experts_held=held, expert_share=rank)
        sl = slice(rank * held, (rank + 1) * held)
        p = {"router": full["router"], "gate_proj": full["gate"][sl],
             "up_proj": full["up"][sl], "down_proj": full["down"][sl]}
        total = total + layer.apply({"params": p}, x)
    assert np.allclose(total.reshape(-1, H), want, atol=2e-5)


# ------------------------------------------ what a noised block's logits see

def _logits(weights, ids, noisy):
    """Logits of the noised half for GIVEN noisy ids: the model's own blocks
    over [noisy, ids] rows, the final norm and the head."""
    cfg = family.model_config(F32_CONFIG, True)
    rows = jnp.concatenate([noisy, ids], axis=1)
    x = weights["embed_tokens"][rows]
    pos = jnp.concatenate([jnp.arange(SEQ)] * 2)
    blk = weights["layers"]["blk"]
    for i in range(cfg.n_layers):
        p = jax.tree_util.tree_map(lambda t: t[i], blk)
        x = llama.LlamaBlock(cfg, 0, True).apply({"params": p}, x, pos)
    x = llama.RMSNorm(eps=cfg.rms_eps, dtype=jnp.float32).apply(
        {"params": weights["norm"]}, x[:, :SEQ])
    return x @ weights["lm_head"].T


def test_a_noised_blocks_logits_move_only_with_what_the_mask_lets_it_see(
        weights):
    ids = jnp.asarray(_ids(rows=1))
    noisy = jnp.where(jnp.arange(SEQ) % 3 == 0, 511, ids)
    base = jax.jit(_logits)(weights, ids, noisy)
    b = 9                                     # the block looked at
    own = slice(4 * b, 4 * b + 4)

    def moved(ids2, noisy2):
        got = jax.jit(_logits)(weights, ids2, noisy2)
        return float(jnp.max(jnp.abs(got[:, own] - base[:, own])))

    other = lambda x: (x + 7) % 511           # noqa: E731
    # clean tokens of its own and of later blocks: unseen
    assert moved(ids.at[:, 4 * b:].set(other(ids[:, 4 * b:])), noisy) == 0.0
    # noised tokens of every other block: unseen
    rest = jnp.ones(SEQ, bool).at[own].set(False)
    assert moved(ids, jnp.where(rest, other(noisy), noisy)) == 0.0
    # a clean token of an earlier block, and a noised one of its own: seen
    assert moved(ids.at[:, 4 * b - 1].set(other(ids[:, 4 * b - 1])),
                 noisy) > 1e-4
    assert moved(ids, noisy.at[:, 4 * b + 1].set(
        other(noisy[:, 4 * b + 1]))) > 1e-4


# ----------------------------------------------------------------- the noise

def test_the_noise_is_a_function_of_its_key_alone():
    ids = jnp.asarray(_ids(rows=4))
    a = llama.block_diffusion_noise(KEY, ids, 4, 1e-3, 511)
    b = llama.block_diffusion_noise(KEY, ids + 0, 4, 1e-3, 511)
    c = llama.block_diffusion_noise(jax.random.PRNGKey(12), ids, 4, 1e-3, 511)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    noisy, masked, t_row = a
    assert np.array_equal(noisy, np.where(masked, 511, ids))
    # one t a block, the documented draw order
    key_t, key_v = jax.random.split(KEY)
    t = 1e-3 + (1 - 1e-3) * jax.random.uniform(key_t, (4, SEQ // 4))
    assert np.array_equal(t_row, np.repeat(t, 4, axis=1))
    assert np.array_equal(masked, jax.random.uniform(key_v, (4, SEQ)) < t_row)
    with pytest.raises(ValueError, match="whole number"):
        llama.block_diffusion_noise(KEY, ids[:, :126], 4, 1e-3, 511)


def test_a_block_is_masked_by_its_t_in_the_mean():
    ids = jnp.zeros((64, 4096), jnp.int32)
    _, masked, t_row = llama.block_diffusion_noise(KEY, ids, 32, 1e-3, 9)
    masked, t = np.asarray(masked, np.float64), np.asarray(t_row)
    for lo, hi in ((0.0, 0.25), (0.25, 0.5), (0.5, 0.75), (0.75, 1.0)):
        sel = (t >= lo) & (t < hi)
        assert masked[sel].mean() == pytest.approx(t[sel].mean(), abs=5e-3)
    assert masked.mean() == pytest.approx(0.5, abs=5e-3)


# ---------------------------------------------------------------- the engine

def _engine(cfg, gas=1, seed=3):
    ds = {"train_batch_size": 2 * gas, "gradient_accumulation_steps": gas,
          "zero_optimization": {"stage": 0},
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "steps_per_print": 10 ** 9, "seed": seed}
    engine, _, _, _ = dstpu.initialize(
        config=ds, model=llama.LlamaForCausalLM(cfg),
        mesh=make_mesh(MeshConfig(data=1), devices=jax.devices()[:1]))
    return engine


def _apply_loss(cfg, params, ids, key):
    return float(llama.LlamaForCausalLM(cfg).apply(
        {"params": params}, ids, labels=ids, mutable=["losses", "stats"],
        rngs={"diffusion": key})[0])


def test_the_engine_hands_the_steps_key_to_the_diffusion_stream():
    """The first step's loss is the model's under ``first_step_key(seed)``,
    the second step's is under another key, and the gauges carry the step's
    masked share and largest weight."""
    cfg = family.model_config(F32_CONFIG, True)
    engine = _engine(cfg)
    ids = _ids(seed=1)
    params = jax.device_get(engine.state.params) if engine.state else None
    loss = float(engine.forward({"input_ids": ids}))
    params = jax.device_get(engine.state.params)
    assert loss == pytest.approx(
        _apply_loss(cfg, params, ids, family.first_step_key(3)), abs=2e-5)
    first = float(engine.train_batch({"input_ids": ids}))
    again = float(engine.train_batch({"input_ids": ids}))
    assert np.isfinite(first) and abs(first - again) > 1e-3
    gauges = engine.telemetry_flush()["gauges"]
    assert 0.2 < gauges["diffusion/masked_share"] < 0.8
    assert gauges["diffusion/weight_max"] > 1.0
    assert gauges["attention/bd_tile_overcompute"] == pytest.approx(
        bd.tile_overcompute(SEQ, 4, 64))
    assert gauges["attention/bd_tiles_per_grid_step"] > 1.0
    assert gauges["moe/dropped_rows"] == 0
    assert 0.0 < gauges["moe/rows_held_share"] < 1.0


def test_under_accumulation_each_micro_batch_draws_from_its_own_split():
    cfg = family.model_config(F32_CONFIG, True)
    engine = _engine(cfg, gas=2)
    ids = _ids(seed=2, rows=4)
    engine.forward({"input_ids": ids[:2]})            # builds the state
    params = jax.device_get(engine.state.params)
    engine = _engine(cfg, gas=2)
    loss = float(engine.train_batch({"input_ids": ids}))
    keys = jax.random.split(family.first_step_key(3), 2)
    want = np.mean([_apply_loss(cfg, params, ids[2 * i:2 * i + 2], keys[i])
                    for i in range(2)])
    assert loss == pytest.approx(want, abs=2e-5)


def test_no_two_l_by_two_l_array_is_in_the_lowered_step():
    """Sizes all distinct: L 96 (2L 192), hidden 64, heads 8 x 16 (q 128),
    experts 32 wide, vocabulary 512: no array of the step has two dimensions
    of 2L, and no [2L, 2L] mask or bias of any dtype exists."""
    cfg = dataclasses.replace(family.model_config(F32_CONFIG, True),
                              max_seq_len=96)
    engine = _engine(cfg)
    ids = np.random.default_rng(0).integers(0, 511, (2, 96), dtype=np.int32)
    engine.train_batch({"input_ids": ids})
    text = engine.lower_train_step({"input_ids": ids}).as_text(debug_info=True)
    assert "bd_fwd" in text and "bd_bwd" in text
    shapes = set(re.findall(r"tensor<([0-9x]+)x[a-z]+[0-9]*>", text))
    assert shapes and not [s for s in shapes
                           if s.split("x").count("192") >= 2], shapes


def test_without_a_block_length_the_model_and_the_step_are_as_before():
    """``block_length`` 0 (every accepted configuration): no rng stream, no
    diffusion statistics, the causal path, and nothing of this PR in the
    lowered step. (That the ten accepted cells' lowered steps are the
    parent's texts is held at one path by CHANGES.md's PR 60 entry.)"""
    from benchmark.families import olmoe
    with open(os.path.join(manifest.HERE, "configs",
                           "olmoe-1b-7b-0125-depth1.json")) as f:
        cfg = olmoe.model_config(json.load(f), rehearse=True)
    model = llama.LlamaForCausalLM(cfg)
    assert model.rng_streams == () and model.stat_maxima == ()
    assert not [g for g in model.stat_gauges.values()
                if g.startswith("diffusion/")]
    assert cfg.head_dim == cfg.hidden_size // cfg.n_heads
    engine = _engine(dataclasses.replace(cfg, dtype=jnp.float32))
    engine.train_batch({"input_ids": _ids()})
    text = engine.lower_train_step({"input_ids": _ids()}).as_text(
        debug_info=True)
    for word in ("bd_fwd", "bd_bwd", "bd_noise", "rng_bit_generator",
                 "threefry"):
        assert word not in text, word


@pytest.mark.parametrize("name", ["attention/bd_tile_overcompute",
                                  "attention/bd_tiles_per_grid_step",
                                  "diffusion/masked_share",
                                  "diffusion/weight_max"])
def test_the_gauges_are_documented_and_the_scopes_listed(name):
    """docs/observability.md's train table and ``spans.annotate``'s list."""
    from deepspeed_tpu.telemetry import spans
    from tests.test_metric_names import documented_metric_names
    assert name in documented_metric_names()
    assert name in spans.annotate.__doc__
    for scope in ("bd_fwd", "bd_bwd", "bd_bwd_dq_sum", "bd_noise"):
        assert scope in spans.annotate.__doc__


def test_block_diffusion_without_the_chunked_head_is_refused():
    """The engine hands labels only to a model with the fused head: a
    block-diffusion config without it would train next-token, silently."""
    with pytest.raises(ValueError, match="loss_chunk"):
        llama.llama_tiny(block_length=4)
    assert llama.llama_tiny(block_length=4, loss_chunk=64).block_length == 4
