"""The engine cases of the ZeRO matrices (``tests/test_zero_matrix.py``'s
census, ``tests/test_zero_matrix_fp32*.py``,
``tests/test_zero_matrix_bf16*.py``): tiny models of every family the repo
trains, an engine over the CPU's eight devices, and one step's gradients and
a three-step trajectory held against stage 0's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import gpt2, llama
from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh

VOCAB, SEQ = 512, 32


def _gpt2(**kw):
    return gpt2.GPT2LMHeadModel(gpt2.gpt2_tiny(**kw))


def _llama(**kw):
    return llama.LlamaForCausalLM(llama.llama_tiny(
        hidden_size=64, intermediate_size=96, n_heads=4, n_kv_heads=2, **kw))


def _olmoe(**kw):
    return llama.LlamaForCausalLM(llama.llama_tiny(
        hidden_size=64, intermediate_size=32, n_heads=4, n_kv_heads=0,
        num_experts=8, num_experts_per_tok=2, qk_norm=True, **kw))


FAMILIES = ("gpt2-scanned", "gpt2-unrolled", "llama", "olmoe")
MODELS = {
    "gpt2-scanned": lambda dtype: _gpt2(scan_layers=True, remat=True,
                                        dtype=dtype),
    "gpt2-unrolled": lambda dtype: _gpt2(scan_layers=False, dtype=dtype),
    # rematted like the scanned GPT-2: the gather edge's promise (weights
    # gathered again in the backward scan, never saved gathered) is made
    # for a rematted block; without remat the forward scan's residuals
    # hold every layer's gathered weights (ROADMAP S15)
    "llama": lambda dtype: _llama(dtype=dtype, remat=True),
    "olmoe": lambda dtype: _olmoe(dtype=dtype, remat=True),
    # S15 itself, held by the census's strict xfail
    "llama-no-remat": lambda dtype: _llama(dtype=dtype),
    # the GPT-2 head's three forms
    "gpt2-tied": lambda dtype: _gpt2(scan_layers=True, dtype=dtype),
    "gpt2-untied": lambda dtype: _gpt2(scan_layers=True, dtype=dtype,
                                       tie_word_embeddings=False),
    "gpt2-tied-chunked": lambda dtype: _gpt2(scan_layers=True, dtype=dtype,
                                             loss_chunk=16),
}
PRECISIONS = {"fp32-gas1": (jnp.float32, 1), "bf16-gas2": (jnp.bfloat16, 2)}


def engine(family, stage, precision="fp32-gas1", mesh=None):
    dtype, gas = PRECISIONS[precision]
    cfg = {"train_batch_size": 8 * gas, "gradient_accumulation_steps": gas,
           "zero_optimization": {"stage": stage,
                                 "stage3_param_persistence_threshold": 0},
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "steps_per_print": 10 ** 9, "seed": 5}
    if dtype == jnp.bfloat16:
        cfg["bf16"] = {"enabled": True}
        cfg["data_types"] = {"grad_dtype": "bf16"}
    out, _, _, _ = dstpu.initialize(
        config=cfg, model=MODELS[family](dtype),
        mesh=mesh or make_mesh(MeshConfig(data=8)))
    return out


def batch(precision="fp32-gas1"):
    rows = 8 * PRECISIONS[precision][1]
    return {"input_ids": np.random.RandomState(0).randint(
        0, VOCAB, (rows, SEQ)).astype(np.int32)}


def trajectory(family, stage, precision):
    """One optimizer step through ``forward()`` / ``backward()`` /
    ``step()``, whose accumulated loss and gradients are kept leaf by leaf,
    then three fused ``train_batch`` steps: their losses and the last
    global gradient norm."""
    eng = engine(family, stage, precision)
    b = batch(precision)
    for micro in np.split(b["input_ids"], PRECISIONS[precision][1]):
        eng.forward({"input_ids": micro})
        eng.backward()
    first = float(eng._accum_loss), jax.device_get(eng._pending_grads)
    eng.step()
    losses = [float(eng.train_batch(b)) for _ in range(3)]
    return first, losses, float(eng.get_global_grad_norm())


@functools.lru_cache(maxsize=None)
def _stage0(family, precision):
    return trajectory(family, 0, precision)


# Relative limits, each set from the largest stage-0 against stage-N
# reading over its cases (builder, PR 29; CPU, eight devices) times 5 to
# 50. ``grad_leaf`` holds a leaf's largest error against the leaf's own
# largest element: float32 read 6.5e-7, bf16 compute with bf16 gradients
# over two micro-batches 3.5e-3 (bf16's epsilon is 2^-8 = 3.9e-3; LLaMA's
# embedding at stage 3). Losses read 1.6e-7 and 1.1e-5, the last global
# gradient norm 2.2e-7 and 6.1e-4. float32 gradients are held element by
# element as well, at the limits PR 25's scanned-GPT-2 case had
LIMITS = {
    "fp32-gas1": dict(loss=5e-6, grad_leaf=1e-5, norm=1e-5,
                      grad_elem=dict(rtol=1e-4, atol=1e-5)),
    "bf16-gas2": dict(loss=2e-4, grad_leaf=2e-2, norm=5e-3),
}


def assert_trajectory_matches_stage0(family, stage, precision):
    """ZeRO stage 1, 2 or 3 gives stage 0's numbers: sharding the state,
    the gradients or the parameters changes where numbers live, not what
    they are. The first step's GRADIENTS agree leaf by leaf (AdamW's update
    does not change when a leaf's gradient is scaled, so a bias gradient
    that is summed where it should be averaged, or that loses an
    all-reduce, moves no loss and hides in the global norm), then three
    fused steps' losses and the last gradient norm agree, and the loss
    falls. In float32 to rounding; under bf16 to bf16's."""
    (want_l0, want_g), want, want_norm = _stage0(family, precision)
    (got_l0, got_g), got, got_norm = trajectory(family, stage, precision)
    lim = LIMITS[precision]
    np.testing.assert_allclose(got_l0, want_l0, rtol=lim["loss"])
    want_leaves = jax.tree_util.tree_flatten_with_path(want_g)[0]
    got_leaves = jax.tree_util.tree_leaves(got_g)
    assert len(got_leaves) == len(want_leaves)
    for (path, a), b in zip(want_leaves, got_leaves):
        name = jax.tree_util.keystr(path)
        scale = max(float(np.abs(a).max()), 1e-6)
        assert float(np.abs(a - b).max()) <= lim["grad_leaf"] * scale, name
        if "grad_elem" in lim:
            np.testing.assert_allclose(b, a, err_msg=name,
                                       **lim["grad_elem"])
    np.testing.assert_allclose(got, want, rtol=lim["loss"])
    np.testing.assert_allclose(got_norm, want_norm, rtol=lim["norm"])
    assert got[-1] < got[0]
