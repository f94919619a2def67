"""The byte budget of what a rematted block keeps (``runtime/remat_budget.py``):
the selection, a name's bytes at two cells' real shapes, the engine's figure
on abstract state, and the fall-back when the compiler refuses the program.
What a model's step then holds: ``tests/test_laguna_remat.py``,
``tests/test_qwen3_next_remat_attention.py``.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.models.gpt2 import REMAT_CANDIDATES
from deepspeed_tpu.runtime import remat_budget as rb

GB = 10 ** 9
# the granite cell's names at 16,384 rows: 10 gated MLPs of 8,192, nine
# mixers whose ``in_proj`` writes 8,512 columns, one layer of 32 / 8 heads
# of 64, ten branches of 2,048 (bf16)
GRANITE = {"mlp_fc": 10 * 16384 * 2 * 8192 * 2,
           "mixer_in": 9 * 16384 * 8512 * 2,
           "attn_proj": 10 * 16384 * 2048 * 2,
           "qkv": 16384 * (32 + 2 * 8) * 64 * 2}


@pytest.mark.parametrize("budget,want", [
    (0, ()),
    (GRANITE["attn_proj"] - 1, ("qkv",)),            # passed over, not a stop
    (1.2 * GB, ("attn_proj", "qkv")),
    (3.3 * GB, ("attn_proj", "qkv", "mixer_in")),    # the MLP's 5.4 GB never
    (9 * GB, ("attn_proj", "qkv", "mixer_in", "mlp_fc"))])
def test_names_are_kept_in_order_whole_or_not_at_all(budget, want):
    assert rb.kept_names(REMAT_CANDIDATES, GRANITE, budget) == want
    assert sum(GRANITE[n] for n in want) <= budget


def _laguna_cell():
    from deepspeed_tpu.models.laguna import (DENSE, FULL, SLIDING, SPARSE,
                                             LagunaConfig, remat_row_bytes)
    rope = {"rope_type": "default", "rope_theta": 1e4,
            "partial_rotary_factor": 1}
    return remat_row_bytes(LagunaConfig(
        num_hidden_layers=5, experts_held=32,
        layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
        mlp_layer_types=[DENSE] + [SPARSE] * 4,
        num_attention_heads_per_layer=[48, 64, 64, 64, 48],
        rope_parameters={FULL: rope, SLIDING: rope}))


def _granite_cell():
    from deepspeed_tpu.models.granite_hybrid import (
        ATTENTION, MAMBA, GraniteHybridConfig, remat_row_bytes)
    return remat_row_bytes(GraniteHybridConfig(
        num_hidden_layers=10, layer_types=[MAMBA] * 5 + [ATTENTION]
        + [MAMBA] * 4))


@pytest.mark.parametrize("row_bytes,want", [
    (_granite_cell, GRANITE),
    # Laguna's five layers: q (48 or 64 heads of 128), k and v (8), the gate
    # a head; the dense layer's 8,192 and the four shared experts' 512,
    # twice each (gated); float32 logits against 256 experts
    (_laguna_cell, {
        "qkv": 16384 * 2 * (2 * (64 * 128 + 48) + 3 * (80 * 128 + 64)),
        "attn_proj": 5 * 16384 * 2048 * 2,
        "mlp_fc": 16384 * 2 * (2 * 8192 + 4 * 2 * 512),
        "moe_scores": 4 * 16384 * 256 * 4})],
    ids=["granite", "laguna"])
def test_a_names_bytes_at_a_cells_shapes(row_bytes, want):
    assert rb.name_bytes(16384, row_bytes()) == want


def test_the_budget_is_what_the_table_and_the_reserve_leave():
    held = 10 * GB
    assert rb.free_bytes("cpu", held) == 0             # a kind not known
    assert rb.free_bytes("TPU v5 lite", 17 * GB) == 0
    assert rb.free_bytes("TPU v5 lite", held) \
        == 16_911_433_728 - 10 ** 9 - held
    # a block input a layer (four streams wide) and the constant's widths
    assert rb.reserve_bytes(4096, 3584, 6, 2, streams=4) \
        == 4096 * 3584 * 2 * (24 + rb.RESERVE_BLOCK_WIDTHS)


def test_the_engine_counts_what_one_chip_holds_on_abstract_state():
    """``_held_bytes``: a chip's shard of the state, and of the parameters
    once more in bf16 for the compute copy and once for the gradients —
    from shapes and shardings alone."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    split, whole = (NamedSharding(mesh, PartitionSpec(*spec))
                    for spec in (("data",), ()))
    SDS = jax.ShapeDtypeStruct
    params = {"w": SDS((8, 512), jnp.float32), "step": SDS((), jnp.int32)}
    moments = {"m": {"w": SDS((8, 512), jnp.bfloat16)}}
    state = TrainState(params=params, opt_state=moments, scaler={},
                       global_step=SDS((), jnp.int32),
                       skipped_steps=SDS((), jnp.int32))
    shardings = TrainState(params={"w": split, "step": whole},
                           opt_state={"m": {"w": split}}, scaler={},
                           global_step=whole, skipped_steps=whole)
    engine = types.SimpleNamespace(
        _config=types.SimpleNamespace(grad_dtype="bf16"))
    shard = 2 * 512
    assert DeepSpeedEngine._held_bytes(engine, state, shardings) \
        == shard * (4 + 2) + 3 * 4 + shard * (2 + 2)


@pytest.mark.parametrize("words,falls", [
    ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
     "memory in memory space hbm. Used 17.90G of 15.75G hbm.", True),
    ("INVALID_ARGUMENT: a shape does not match", False)])
def test_a_step_refused_for_memory_is_built_once_more_with_the_base_set(
        words, falls):
    """The compiler refuses the step with what the blocks kept: the engine
    clears the figure, counts ``remat/fell_back_to_base`` and runs the SAME
    call once more under a scope that says 0; any other error is the
    caller's, and a second refusal too."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.telemetry.registry import MetricsRegistry
    engine = types.SimpleNamespace(
        mesh=None, _gather_edge=None, _remat_free_bytes=3 * GB,
        telemetry=MetricsRegistry(), _note_gather_edge=lambda: None)
    engine._run_pinned = functools.partial(DeepSpeedEngine._run_pinned,
                                           engine)
    seen = []

    def step(x):
        seen.append(mesh_lib.pinned_remat_free_bytes())
        if seen[-1]:
            raise RuntimeError(words)
        return x + 1

    step.lower = None                  # (a jitted function has one)
    call = DeepSpeedEngine._pinned(engine, step)
    if not falls:
        with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
            call(1)
        assert seen == [3 * GB] and engine._remat_free_bytes == 3 * GB
        return
    assert call(1) == 2 and seen == [3 * GB, 0]
    assert engine._remat_free_bytes == 0
    assert engine.telemetry.counter("remat/fell_back_to_base").value == 1
    assert call(2) == 3 and seen[-1] == 0          # and stays there
    assert engine.telemetry.counter("remat/fell_back_to_base").value == 1
