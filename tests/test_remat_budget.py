"""The byte budget of what a rematted block keeps (``runtime/remat_budget.py``):
the selection, the KL gradient of a learned-sparse stack, a name's bytes at
two cells' real shapes, the reserve counted
from each cell's shapes against what its program compiled to, the engine's
figure on abstract state, and the fall-back when the compiler refuses the
program.
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
# of 64, ten branches of 2,048 (bf16); the nine scans' y (64 heads of 64)
# and a float32 state [128, 64] a head every 128 tokens
GRANITE = {"mlp_fc": 10 * 16384 * 2 * 8192 * 2,
           "mixer_in": 9 * 16384 * 8512 * 2,
           "scan_states": 9 * 16384 * (4096 * 2 + 4 * 64 * 128 * 64 // 128),
           "attn_proj": 10 * 16384 * 2048 * 2,
           "qkv": 16384 * (32 + 2 * 8) * 64 * 2}


@pytest.mark.parametrize("budget,want", [
    (0, ()),
    (GRANITE["attn_proj"] - 1, ("qkv",)),            # passed over, not a stop
    (1.2 * GB, ("attn_proj", "qkv")),
    (3.3 * GB, ("attn_proj", "qkv", "mixer_in")),    # the MLP's 5.4 GB never
    (9 * GB, ("attn_proj", "qkv", "mixer_in", "scan_states")),
    (13 * GB, ("attn_proj", "qkv", "mixer_in", "scan_states", "mlp_fc"))])
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
    # a block input a layer and the ends of the block in flight (four
    # streams wide), and what its widest branch holds a row
    assert rb.reserve_bytes(4096, 3584, 6, 2, 81920, streams=4) \
        == 4096 * (3584 * 2 * 4 * (6 + rb.BLOCK_END_WIDTHS) + 81920)


def test_the_counts_of_a_branch_in_flight():
    """Laguna's full-attention layer (48 heads of 128 over 8, an output
    gate, 2.5 rows of float32 dq partials a row at 16,384 in chunks of
    4,096) is 40 widths of its 2,048-wide stream; a window layer leaves no
    partials; granite's gated MLP of 8,192 is 12 widths, a mixer's 8,512
    projected columns 8.3."""
    from deepspeed_tpu.ops.pallas.flash_attention import bwd_dq_slab_rows
    assert bwd_dq_slab_rows(16384, 128, 128, 2) == 2.5
    assert bwd_dq_slab_rows(8192, 256, 256, 2) == 2.5     # chunks of 2,048
    assert bwd_dq_slab_rows(4096, 192, 128, 2) == 0.0     # one chunk
    assert bwd_dq_slab_rows(1024, 64, 64, 2) == 0.0       # the whole row
    q = 48 * 128
    assert rb.attention_inflight(q, q, 2 * 8 * 128, 2, 2.5, gated=True) \
        == 2 * (3 * q + 5 * q + 2048) + 10 * q == 40 * 4096
    assert rb.attention_inflight(q, q, 2048, 2) == 2 * (6 * q + 2048)
    assert rb.mlp_inflight(8192, 2) == 12 * 4096
    assert rb.mlp_inflight(3712, 2, gated=False) == 4 * 3712
    assert rb.projection_inflight(8512, 2) == 4 * 8512


# the eight cells that remat block by block, at their configurations'
# shapes: MB free before the reserve as the engine counts it on the cell's
# abstract state (the log line ``rematted blocks keep ...`` of ``python -m
# benchmark.tools.rehearse_compile <cell>``), the widths of one stream the
# compiled program held beside the engine's bytes, a block input a layer
# and the kept names (PERF.md Findings PR 61 and PR 64: the larger of the
# two tables' C for the set the rule picks), and the names the rule keeps
CELLS = {
    "granite4hmicro-train-1chip-s16384": (5101, 8.1, (
        "attn_proj", "qkv", "mixer_in")),
    "qwen3next-train-1chip-s8192": (7152, 23.7, (
        "moe_scores", "attn_proj", "qkv", "mixer_in", "scan_states",
        "mlp_fc")),
    "xing4-train-1chip-s4096": (3123, 4.0, (
        "moe_scores", "attn_proj", "mlp_proj", "qkv", "mlp_fc")),
    "nemotron3nano-train-1chip-s16384": (6574, 12.3, (
        "moe_scores", "qkv", "mixer_in", "scan_states", "mlp_fc")),
    "kanana2-train-1chip-s16384": (6286, 33.9, (
        "moe_scores", "attn_proj", "mlp_fc")),
    "laguna-train-1chip-s16384": (6229, 40.1, (
        "moe_scores", "attn_proj", "qkv", "mlp_fc")),
    "smallthinker-train-1chip-s16384": (6720, 19.0, (
        "moe_scores", "attn_proj", "qkv")),
    # PR 68: 13.0 GB of step state; the compiled peak (13.945 GB) stands
    # UNDER held + kept (13.004 + 1.576): the 14 B a parameter are not all
    # alive at the peak, so nothing is left unexplained (-14.1 widths of
    # 62.9 MB) and ``scan_states`` (1,510 MB at 128 x 256) stays out by 1.4 GB
    "olmohybrid-train-1chip-s8192": (2907, -14.1, (
        "attn_proj", "qkv", "mixer_in"))}


def _stack_of(cell_name):
    """The figures a cell's model hands ``keep_for_stack``, from its
    configuration file through its family, as the model's ``__call__``
    reads them."""
    import importlib
    from benchmark import manifest
    bench = manifest.load()
    cell = manifest.cell_of(bench, cell_name)
    config = manifest.config_of(bench, cell)
    traffic = manifest.traffic_of(cell)
    cfg = manifest.family_module(config).model_config(config, False)
    model = importlib.import_module(type(cfg).__module__)
    seq = traffic["seq_len"]
    return dict(
        rows=traffic["global_batch"] * seq, hidden=cfg.hidden_size,
        layers=cfg.num_hidden_layers
        + getattr(cfg, "num_nextn_predict_layers", 0), itemsize=2,
        row_bytes=model.remat_row_bytes(cfg),
        inflight_row_bytes=model.remat_inflight_row_bytes(cfg, seq),
        streams=getattr(cfg, "hc_mult", 1))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_reserve_from_a_cells_shapes_covers_its_compiled_peak(cell):
    """The reserve is at least what the cell's compiled program held beside
    the engine's bytes and the kept names, and with it the rule keeps the
    set the cell was compiled and measured with — Kanana-2's ``qkv`` (3,334
    MB: refused by the compiler at 16.17 GiB when forced) stays out by the
    rule's own arithmetic; the gauges say what was taken."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.telemetry.registry import default_registry
    free_mb, compiled_widths, want = CELLS[cell]
    stack = _stack_of(cell)
    sizes = stack.pop("row_bytes")
    reserve = rb.reserve_bytes(**stack)
    width = stack["rows"] * stack["hidden"] * 2
    assert reserve / width - stack["layers"] * stack["streams"] \
        >= compiled_widths
    with mesh_lib.layout_pins(None, remat_free_bytes=free_mb * 10 ** 6):
        kept = rb.keep_for_stack(REMAT_CANDIDATES, row_bytes=sizes, **stack)
    assert kept == want
    if cell.startswith("kanana2"):
        assert "qkv" not in kept and sizes["qkv"] * stack["rows"] > 3.3 * GB
    gauge = default_registry().peek_gauge
    assert gauge("remat/reserve_mb") == pytest.approx(reserve / 1e6)
    assert gauge("remat/budget_mb") == pytest.approx(free_mb - reserve / 1e6)
    assert gauge("remat/kept_names") == len(want)
    assert gauge("remat/scan_states_kept") == ("scan_states" in want)
    # every name kept and the reserve fit what the chip has left
    assert gauge("remat/kept_mb") + gauge("remat/reserve_mb") <= free_mb


# the twelfth cell's stack (``models/llama.py``: none of the candidates, one
# name of its own — the KL's gradient in the indexer's scores) as ``CELLS``
# has the others: MB free as the engine counts it, and the widths of its
# stream the program compiled with the name kept (peak 15.021 GB, PERF.md
# Findings PR 66) held beside the engine's bytes, a block input a layer, the
# selection's pin and the kept gradient
KEYE = ("keyevl2-train-1chip-s16384", 6683, 52.6)


@pytest.mark.parametrize("seq,layers,free_mb,kept", [
    (16384, 6, 6683, 1),      # the cell: 1,661 MB of a budget of 2,412
    (16384, 6, 5900, 0),      # 0.8 GB less free: 1,629 left
    (32768, 6, 6683, 0),      # twice the tokens: 6.5 GB of tiles
    (16384, 12, 6683, 0),     # twice the layers a chip: 3.3 GB
    (16384, 6, 0, 0)],        # no engine, or a step refused once
    ids=["the-cell", "less-free", "longer", "deeper", "zero"])
def test_the_keye_stack_keeps_the_kl_gradient_by_its_bytes(seq, layers,
                                                           free_mb, kept):
    """A layer's gradient is its 528 causal tiles of 512 x 512 in bf16 at
    16,384 tokens — 277 MB, 1.66 GB for six, where the dense array is 537 MB
    a layer; kept where that fits the free bytes less the selection's pin
    less the reserve, which covers what the cell's program compiled to."""
    import dataclasses
    from benchmark import manifest
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.telemetry.registry import default_registry
    assert rb.kl_grad_bytes(1, 16384, 1, 2) == 528 * 512 * 512 * 2 \
        == 276_824_064
    assert rb.kl_grad_bytes(1, 16384, 6, 2) == 1_660_944_384
    # a padded sequence: 1,000 tokens are 8 tiles of 128 a side, 36 causal
    assert rb.kl_grad_bytes(2, 1000, 3, 4, tile=128) \
        == 3 * 2 * 36 * 128 * 128 * 4
    name, cell_free_mb, compiled_widths = KEYE
    bench = manifest.load()
    config = manifest.config_of(bench, manifest.cell_of(bench, name))
    cfg = dataclasses.replace(
        manifest.family_module(config).model_config(config, False),
        n_layers=layers)
    inflight = llama.remat_inflight_row_bytes(cfg, seq)
    reserve = rb.reserve_bytes(seq, cfg.hidden_size, layers, 2, inflight)
    if (seq, layers) == (16384, 6):
        # the attention branch is the widest: q, o, their cotangents, dk, dv
        # and the dq partials, a row of the float32 scores, of the mask and
        # of its transpose, and 1 / 16,384 of a layer's gradient
        assert inflight == 92160 + 6 * 16384 + 276_824_064 // 16384
        assert reserve / (seq * cfg.hidden_size * 2) - layers \
            >= compiled_widths
        assert cell_free_mb * 10 ** 6 >= reserve + 1_660_944_384 \
            + rb.selection_pin_bytes(1, seq, layers)
    x = jax.ShapeDtypeStruct((1, seq, cfg.hidden_size), jnp.bfloat16)
    policy = jax.checkpoint_policies.nothing_saveable
    with mesh_lib.layout_pins(None, remat_free_bytes=free_mb * 10 ** 6):
        assert llama._pinned(cfg, policy, x) is not policy
    gauge = default_registry().peek_gauge
    assert gauge("remat/dsa_kl_grad_kept") == kept
    need = rb.kl_grad_bytes(1, seq, layers, 2)
    assert gauge("remat/dsa_kl_grad_mb") == pytest.approx(need / 1e6)
    pin = rb.selection_pin_bytes(1, seq, layers)
    assert kept == (need + pin + reserve <= free_mb * 10 ** 6)


def test_a_stack_with_no_figures_keeps_the_base_names():
    """``models/llama._maybe_remat`` (SDAR's scanned stack: measured slower
    with more, PERF.md Findings PR 61) calls with no figures: the policy
    saves the base names and nothing else, whatever the trace was handed."""
    from jax.ad_checkpoint import checkpoint_name
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.parallel import mesh as mesh_lib

    def block(x):
        y = checkpoint_name(jnp.sin(x), "qkv")
        return checkpoint_name(jnp.cos(y), "flash_o").sum()

    with mesh_lib.layout_pins(None, remat_free_bytes=8 * GB):
        policy = gpt2.block_remat_policy()
        text = str(jax.make_jaxpr(jax.grad(jax.checkpoint(
            block, policy=policy)))(jnp.ones(8)))
    # what ``qkv`` names is made again in the backward pass, what
    # ``flash_o`` names is not
    assert text.count("name[name=qkv]") == 2
    assert text.count("name[name=flash_o]") == 1
    assert gpt2.REMAT_CANDIDATES.index("scan_states") \
        == gpt2.REMAT_CANDIDATES.index("mixer_in") + 1


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
