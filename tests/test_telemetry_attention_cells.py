"""The latent- and window-attention cells' scope names and gauges reach the
compiled step (ISSUEs 47, 33): a DeepSeek-V3 and a Laguna model through
``dstpu.initialize``. Files beside ``tests/test_telemetry.py``
(``tests/test_telemetry_mixer_cells.py`` is the other) because each case
builds and compiles a whole routed step, a minute or so."""

import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.telemetry import default_registry
from tests.simple_model import base_config


def test_deepseek_v3_scope_names_and_gauges_reach_the_step():
    """ISSUE 47's names: a latent-attention model whose attention takes the
    flash kernels (``use_flash``: the interpreter here) carries
    ``mla_latent`` / ``mla_expand`` / ``mla_rope`` and the chunked
    ``flash_*`` scopes under the module ``mla_attn``, ``dense_mlp`` under
    the leading block's ``mlp`` and the ``moe_*`` scopes under the others',
    in its compiled step's ``op_name``s, and leaves the two gauges of the
    widths the kernels saw."""
    import re
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3ForCausalLM,
                                                  deepseek_v3_tiny)
    default_registry().reset()
    cfg = deepseek_v3_tiny(num_hidden_layers=3, experts_held=4,
                           loss_chunk=16, use_flash=True)
    engine, _, _, _ = dstpu.initialize(config=base_config(),
                                       model=DeepseekV3ForCausalLM(cfg))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 256, (8, 64)).astype(np.int32)}
    engine.train_batch(batch)
    gauges = engine.telemetry_flush()["gauges"]
    assert {"moe/dropped_rows", "moe/rows_held_share", "moe/held_slabs",
            "moe/combine_rows_walked", "moe/rows_max_over_mean"} \
        <= set(gauges)
    assert not {"moe/aux_loss", "moe/z_loss"} & set(gauges)
    assert gauges["attention/mla_qk_dim"] == 48
    assert gauges["attention/mla_v_dim"] == 32
    hlo = engine.lower_train_step(batch).compile().as_text()
    for scope in ("mla_attn/mla_latent", "mla_attn/mla_expand",
                  "mla_attn/mla_rope", "mla_attn/q_proj", "mla_attn/o_proj",
                  # per device inside a shard_map on this mesh of eight
                  "mla_attn/shard_map/flash_fwd_chunk",
                  "mla_attn/shard_map/flash_bwd_chunk",
                  "layer_0/mlp/dense_mlp", "mlp/moe_shared", "mlp/moe_router",
                  "moe_dispatch", "moe_gmm", "moe_combine", "ds_embed",
                  "ds_loss_head"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
    from deepspeed_tpu.telemetry import spans
    for name in ("mla_latent", "mla_expand", "mla_rope", "mla_attn",
                 "attention/mla_qk_dim", "attention/mla_v_dim"):
        assert name in spans.annotate.__doc__, name


def test_laguna_scope_names_and_gauges_reach_the_step():
    """ISSUE 33's names: a Laguna model whose sliding layers take the window
    kernels (``use_flash``: the interpreter here) carries ``swa_fwd`` /
    ``swa_bwd`` (one backward call since ISSUE 53) and ``attn_gate`` under
    ``attn``,
    ``dense_mlp`` under the leading block's ``mlp``, the ``moe_*`` scopes
    under the others', in its compiled step's ``op_name``s, and leaves the
    gauge ``attention/window_tile_overcompute``."""
    import re
    from deepspeed_tpu.models.laguna import LagunaForCausalLM, laguna_tiny
    default_registry().reset()
    cfg = laguna_tiny(num_hidden_layers=5, experts_held=4, loss_chunk=16,
                      use_flash=True)
    engine, _, _, _ = dstpu.initialize(config=base_config(),
                                       model=LagunaForCausalLM(cfg))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 256, (8, 64)).astype(np.int32)}
    engine.train_batch(batch)
    gauges = engine.telemetry_flush()["gauges"]
    assert {"moe/dropped_rows", "moe/rows_held_share",
            "moe/held_slabs", "moe/combine_rows_walked"} <= set(gauges)
    # S 64, window 16, blocks of 64 in the interpreter: one block a band
    assert gauges["attention/window_tile_overcompute"] == pytest.approx(
        64 * 64 / (64 * 16 - 16 * 15 // 2))
    hlo = engine.lower_train_step(batch).compile().as_text()
    assert not re.search(r"swa_bwd_d(q|kv)", hlo)
    for scope in ("attn/shard_map/swa_fwd", "attn/shard_map/swa_bwd",
                  "attn/attn_gate",
                  "lead_0/mlp/dense_mlp", "mlp/moe_shared", "mlp/moe_router",
                  "moe_dispatch", "moe_gmm", "moe_combine", "ds_embed",
                  "ds_loss_head"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
