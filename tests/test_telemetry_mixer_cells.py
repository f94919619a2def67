"""The recurrent-mixer cells' scope names and gauges reach the compiled step
(ISSUEs 31, 40): a Qwen3-Next and a Nemotron-H model through
``dstpu.initialize``. Files beside ``tests/test_telemetry.py``
(``tests/test_telemetry_attention_cells.py`` is the other) because each case
builds and compiles a whole routed step, a minute or so."""

import numpy as np

import deepspeed_tpu as dstpu
from deepspeed_tpu.telemetry import default_registry
from tests.simple_model import base_config


def test_qwen3_next_scope_names_and_gauges_reach_the_step():
    """ISSUE 31's names: a Qwen3-Next model carries the DeltaNet scopes
    (``gdn_conv`` / ``gdn_gates`` / ``gdn_scan*`` / ``gdn_out_norm`` under the
    module ``linear_attn``), ``attn_gate`` and ``qk_norm`` under ``attn``,
    ``moe_shared`` beside the ``moe_*`` scopes under ``mlp`` in its compiled
    step's ``op_name``s, and a layer that holds a share of its experts sows
    ``moe/rows_held_share``, ``moe/held_slabs`` and ``moe/combine_rows_walked``
    beside the four gauges every dropless layer has."""
    import re
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                                 qwen3_next_tiny)
    default_registry().reset()
    cfg = qwen3_next_tiny(num_hidden_layers=4, experts_held=4, loss_chunk=16)
    engine, _, _, _ = dstpu.initialize(config=base_config(),
                                       model=Qwen3NextForCausalLM(cfg))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 256, (8, 32)).astype(np.int32)}
    engine.train_batch(batch)
    gauges = engine.telemetry_flush()["gauges"]
    assert {"moe/aux_loss", "moe/z_loss", "moe/rows_max_over_mean",
            "moe/dropped_rows", "moe/rows_held_share",
            "moe/held_slabs", "moe/combine_rows_walked"} <= set(gauges)
    assert gauges["moe/dropped_rows"] == 0
    assert 0 < gauges["moe/rows_held_share"] < 1
    assert gauges["moe/held_slabs"] >= 1
    # the delta rule's kernels took the call (the interpreter, off the TPU)
    assert gauges["linear_attn/gdn_kernel_heads_per_step"] > 0
    assert gauges["linear_attn/gdn_states_kept_every"] == 1
    hlo = engine.lower_train_step(batch).compile().as_text()
    for scope in ("linear_attn/gdn_conv", "linear_attn/gdn_gates",
                  # per device inside a shard_map on this mesh of eight
                  "linear_attn/shard_map/gdn_scan_prep",
                  "linear_attn/shard_map/gdn_scan_fwd",
                  "linear_attn/shard_map/gdn_scan_bwd",
                  "linear_attn/gdn_out_norm", "attn/qk_norm",
                  "attn/attn_gate", "mlp/moe_shared", "mlp/moe_router",
                  "moe_dispatch", "moe_gmm", "moe_gmm_dlhs",
                  "moe_gmm_drhs", "moe_combine", "ds_embed", "ds_loss_head"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope


def test_nemotron_h_scope_names_and_gauges_reach_the_step():
    """ISSUE 40's names: a Nemotron-H model carries the Mamba-2 scopes
    (``ssm_conv`` / ``ssm_gates`` / ``ssd_scan*`` / ``ssm_norm`` under the
    module ``mamba``) and the expert scopes under ``mixer`` in its compiled
    step's ``op_name``s; the scan's kernels took the call; and a layer
    without an auxiliary loss sows neither ``moe/aux_loss`` nor
    ``moe/z_loss``."""
    import re
    from deepspeed_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                                 nemotron_h_tiny)
    default_registry().reset()
    cfg = nemotron_h_tiny(hybrid_override_pattern="ME*", experts_held=4,
                          loss_chunk=16)
    engine, _, _, _ = dstpu.initialize(config=base_config(),
                                       model=NemotronHForCausalLM(cfg))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 256, (8, 32)).astype(np.int32)}
    engine.train_batch(batch)
    gauges = engine.telemetry_flush()["gauges"]
    assert {"moe/rows_max_over_mean", "moe/dropped_rows",
            "moe/rows_held_share", "moe/held_slabs",
            "moe/combine_rows_walked"} <= set(gauges)
    assert not {"moe/aux_loss", "moe/z_loss"} & set(gauges)
    assert gauges["moe/dropped_rows"] == 0
    # the scan's kernels took the call (the interpreter, off the TPU): a
    # group's two heads a grid step
    assert gauges["ssm/ssd_kernel_heads_per_step"] == 2
    hlo = engine.lower_train_step(batch).compile().as_text()
    for scope in ("mamba/ssm_conv", "mamba/ssm_gates",
                  # per device inside a shard_map on this mesh of eight
                  "mamba/shard_map/ssd_scan_prep",
                  "mamba/shard_map/ssd_scan_fwd",
                  "mamba/shard_map/ssd_scan_bwd", "mamba/ssm_norm",
                  "mixer/moe_shared", "mixer/moe_router", "moe_dispatch",
                  "moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs", "moe_combine",
                  "mixer/q_proj", "ds_embed", "ds_loss_head"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
