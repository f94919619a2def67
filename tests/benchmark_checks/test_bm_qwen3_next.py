"""The Qwen3-Next family's counts, its cell's parameters, its tolerance and
its four new readers, on hand-worked numbers and a hand-made scope table.
No chip, no compile (the system against the reference:
``tests/test_qwen3_next.py``).
"""

import json
import os
import types

import pytest

from benchmark import harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import gpt2, olmoe, qwen3_next
from benchmark.layer_metrics import (flash_attn_share, gdn_layer_ms,
                                     gdn_scan_roofline, gdn_scan_share,
                                     moe_dispatch_ms, moe_gmm_roofline,
                                     moe_rows_held_share)

CELL = "qwen3next-train-1chip-s8192"
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs",
                       "qwen3-next-80b-a3b-ep16-depth4.json")) as f:
    CONFIG = json.load(f)

# by hand, from the published shapes
GDN_MIXER = 2048 * 12288 + 2048 * 64 + 4096 * 2048      # matmul parameters
ATTN_MIXER = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
MOE_HERE = 2048 * 512 + 3 * 2048 * 512 + 2048 \
    + 10 / 16 * 3 * 2048 * 512                            # 10 x 32 / 512 rows
HEAD = 18992 * 2048


def test_flops_a_token_count_what_this_rank_multiplies():
    """Three DeltaNet mixers, ONE attention mixer, four MoE layers with the
    held share 10 x 32 / 512 of expert rows, the sliced head; + causal
    attention in the one attention layer + the recurrence's 6 x 128 x 128 a
    value head (x 3 with backward) in the three DeltaNet layers."""
    assert qwen3_next.active_matmul_params(CONFIG) == \
        3 * GDN_MIXER + ATTN_MIXER + 4 * MOE_HERE + HEAD
    scan = 3 * 6 * 128 * 128 * 32
    assert qwen3_next.train_flops_per_token(CONFIG, 8192) == \
        6 * (3 * GDN_MIXER + ATTN_MIXER + 4 * MOE_HERE + HEAD) \
        + 6 * 8192 * 16 * 256 + 3 * scan
    # ISSUE 31's shares of the forward flops: DeltaNet 46 %, attention
    # 26 %, MoE 11 %, head 17 %
    total = qwen3_next.train_flops_per_token(CONFIG, 8192)
    assert 6 * HEAD / total == pytest.approx(0.169, abs=0.002)
    assert 3 * (6 * GDN_MIXER + scan) / total == pytest.approx(0.46, abs=0.01)
    assert 6 * 4 * MOE_HERE / total == pytest.approx(0.107, abs=0.002)
    # ~22.6 TFLOP a step of 16,384 tokens
    assert total * 16384 == pytest.approx(22.62e12, rel=0.005)


def test_kernel_flops_and_bytes_a_step():
    # six S x S x D matmuls a head, halved: 2 x 16 heads of 256, ONE layer
    assert qwen3_next.train_attention_flops_per_step(CONFIG, 2, 8192) == \
        6 * 2 * 16 * 8192 * 8192 * 256 == 3_298_534_883_328
    # rows held: 16,384 x 10 / 16 = 10,240; nine products, four layers
    assert qwen3_next.moe_gmm_flops_per_step(CONFIG, 16384) == \
        4 * 9 * 2 * 10_240 * 2048 * 512 == 773_094_113_280
    flops, nbytes = qwen3_next.gdn_scan_flops_and_bytes(CONFIG, 16384)
    assert flops == 3 * 16384 * 3 * 6 * 128 * 128 * 32 == 463_856_467_968
    # a token a layer: q, k 2 x 2048 + v 4096 bf16 and g, beta 2 x 32 f32
    # = 16,640 B read forward, again by backward, again as cotangents;
    # o 4096 bf16 = 8,192 B written, and read as a cotangent
    assert nbytes == 3 * 16384 * (3 * 16_640 + 2 * 8_192) == 3_258_974_208
    # the bytes bind on a v5e: 3.98 ms against 2.35 ms of flops
    assert nbytes / 819e9 > flops / 197e12


def the_cell_is_the_one_issue_31_names(bench):
    """Held on ``bench`` by name (``test_bm_manifest_rules.py`` runs it over
    the manifest with a cell, a configuration and a metric appended)."""
    cell = manifest.cell_of(bench, CELL)
    traffic = manifest.traffic_of(cell)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        "qwen3-next-80b-a3b-ep16-depth4", 1, "pretrain-b2x8192")
    assert {k: traffic[k] for k in (
        "kind", "global_batch", "seq_len", "batch_pool", "token_below",
        "warmup_steps", "fence_lag_steps", "trace_steps")} == {
        "kind": "train_steps", "global_batch": 2, "seq_len": 8192,
        "batch_pool": 16, "token_below": 18992, "warmup_steps": 3,
        "fence_lag_steps": 2, "trace_steps": 3}
    for words in ("10,240 of 163,840", "320 an expert", "5,120", "16x",
                  "1 chip"):
        assert words in cell["why"], words
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"] == CONFIG["reduced"]
    assert [(CONFIG[k], CONFIG["published"][k]) for k in entry["reduced"]] \
        == [(4, 48), (32, 512), (18992, 151936)]
    # the router is as wide as published, and 1/8 of the vocabulary is held
    assert CONFIG["num_experts"] * CONFIG["expert_parallel_size"] == 512
    assert CONFIG["vocab_size"] * 8 == 151936
    for key in ("changed_why", "assumed", "deployment", "weights"):
        assert CONFIG[key], key
    assert set(CONFIG["changed_why"]) == set(entry["reduced"])
    assert {"router_aux_loss_coef", "mtp", "init",
            "fused_projection_layout"} <= set(CONFIG["assumed"])
    names = {m["name"] for m in manifest.metrics_for(bench, cell, "per_layer")}
    assert {"gdn_scan_share", "gdn_scan_roofline", "gdn_layer_ms",
            "moe_rows_held_share", "moe_gmm_roofline", "moe_gmm_share",
            "moe_dispatch_ms", "moe_rows_max_over_mean", "flash_attn_share",
            "flash_attn_roofline", "flash_fwd_roofline", "flash_bwd_roofline",
            "train_mfu", "train_step_ms", "train_program_hbm_gb",
            "train_unscoped_share"} <= names
    assert not names & {"collective_exposed_share", "collectives_per_step",
                        "swa_attn_share", "swa_fwd_roofline",
                        "swa_bwd_roofline", "swa_tile_overcompute"}
    # the four it brought list this cell (a later expert-parallel cell may
    # list itself under ``moe_rows_held_share``: the Laguna cell does)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("gdn_scan_share", "gdn_scan_roofline", "gdn_layer_ms",
                 "moe_rows_held_share"):
        assert CELL in by_name[name]["workloads"], name


def test_the_cell_is_the_one_issue_31_names():
    the_cell_is_the_one_issue_31_names(BENCH)


def test_the_catalogs_numbers_are_the_files():
    """Every number of the catalog's ``config`` for this model, under the
    same key; depth, experts held and vocabulary differ, and are listed."""
    catalog = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts": 512,
        "num_experts_per_tok": 10, "num_hidden_layers": 48,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "vocab_size": 151936,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "mlp_only_layers": [], "rope_scaling": None,
        "model_type": "qwen3_next", "hidden_act": "silu"}
    differs = sorted(k for k, v in catalog.items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"])
    # every head and expert width is a width of the family
    for key in ("head_dim", "linear_key_head_dim", "linear_value_head_dim",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "linear_num_key_heads", "linear_num_value_heads",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok"):
        assert key in qwen3_next.WIDTH_KEYS or key.endswith("_dim")
        assert CONFIG[key] == CONFIG["published"][key]


def test_the_layer_kinds_come_from_the_two_keys():
    from deepspeed_tpu.models.qwen3_next import qwen3_next_80b_a3b
    kinds = qwen3_next_80b_a3b().layer_kinds
    assert len(kinds) == 48 and kinds.count("attention") == 12
    assert kinds[:4] == ("linear", "linear", "linear", "attention")
    assert list(kinds) == qwen3_next.layer_kinds(48, 4)
    cut = qwen3_next.model_config(CONFIG, rehearse=False)
    assert (cut.n_periods, cut.num_experts, cut.experts_held,
            cut.expert_share) == (1, 512, 32, 0)
    assert cut.num_params() == 625_667_136


# --------------------------------------------------------- the tolerance

LOSS, NORM = 9.88, 1.5        # of the order the chip shows
LEAVES = set(qwen3_next.LAYER_LEAVES["linear"]) \
    | set(qwen3_next.LAYER_LEAVES["attention"]) | {"embed", "lm_head", "norm"}
# an honest run's readings on the chip (PERF.md Findings PR 31)
DIFFERENCES = {"routing_differs": 4_000, "routing_assignments": 655_360,
               "gdn_out_rel": 0.0098, "attn_out_rel": 0.0063,
               "ffn_out_rel": 0.005, "system_grad_norm": NORM,
               "own_stream_by_layer": [["linear", 0.0093, 0.0216, 0.0119],
                                       ["linear", 0.0458, 0.0571, 0.0333],
                                       ["linear", 0.0904, 0.0795, 0.0469],
                                       ["attention", 0.0787, 0.0947, 0.0557]],
               "stream_add_rel": 0.002,
               "grad_leaf_rel": dict({name: 0.017 for name in LEAVES},
                                     lm_head=0.006, norm=0.002,
                                     A_log=0.034, dt_bias=0.031)}


def _passes(loss=LOSS, norm=NORM, **differences):
    leaves = dict(DIFFERENCES["grad_leaf_rel"],
                  **differences.pop("grad_leaf_rel", {}))
    checks, _ = qwen3_next.judge_train(
        CONFIG, loss, norm, LOSS, NORM,
        dict(DIFFERENCES, grad_leaf_rel=leaves, **differences))
    return all(checks.values())


def test_an_honest_step_passes_with_room():
    tol = CONFIG["train"]["tolerance"]
    assert tol["loss_abs"] <= 3e-3 and tol["grad_norm_rel"] <= 0.005
    assert _passes()
    # a limit for every gradient leaf of the model, and no other
    assert set(tol["grad_leaf_rel"]) == LEAVES
    assert _passes(
        gdn_out_rel=0.5 * tol["gdn_out_rel"],
        attn_out_rel=0.5 * tol["attn_out_rel"],
        ffn_out_rel=0.5 * tol["ffn_out_rel"],
        grad_leaf_rel={name: 0.5 * limit
                       for name, limit in tol["grad_leaf_rel"].items()})
    assert tol["why"] and len(tol["why"]) > 500


@pytest.mark.parametrize("fault,kw", [
    # 0.001 x 4 layers x E sum f P ~ 0.001 x 4 x 10
    ("the balance loss left out", dict(loss=LOSS - 0.04)),
    ("no decay gate / beta left out", dict(gdn_out_rel=0.5)),
    ("RoPE over the whole head / no output gate", dict(attn_out_rel=0.5)),
    ("the shared expert ungated / top-10 not renormalised",
     dict(ffn_out_rel=0.9)),
    ("a router that picks other experts", dict(routing_differs=65_000)),
    ("no expert weight gradient",
     dict(grad_leaf_rel={"gate": 1.0, "up": 1.0, "down": 1.0})),
    # the reference with fp8 weight matrices, judged against itself
    ("weights rounded to fp8: the DeltaNet branch", dict(gdn_out_rel=0.092)),
    ("weights rounded to fp8: the attention branch", dict(attn_out_rel=0.049)),
    ("weights rounded to fp8: the leaves",
     dict(grad_leaf_rel={"in_qkvz": 0.10, "conv": 0.10, "gate": 0.059,
                         "embed": 0.046, "norm": 0.022})),
    # not pinned: the first layer of the own passes, the residual adds
    ("a first layer that is wrong where a pinned pass cannot see",
     dict(own_stream_by_layer=[["linear", 0.0093, 0.2, 0.0119]])),
    ("a first layer's routing on its own stream",
     dict(own_stream_by_layer=[["linear", 0.0093, 0.0216, 0.06]])),
    ("a residual add that loses a tenth of a branch",
     dict(stream_add_rel=0.07)),
    # before the router's choice was kept across remat
    ("a recomputed forward pass that routes otherwise",
     dict(grad_leaf_rel={"gate": 0.065, "up": 0.064, "router": 0.07})),
    ("a leaf the comparison never saw",
     dict(grad_leaf_rel={"A_log": float("nan")})),
    ("the compared gradients are not the step's",
     dict(system_grad_norm=NORM * 1.01)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_wrong_step_fails(fault, kw):
    loss, norm = kw.pop("loss", LOSS), kw.pop("norm", NORM)
    assert not _passes(loss, norm, **kw), fault


# ------------------------------------------------------------ the readers

STEP = "jit(train_batch_fn)/ds_fwd_bwd"
FWD = STEP + "/jvp(Qwen3NextForCausalLM)/layers/while/body/closed_call"
BWD = STEP + "/transpose(jvp(Qwen3NextForCausalLM))/layers/while/body/" \
    "closed_call"
REMAT = BWD + "/l0/checkpoint/rematted_computation"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, op_name, ns): one step of 1000 ms on one chip
OPS = [
    ("%fusion.1 = bf16[128,2,32,64,128] fusion(%a)",
     FWD + "/l0/linear_attn/gdn_scan_prep/dot_general", 30e6),
    ("%fusion.2 = f32[2,32,128,128] fusion(%a)",
     FWD + "/l1/linear_attn/gdn_scan/while/body/dot_general", 20e6),
    ("%fusion.3 = f32[2,32,128,128] fusion(%a)",
     BWD + "/l1/linear_attn/gdn_scan/while/body/transpose", 40e6),
    ("%fusion.4 = f32[2,32,128,128] fusion(%a)",
     REMAT + "/linear_attn/gdn_scan/while/body/dot_general", 10e6),
    # a later kernel under the same scope keeps the tag
    ("%gdn_scan_fwd.5 = bf16[2,32,8192,128] custom-call(%a)" + PALLAS,
     FWD + "/l2/linear_attn/gdn_scan_fwd/pallas_call", 5e6),
    ("%fusion.6 = bf16[2,8192,8192] fusion(%a)",
     FWD + "/l0/linear_attn/gdn_conv/mul", 6e6),
    ("%fusion.7 = f32[2,8192,32] fusion(%a)",
     FWD + "/l0/linear_attn/gdn_gates/exp", 3e6),
    ("%fusion.8 = bf16[2,8192,4096] fusion(%a)",
     BWD + "/l0/linear_attn/gdn_out_norm/mul", 4e6),
    ("%fusion.9 = bf16[2,8192,12288] fusion(%a)",
     FWD + "/l0/linear_attn/in_proj_qkvz/dot_general", 50e6),
    ("%flash_fwd_chunk.10 = f32[32,8192,256] custom-call(%a)" + PALLAS,
     FWD + "/l3/attn/flash_fwd_chunk/pallas_call", 25e6),
    ("%fusion.11 = bf16[2,8192,16,256] fusion(%a)",
     FWD + "/l3/attn/attn_gate/mul", 2e6),
    ("%moe_gmm.12 = bf16[20480,512] custom-call(%a)" + PALLAS,
     FWD + "/l3/mlp/moe_gmm/pallas_call", 8e6),
    ("%fusion.13 = bf16[2,8192,2048] fusion(%a)",
     FWD + "/l3/mlp/moe_shared/dot_general", 9e6),
    ("%sort.14 = s32[163840] sort(%a)", FWD + "/l3/mlp/moe_dispatch/sort",
     7e6),
    ("%fusion.15 = bf16[16384,2048] fusion(%a)", FWD + "/l3/mlp/add", 781e6),
]


def _record(family, extra=None, peaks=None):
    text = "HloModule jit_train_batch_fn\n\nENTRY %main (a: f32[8]) -> f32[8] {\n"
    events, t = [], 0.0
    for name, op_name, ns in OPS:
        text += f'  {name}, metadata={{op_name="{op_name}"}}\n'
        events.append(tr.Event(name, t, t + ns))
        t += ns
    text += "}\n"
    record = harness.Record(
        cell={"name": CELL, "chips": 1}, config=CONFIG, family=family,
        rehearse=False, compiled_text=text,
        peaks=peaks or {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    plane = "/device:TPU:0"
    record.trace = tr.Trace({plane: {
        "XLA Ops": events,
        "XLA Modules": [tr.Event("jit_train_batch_fn(1)", 0.0, t)]}}, {})
    record.slice = (0.0, t)
    record.extra.update(step_module="jit_train_batch_fn", global_batch=2,
                        seq_len=8192, tokens_per_step=16384, **(extra or {}))
    return record


def test_the_new_readers_on_a_hand_made_scope_table():
    record = _record(qwen3_next)
    chip = sr.busiest_chip(record)
    assert chip["busy_ms"] == pytest.approx(1000.0)
    # prep 30 + loop 20 + 40 + 10 (recompute) + the kernel 5, by prefix
    assert gdn_scan_share.read(record) == pytest.approx(10.5)
    # the bytes bind: 3,258,974,208 B / 819 GB/s = 3.979 ms of 105 ms
    assert gdn_scan_roofline.read(record) == pytest.approx(
        100 * 3_258_974_208 / 819e9 / 0.105)
    assert gdn_scan_roofline.read(record) == pytest.approx(3.79, abs=0.01)
    # everything under linear_attn: scan 105 + conv 6 + gates 3 + norm 4 +
    # the projection 50
    assert gdn_layer_ms.read(record) == pytest.approx(168.0)
    # the other families' readers see their own scopes in this program
    assert flash_attn_share.read(record) == pytest.approx(2.5)
    assert moe_dispatch_ms.read(record) == pytest.approx(7.0)
    # 773 GFLOP expected in 8 ms
    assert moe_gmm_roofline.read(record) == pytest.approx(
        100 * 773_094_113_280 / 197e12 / 0.008)
    rows = {(p, t): ms for p, t, _, ms in chip["rows"]}
    assert rows[("forward", "attn_gate")] == pytest.approx(2.0)
    assert rows[("forward", "moe_shared")] == pytest.approx(9.0)
    assert rows[("recompute", "gdn_scan")] == pytest.approx(10.0)
    # where flops would bind (a chip with 10 x the bandwidth) they are taken
    fast = _record(qwen3_next, peaks={"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 8190e9})
    assert gdn_scan_roofline.read(fast) == pytest.approx(
        100 * 463_856_467_968 / 197e12 / 0.105)


@pytest.mark.parametrize("family", [gpt2, olmoe], ids=["gpt2", "olmoe"])
def test_a_program_without_the_layer_reads_nothing(family):
    """The parent's programs under this PR's benchmark files: no family
    there lists a ``gdn_scan`` scope, counts such work or sets the gauge, so
    every new reader returns None and raises nothing."""
    record = _record(family)
    for reader in (gdn_scan_share, gdn_scan_roofline, gdn_layer_ms,
                   moe_rows_held_share):
        assert reader.read(record) is None, reader.NAME
    untraced = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                              family=qwen3_next, rehearse=False, peaks=None)
    untraced.extra.update(tokens_per_step=16384)
    for reader in (gdn_scan_share, gdn_scan_roofline, gdn_layer_ms):
        assert reader.read(untraced) is None, reader.NAME


def test_the_held_share_is_read_through_the_family(monkeypatch):
    fake = types.SimpleNamespace(
        program_gauges=lambda: {"moe/rows_held_share": 0.0625,
                                "moe/rows_max_over_mean": 1.2})
    record = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                            family=fake, rehearse=False, peaks=None)
    assert moe_rows_held_share.read(record) == 6.25
    assert qwen3_next.rows_held_share(CONFIG) == 0.0625
    # before any engine was built there is no gauge to read
    monkeypatch.setattr(qwen3_next, "_LIVE", {})
    record.family = qwen3_next
    assert moe_rows_held_share.read(record) is None
    # the slot is this family's own: what a run of it folded is not handed
    # to OLMoE's readers in the same process
    monkeypatch.setattr(qwen3_next, "_LIVE",
                        {"gauges": {"moe/rows_held_share": 0.255}})
    assert moe_rows_held_share.read(record) == 25.5
    record.family = olmoe
    assert moe_rows_held_share.read(record) is None


def test_the_precision_control_rounds_the_matrices_and_nothing_else():
    """``benchmark/tools/precision_control.fp8_matrices``: a matrix lands on
    an e4m3 grid under its own scale (a few per cent off, at most 2 x 120
    distinct values), a vector stays as it is, and a leaf under the layer
    scan's subtree is judged without its stacking axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.tools.precision_control import fp8_matrices
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"embed": jax.random.normal(keys[0], (64, 32)),
              "norm": {"scale": jax.random.normal(keys[1], (32,))},
              "layers": {"l0": {
                  "kernel": 0.02 * jax.random.normal(keys[2], (2, 32, 48)),
                  "scale": jax.random.normal(keys[3], (2, 32))}}}
    out = fp8_matrices(params)
    np.testing.assert_array_equal(out["norm"]["scale"],
                                  params["norm"]["scale"])
    np.testing.assert_array_equal(out["layers"]["l0"]["scale"],
                                  params["layers"]["l0"]["scale"])
    for got, want in ((out["embed"], params["embed"]),
                      (out["layers"]["l0"]["kernel"],
                       params["layers"]["l0"]["kernel"])):
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert 0.01 < rel < 0.06, rel
        assert len(np.unique(np.asarray(got))) <= 240
        assert float(jnp.max(jnp.abs(got))) == pytest.approx(
            float(jnp.max(jnp.abs(want))), rel=1e-6)
