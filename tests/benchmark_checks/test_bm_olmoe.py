"""The OLMoE family's counts, its cell's parameters, its tolerance and its
four ``moe_*`` readers, on hand-worked numbers and a hand-made scope table.
No chip, no compile (the system against the reference: ``tests/test_olmoe.py``).
"""

import json
import os
import types

import pytest

from benchmark import harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import gpt2, olmoe
from benchmark.layer_metrics import (flash_attn_share, moe_dispatch_ms,
                                     moe_gmm_roofline, moe_gmm_share,
                                     moe_rows_max_over_mean)

CELL = "olmoe-train-1chip-s4096"
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs",
                       "olmoe-1b-7b-0125-depth1.json")) as f:
    CONFIG = json.load(f)


def test_flops_a_token_count_the_active_parameters():
    """6 x (attention 4 x 2048^2 + 8 experts x 3 x 2048 x 1024 + router
    2048 x 64 + head 50304 x 2048) + causal attention 6 x 4096 x 2048, by
    hand: ISSUE 27's 1,071 MFLOP — never the 64 experts' 2.4 G."""
    assert olmoe.active_matmul_params(CONFIG) == \
        16_777_216 + 50_331_648 + 131_072 + 103_022_592
    assert olmoe.train_flops_per_token(CONFIG, 4096) == 1_071_906_816
    # at the published depth the head is 7.8 % of it, here 58 %
    head = 6 * 50304 * 2048
    assert head / 1_071_906_816 == pytest.approx(0.577, abs=0.001)
    deep = dict(CONFIG, num_hidden_layers=16)
    assert head / olmoe.train_flops_per_token(deep, 4096) == \
        pytest.approx(0.078, abs=0.001)


def test_kernel_flops_a_step():
    # three products x three matrices x 2 x 131,072 rows x 2048 x 1024
    assert olmoe.moe_gmm_flops_per_step(CONFIG, 4 * 4096) == \
        9 * 2 * 131_072 * 2048 * 1024 == 4_947_802_324_992
    # six S x S x D matmuls a head, halved by the mask: 4 x 16 heads of 128
    assert olmoe.train_attention_flops_per_step(CONFIG, 4, 4096) == \
        6 * 4 * 16 * 4096 * 4096 * 128 == 824_633_720_832


def the_cell_is_the_one_issue_27_names(bench):
    """Held on ``bench`` by name (``test_bm_manifest_rules.py`` runs it over
    the manifest with a cell, a configuration and a metric appended)."""
    cell = manifest.cell_of(bench, CELL)
    traffic = manifest.traffic_of(cell)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        "olmoe-1b-7b-0125-depth1", 1, "pretrain-b4x4096")
    assert {k: traffic[k] for k in (
        "kind", "global_batch", "seq_len", "batch_pool", "token_below",
        "warmup_steps", "fence_lag_steps", "trace_steps")} == {
        "kind": "train_steps", "global_batch": 4, "seq_len": 4096,
        "batch_pool": 16, "token_below": 50304, "warmup_steps": 3,
        "fence_lag_steps": 2, "trace_steps": 3}
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert (CONFIG["num_hidden_layers"],
            CONFIG["published"]["num_hidden_layers"]) == (1, 16)
    for key in ("changed_why", "assumed", "deployment", "weights"):
        assert CONFIG[key], key
    assert {"intermediate_size", "router_aux_loss_coef",
            "router_z_loss_coef"} <= set(CONFIG["assumed"])
    names = {m["name"] for m in manifest.metrics_for(bench, cell, "per_layer")}
    assert {"moe_gmm_roofline", "moe_gmm_share", "moe_dispatch_ms",
            "moe_rows_max_over_mean", "flash_attn_share",
            "flash_attn_roofline", "flash_fwd_roofline", "flash_bwd_roofline",
            "train_mfu", "train_step_ms", "train_program_hbm_gb",
            "train_unscoped_share"} <= names
    assert not names & {"collective_exposed_share", "collectives_per_step"}


def test_the_cell_is_the_one_issue_27_names():
    the_cell_is_the_one_issue_27_names(BENCH)


def test_the_catalogs_numbers_are_the_files():
    """Every number of the catalog's ``config`` for this model, under the
    same key; only the depth differs, and it is listed."""
    catalog = {"hidden_size": 2048, "intermediate_size": 1024,
               "max_position_embeddings": 4096, "num_attention_heads": 16,
               "num_experts": 64, "num_experts_per_tok": 8,
               "num_hidden_layers": 16, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-05, "rope_theta": 10000,
               "vocab_size": 50304, "norm_topk_prob": False,
               "tie_word_embeddings": False, "attention_bias": False}
    differs = [k for k, v in catalog.items() if CONFIG[k] != v]
    assert differs == ["num_hidden_layers"] == CONFIG["reduced"]


# --------------------------------------------------------- the tolerance

LOSS, NORM = 10.93, 1.7       # of the order the chip shows
LEAVES = ("embed", "lm_head", "norm", "input_norm", "post_attn_norm", "q",
          "k", "v", "o", "q_norm", "k_norm", "router", "gate", "up", "down")
DIFFERENCES = {"routing_differs": 400, "routing_assignments": 131_072,
               "attn_out_rel": 0.004, "ffn_out_row_rel": 0.03,
               "system_grad_norm": NORM,
               "grad_leaf_rel": {name: 0.005 for name in LEAVES}}


def _passes(loss=LOSS, norm=NORM, **differences):
    leaves = dict(DIFFERENCES["grad_leaf_rel"],
                  **differences.pop("grad_leaf_rel", {}))
    checks, _ = olmoe.judge_train(
        CONFIG, loss, norm, LOSS, NORM,
        dict(DIFFERENCES, grad_leaf_rel=leaves, **differences))
    return all(checks.values())


def test_an_honest_step_passes_with_room():
    tol = CONFIG["train"]["tolerance"]
    # the chip's largest over 17 runs: 7.5e-4 and 0.046 %
    assert tol["loss_abs"] <= 3e-3 and tol["grad_norm_rel"] <= 0.005
    assert _passes()
    assert _passes(LOSS + 1.5 * 7.5e-4, NORM * 1.002)
    assert _passes(ffn_out_row_rel=0.5 * tol["ffn_out_row_rel"],
                   attn_out_rel=0.5 * tol["attn_out_rel"])
    # a limit for every gradient leaf of the model, and no other
    assert set(tol["grad_leaf_rel"]) == set(LEAVES)
    assert _passes(grad_leaf_rel={
        name: 0.5 * limit for name, limit in tol["grad_leaf_rel"].items()})


@pytest.mark.parametrize("fault,kw", [
    # 0.001 x mean(logsumexp^2) ~ 0.001 x 21 at 64 experts
    ("the z-loss left out", dict(loss=LOSS - 0.021)),
    ("the balance loss left out", dict(loss=LOSS - 0.08)),
    # top-8 of 64 near-uniform probabilities sum to ~0.3: rows 3 x too large
    ("top-k weights renormalised", dict(ffn_out_row_rel=2.3)),
    ("one token's expert output missing", dict(ffn_out_row_rel=1.0)),
    ("attention without QK-norm", dict(attn_out_rel=0.9)),
    ("a router that picks other experts", dict(routing_differs=13_000)),
    ("the gradient not averaged over the tokens", dict(norm=NORM * 16_380)),
    # the backward pass of the expert layer, which the norm cannot see: the
    # expert and router gradients are half a percent of its square
    ("no expert weight gradient (drhs zero)",
     dict(grad_leaf_rel={"gate": 1.0, "up": 1.0, "down": 1.0})),
    ("the grouped matmul's cotangent rounded to fp8",
     dict(grad_leaf_rel={"gate": 0.039, "up": 0.039, "down": 0.028})),
    ("a router that does not learn", dict(grad_leaf_rel={"router": 1.0})),
    ("a leaf the comparison never saw",
     dict(grad_leaf_rel={"q_norm": float("nan")})),
    ("the compared gradients are not the step's",
     dict(system_grad_norm=NORM * 1.01)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_wrong_step_fails(fault, kw):
    loss, norm = kw.pop("loss", LOSS), kw.pop("norm", NORM)
    assert not _passes(loss, norm, **kw), fault


# ------------------------------------------------------------ the readers

STEP = "jit(train_batch_fn)/ds_fwd_bwd"
FWD = STEP + "/jvp(LlamaForCausalLM)/layers/while/body/closed_call/blk"
BWD = STEP + "/transpose(jvp(LlamaForCausalLM))/layers/while/body/" \
    "closed_call/blk"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, op_name, ns): one step of 1000 ms on one chip
OPS = [
    ("%moe_gmm.1 = bf16[131072,1024] custom-call(%a)" + PALLAS,
     FWD + "/mlp/moe_gmm/pallas_call", 20e6),
    ("%moe_gmm_dlhs.2 = bf16[131072,2048] custom-call(%a)" + PALLAS,
     BWD + "/mlp/moe_gmm_dlhs/pallas_call", 30e6),
    ("%moe_gmm_drhs.3 = bf16[64,2048,1024] custom-call(%a)" + PALLAS,
     BWD + "/mlp/moe_gmm_drhs/pallas_call", 50e6),
    ("%flash_fwd_chunk.4 = bf16[64,4096,128] custom-call(%a)" + PALLAS,
     FWD + "/attn/flash_fwd_chunk/pallas_call", 10e6),
    ("%gather.5 = bf16[131072,2048] gather(%a)",
     FWD + "/mlp/moe_dispatch/gather", 7e6),
    ("%sort.6 = s32[131072] sort(%a)", FWD + "/mlp/moe_dispatch/sort", 2e6),
    ("%fusion.7 = f32[16384,64] fusion(%a)",
     FWD + "/mlp/moe_router/dot_general", 1e6),
    ("%gather.8 = bf16[131072,2048] gather(%a)",
     BWD + "/mlp/moe_combine/gather", 5e6),
    ("%fusion.9 = bf16[16384,2048] fusion(%a)",
     FWD + "/attn/qk_norm/q_norm/mul", 3e6),
    ("%fusion.10 = bf16[131072,1024] fusion(%a)",
     FWD + "/mlp/moe_act/mul", 4e6),
    ("%fusion.11 = bf16[16384,2048] fusion(%a)", FWD + "/mlp/add", 868e6),
]


def _record(family, extra=None):
    text = "HloModule jit_train_batch_fn\n\nENTRY %main (a: f32[8]) -> f32[8] {\n"
    events, t = [], 0.0
    for name, op_name, ns in OPS:
        text += f'  {name}, metadata={{op_name="{op_name}"}}\n'
        events.append(tr.Event(name, t, t + ns))
        t += ns
    text += "}\n"
    record = harness.Record(
        cell={"name": CELL, "chips": 1}, config=CONFIG, family=family,
        rehearse=False, peaks={"bf16_flops_per_s": 197e12},
        compiled_text=text)
    plane = "/device:TPU:0"
    record.trace = tr.Trace({plane: {
        "XLA Ops": events,
        "XLA Modules": [tr.Event("jit_train_batch_fn(1)", 0.0, t)]}}, {})
    record.slice = (0.0, t)
    record.extra.update(step_module="jit_train_batch_fn", global_batch=4,
                        seq_len=4096, tokens_per_step=16384, **(extra or {}))
    return record


def test_the_expert_layers_readers_on_a_hand_made_scope_table():
    record = _record(olmoe)
    chip = sr.busiest_chip(record)
    assert chip["kernel_ms"] == {"flash_fwd": pytest.approx(10.0),
                                 "flash_bwd": 0.0,
                                 "moe_gmm": pytest.approx(100.0)}
    # forward, dlhs and drhs share the tag by prefix: 100 of 1000 busy ms
    assert moe_gmm_share.read(record) == pytest.approx(10.0)
    # 4.948 TFLOP needed in 100 ms is 49.48 TFLOP/s of 197
    assert moe_gmm_roofline.read(record) == pytest.approx(
        100 * 4_947_802_324_992 / 197e12 / 0.100)
    assert moe_gmm_roofline.read(record) == pytest.approx(25.12, abs=0.01)
    # router 1 + dispatch 7 + 2 + combine 5, every phase; not the
    # activation, not QK-norm, not the matmuls
    assert moe_dispatch_ms.read(record) == pytest.approx(15.0)
    # the flash readers still see only their own kernels
    assert flash_attn_share.read(record) == pytest.approx(1.0)
    rows = {(p, t): ms for p, t, _, ms in chip["rows"]}
    assert rows[("forward", "qk_norm")] == pytest.approx(3.0)
    assert rows[("forward", "moe_act")] == pytest.approx(4.0)
    assert rows[("forward", "mlp")] == pytest.approx(868.0)


def test_a_program_without_the_expert_layer_reads_nothing():
    """The parent's program under this PR's benchmark files: GPT-2's family
    lists no ``moe_gmm`` scope, counts no such flops and has no gauges, so
    every new reader returns None and raises nothing."""
    record = _record(gpt2)
    for reader in (moe_gmm_roofline, moe_gmm_share, moe_dispatch_ms,
                   moe_rows_max_over_mean):
        assert reader.read(record) is None, reader.NAME
    untraced = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                              family=olmoe, rehearse=False, peaks=None)
    untraced.extra.update(tokens_per_step=16384)
    for reader in (moe_gmm_roofline, moe_gmm_share, moe_dispatch_ms):
        assert reader.read(untraced) is None, reader.NAME


def test_the_balance_gauge_is_read_through_the_family(monkeypatch):
    fake = types.SimpleNamespace(
        program_gauges=lambda: {"moe/rows_max_over_mean": 1.0625,
                                "moe/dropped_rows": 0.0})
    record = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                            family=fake, rehearse=False, peaks=None)
    assert moe_rows_max_over_mean.read(record) == 1.0625
    # before any engine was built there is no gauge to read
    monkeypatch.setattr(olmoe, "_LIVE", {})
    record.family = olmoe
    assert moe_rows_max_over_mean.read(record) is None
