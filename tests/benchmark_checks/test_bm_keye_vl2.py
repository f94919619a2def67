"""The Keye-VL-2.0 cell (ISSUE 65): the manifest's entries found by NAME, the
catalog's numbers, the parameter arithmetic, the pair counts against a brute
force, the family's counts of operations and bytes, the comparison that
decides ``correct`` on hand-made readings, the twelve new readers on a
hand-made scope table and on a program that lacks the scopes, and the cell's
rehearsal on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import keye_vl2, olmoe
from benchmark.layer_metrics import (
    bd_fwd_roofline, dsa_attn_share, dsa_bwd_roofline, dsa_fwd_roofline,
    dsa_indexer_bwd_roofline, dsa_indexer_ms, dsa_indexer_roofline,
    dsa_kl_ms, dsa_kl_roofline, dsa_select_ms, dsa_select_roofline,
    dsa_selected_share, dsa_tile_overcompute, flash_fwd_roofline,
    moe_gmm_roofline)

CELL = "keyevl2-train-1chip-s16384"
NAME = "keye-vl-2.0-30b-a3b-ep8-depth6"
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
TRAFFIC = manifest.traffic_of({"name": CELL})

S = 16384
ATTN = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
EXPERT = 3 * 2048 * 768
INDEXER = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16 + 2 * 64
SELECTED, CAUSAL = 31_458_304, 134_225_920
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW = [dsa_attn_share, dsa_indexer_ms, dsa_select_ms, dsa_kl_ms,
       dsa_fwd_roofline, dsa_bwd_roofline, dsa_tile_overcompute,
       dsa_selected_share, dsa_indexer_roofline, dsa_indexer_bwd_roofline,
       dsa_select_roofline, dsa_kl_roofline]


def test_the_cell_is_the_one_issue_65_names():
    """Entries by name: a later PR appends and this stays true."""
    cell = manifest.cell_of(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain-b1x16384", 1)
    assert len(cell["why"]) <= 200 and "top-2,048" in cell["why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"]
    assert sorted(entry["reduced"]) == sorted(REDUCED) \
        == sorted(CONFIG["reduced"])
    names = {m["name"] for m in manifest.metrics_for(BENCH, cell, "per_layer")}
    assert {r.NAME for r in NEW} | {
        "train_mfu", "train_step_ms", "train_program_hbm_gb",
        "train_peak_hbm_gb", "train_unscoped_share",
        "train_device_idle_share", "train_compiles_in_window",
        "loss_head_ms", "moe_gmm_roofline", "moe_gmm_share",
        "moe_dispatch_ms", "moe_rows_max_over_mean", "moe_rows_held_share",
        "moe_router_ms", "setup_engine_init_s", "setup_first_step_s",
        "setup_outside_program_s", "setup_compile_s",
        "setup_programs_compiled", "setup_cache_misses"} <= names
    # no kernel of this step runs under another family's scopes
    assert not [n for n in names if n.startswith((
        "flash_", "swa_", "bd_", "gdn_", "ssd_", "ssm_", "mla_", "mhc_"))]
    e2e = {m["name"] for m in manifest.metrics_for(BENCH, cell, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    for reader in NEW:      # each lists the cell it reads
        entry = next(m for m in BENCH["per_layer"] if m["name"] == reader.NAME)
        assert CELL in entry["workloads"] and entry["layer"] == reader.LAYER
    assert not manifest.problems(BENCH)
    assert TRAFFIC["seq_len"] == S and TRAFFIC["global_batch"] == 1
    assert (TRAFFIC["batch_pool"], TRAFFIC["warmup_steps"],
            TRAFFIC["fence_lag_steps"], TRAFFIC["trace_steps"]) == (16, 3, 2,
                                                                    3)
    assert "WHY 16,384" in TRAFFIC["why_in_full"]


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key, nested groups whole; depth, experts held and vocabulary differ, and
    are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert CONFIG["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    published = CONFIG["published"]
    for key in ("head_dim", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts_per_tok"):
        assert key in keye_vl2.WIDTH_KEYS
        assert CONFIG[key] == published[key] == row["config"][key]
    for key in REDUCED + ["sa_config", "rope_scaling"]:
        assert published[key] == row["config"][key], key
    assert CONFIG["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert CONFIG["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert CONFIG["num_experts"] * CONFIG["expert_parallel_size"] == 128
    assert CONFIG["vocab_size"] * 8 == 151936
    assert set(CONFIG["changed_why"]) == set(REDUCED)
    for reason in ("a_language_model_only", "b_qk_norm", "c_indexer_input",
                   "d_indexer_key_norm_and_scale", "e_indexer_rotary",
                   "f_chunk_sizes", "g_selection", "h_indexer_loss",
                   "k_init"):
        assert len(CONFIG["assumed"][reason]) > 60, reason
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert "vision tower" in CONFIG["deployment"]
    assert CONFIG["model"]["remat"] and CONFIG["rehearse_cpu"]
    assert "dsa_selection" in CONFIG["model"]["remat_why"]
    assert CONFIG["train"]["engine"]["scheduler"]["params"][
        "warmup_num_steps"] == 2000
    # the rehearsal prunes: its top-k is shorter than its sequence
    small = CONFIG["rehearse_cpu"]
    assert small["sa_config"]["topk"] < small["train_seq_len"]
    assert sum(small["rope_scaling"]["mrope_section"]) \
        == small["head_dim"] // 2


def test_the_parameter_arithmetic_is_the_initialised_trees():
    """``changed_why``'s numbers against ``jax.eval_shape`` of the model the
    configuration builds."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    model = keye_vl2._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == model.config.num_params() == 659_190_016
    blk = shapes["layers"]["blk"]
    attn = blk["attn"]
    assert sum(count(attn[k]) for k in ("q_proj", "k_proj", "v_proj",
                                        "o_proj")) == 6 * ATTN
    assert ATTN == 18_874_368 and INDEXER == 2_261_120
    assert sum(count(attn[k]) for k in ("index_q", "index_k", "index_w",
                                        "index_k_norm")) == 6 * INDEXER
    assert attn["index_q"]["kernel"].shape == (6, 2048, 1024)
    assert attn["index_k"]["kernel"].shape == (6, 2048, 64)
    assert attn["index_w"]["kernel"].shape == (6, 2048, 16)
    assert set(attn["index_k_norm"]) == {"scale", "bias"}
    assert blk["mlp"]["router"].shape == (6, 2048, 128)
    assert blk["mlp"]["gate_proj"].shape == (6, 16, 2048, 768)
    assert count(blk) == 6 * 96_899_456
    why = " ".join(CONFIG["changed_why"].values())
    for number in ("18,874,368", "262,144", "4,718,592", "75,497,472",
                   "2,261,120", "96,899,456", "77,791,232", "659,190,016",
                   "9.23 GB"):
        assert number in why, number
    assert 659_190_016 * 14 / 1e9 == pytest.approx(9.23, abs=0.005)
    whole = dataclasses.replace(model.config, n_layers=48, experts_held=0,
                                vocab_size=151936)
    assert whole.num_params() / 1e9 == pytest.approx(30.6, abs=0.1)
    # the model file's other configurations count as before
    with open(os.path.join(manifest.HERE, "configs",
                           "olmoe-1b-7b-0125-depth1.json")) as f:
        assert olmoe.model_config(json.load(f), False).num_params() \
            == 625_616_896


@pytest.mark.parametrize("seq,topk", [(16, 4), (24, 24), (32, 5), (7, 100),
                                      (64, 1)])
def test_the_pair_counts_are_the_brute_force_counts(seq, topk):
    assert keye_vl2.selected_pairs(seq, topk) == sum(
        min(t + 1, topk) for t in range(seq))
    assert keye_vl2.causal_pairs(seq) == sum(t + 1 for t in range(seq))
    import jax.numpy as jnp
    from benchmark.reference.keye_vl2 import select
    scores = jnp.asarray(np.random.default_rng(seq).normal(
        size=(1, seq, seq)), jnp.float32)
    kept = np.asarray(select(scores, topk))[0]
    assert int(kept.sum()) == keye_vl2.selected_pairs(seq, topk)
    assert not np.triu(kept, 1).any()


def test_flops_and_bytes_count_what_this_rank_needs():
    f = keye_vl2
    assert f.selected_pairs(S, 2048) == SELECTED
    assert f.causal_pairs(S) == CAUSAL
    assert SELECTED / CAUSAL == pytest.approx(0.2344, abs=0.0001)
    assert f.rows_held_share(CONFIG) == 0.125
    layer = ATTN + 2048 * (1024 + 64 + 16) + 2048 * 128 + 8 * 0.125 * EXPERT
    assert f.layer_matmul_params(CONFIG) == layer
    attention = f.train_attention_flops_per_step(CONFIG, 1, S)
    assert attention == 6 * 32 * 12 * SELECTED * 128
    # ISSUE 65's sizes, a layer forward: 0.52 TF over the selected pairs,
    # 0.27 TF of indexer, against 2.20 TF of dense causal attention
    assert attention / 6 / 3 / 1e12 == pytest.approx(0.52, abs=0.01)
    fwd, bwd = f.indexer_flops_per_step(CONFIG, 1, S)
    assert fwd == 6 * CAUSAL * 2 * 16 * 64 and bwd == 3 * fwd
    assert fwd / 6 / 1e12 == pytest.approx(0.27, abs=0.01)
    assert 32 * 4 * CAUSAL * 128 / 1e12 == pytest.approx(2.20, abs=0.01)
    assert f.kl_flops_per_step(CONFIG, 1, S) == 6 * 32 * 2 * SELECTED * 128
    assert f.select_bytes_per_step(CONFIG, 1, S) == 6 * CAUSAL * 5
    step = f.train_flops_per_token(CONFIG, S) * S
    assert step == pytest.approx(
        6 * S * (6 * layer + 18992 * 2048) + attention + fwd + bwd
        + f.kl_flops_per_step(CONFIG, 1, S))
    # anything larger — the causal tiles walked — would let a share read
    # over 100 %
    assert attention < 6 * 32 * 12 * CAUSAL * 128 / 4.2
    assert f.moe_gmm_flops_per_step(CONFIG, S) == 6 * 9 * 2 * (
        S * 8 / 8) * 2048 * 768


def test_the_placement_names_the_cells_pool():
    how = CONFIG["train"]["expert_placement"]
    assert {k: how[k] for k in ("batch_pool", "seq_len", "token_below")} \
        == {k: TRAFFIC[k] for k in ("batch_pool", "seq_len", "token_below")}
    assert how["rounds"] >= 2 and "place_by_load" in how["why"]


# --------------------------------------------------------- the tolerance

LOSS, NORM = 9.9, 1.4
TOL = CONFIG["train"]["tolerance"]
LEAVES = {"embed", "lm_head", "norm", "input_norm", "post_attn_norm", "q",
          "k", "v", "o", "q_norm", "k_norm", "router", "gate", "up",
          "down"} | set(keye_vl2.INDEXER_LEAVES)
ASSIGNED = 6 * S * 8
DIFFERENCES = {
    "routing_differs": int(0.3 * TOL["routing_differs_share"] * ASSIGNED),
    "routing_assignments": ASSIGNED,
    "selection_differs_share": 0.5 * TOL["selection_differs_share"],
    "selected_pairs": [6 * SELECTED, 6 * SELECTED],
    "index_scores_rel": 0.5 * TOL["index_scores_rel"],
    "attn_out_rel": 0.5 * TOL["attn_out_rel"],
    "ffn_out_rel": 0.5 * TOL["ffn_out_rel"],
    "dsa_kl_abs": 0.5 * TOL["dsa_kl_abs"], "ce_on_indexer": 0.0,
    "kl_on_trunk": 0.0, "system_grad_norm": NORM,
    "grad_leaf_rel": {n: 0.5 * TOL["grad_leaf_rel"][n] for n in LEAVES}}


def _passes(loss=LOSS, norm=NORM, **differences):
    leaves = dict(DIFFERENCES["grad_leaf_rel"],
                  **differences.pop("grad_leaf_rel", {}))
    checks, _ = keye_vl2.judge_train(
        CONFIG, loss, norm, LOSS, NORM,
        dict(DIFFERENCES, grad_leaf_rel=leaves, **differences))
    return all(checks.values())


def test_an_honest_step_passes_with_room(monkeypatch):
    monkeypatch.delitem(keye_vl2._LIVE, "engine", raising=False)
    assert _passes()
    assert set(TOL["grad_leaf_rel"]) == LEAVES
    assert set(keye_vl2.INDEXER_LEAVES) <= LEAVES
    assert TOL["why"] and len(TOL["why"]) > 500
    for key in ("loss_abs", "grad_norm_rel", "routing_differs_share",
                "selection_differs_share", "index_scores_rel", "dsa_kl_abs",
                "attn_out_rel", "ffn_out_rel", "grad_leaf_rel"):
        assert key in TOL["why"], f"no reason given for {key}"
    # the controls are recorded with readings that fail
    controls = dict(CONFIG["train"]["controls"])
    assert "my chip runs, PR 65" in controls.pop("source")
    assert set(controls) == set(keye_vl2.CONTROLS) | {"lower_precision"}
    for name, control in controls.items():
        assert control["fails"] and control["readings"], name


@pytest.mark.parametrize("fault,kw", [
    ("the KL left out of the loss", dict(loss=LOSS - 0.05)),
    ("a selection that is not the exact top-k",
     dict(selection_differs_share=4 * TOL["selection_differs_share"])),
    ("a top-(k - 1)", dict(selected_pairs=[6 * SELECTED,
                                           6 * SELECTED - 6 * (S - 2047)])),
    ("relu left out of the index score",
     dict(index_scores_rel=0.7, selection_differs_share=0.4)),
    ("a head's weight dropped", dict(index_scores_rel=0.25)),
    ("the KL over all causal keys", dict(dsa_kl_abs=0.5)),
    ("the pruned kernels wrong", dict(attn_out_rel=0.3)),
    ("the top-8 not renormalised", dict(ffn_out_rel=0.6)),
    ("a router that reads another tensor", dict(routing_differs=200_000)),
    ("the stop-gradient left out", dict(kl_on_trunk=1e-9)),
    ("the cross-entropy reaches the indexer", dict(ce_on_indexer=1e-12)),
    ("no indexer gradient",
     dict(grad_leaf_rel={"index_q": 1.0, "index_k": 1.0, "index_w": 1.0})),
    ("the pruned backward's dk wrong", dict(grad_leaf_rel={"k": 0.5})),
    ("a leaf the comparison never saw",
     dict(grad_leaf_rel={"index_k_bias": float("nan")})),
    ("the compared gradients are not the step's",
     dict(system_grad_norm=NORM * 1.05)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_wrong_step_fails(monkeypatch, fault, kw):
    monkeypatch.delitem(keye_vl2._LIVE, "engine", raising=False)
    loss, norm = kw.pop("loss", LOSS), kw.pop("norm", NORM)
    assert not _passes(loss, norm, **kw), fault


# ------------------------------------------------------------ the readers

STEP = "jit(train_batch_fn)/ds_fwd_bwd"
FWD = STEP + "/jvp(LlamaForCausalLM)"
BWD = STEP + "/transpose(jvp(LlamaForCausalLM))"
SCAN = "/layers/while/body/closed_call/checkpoint"
REMAT = SCAN + "/rematted_computation"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, op_name, ns): one step of 1,000 ms on one chip
OPS = [
    ("%idx.1 = f32[1,16384,16384] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/blk/attn/dsa_indexer/pallas_call", 30e6),
    ("%idx.2 = f32[1,16384,16384] custom-call(%a)" + PALLAS,
     BWD + REMAT + "/blk/attn/dsa_indexer/pallas_call", 30e6),
    ("%sel.3 = s8[1,16384,16384] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/blk/attn/dsa_select/pallas_call", 50e6),
    # packing the kept set: under dsa_select's tag, no Pallas call
    ("%fusion.4 = u8[2048,16384] fusion(%a)",
     FWD + SCAN + "/blk/attn/dsa_select_pin/reduce_sum", 4e6),
    ("%fwd.5 = f32[32,16384,128] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/blk/attn/dsa_fwd/pallas_call", 150e6),
    ("%bwd.6 = f32[32,80,512,128] custom-call(%a)" + PALLAS,
     BWD + SCAN + "/blk/attn/dsa_bwd/pallas_call", 350e6),
    ("%fusion.7 = bf16[32,16384,128] fusion(%a)",
     BWD + SCAN + "/blk/attn/dsa_bwd_dq_sum/add", 6e6),
    ("%kl.8 = f32[1,1,16384] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/blk/attn/dsa_kl/pallas_call", 60e6),
    ("%kl.9 = f32[1,1,16384] custom-call(%a)" + PALLAS,
     BWD + REMAT + "/blk/attn/dsa_kl/pallas_call", 60e6),
    ("%fusion.10 = bf16[1,16384,16384] fusion(%a)",
     BWD + SCAN + "/blk/attn/dsa_kl_bwd/mul", 8e6),
    ("%ibwd.11 = f32[1,16,16384,64] custom-call(%a)" + PALLAS,
     BWD + SCAN + "/blk/attn/dsa_indexer_bwd/pallas_call", 80e6),
    ("%fusion.12 = bf16[1,16384,64] fusion(%a)",
     BWD + SCAN + "/blk/attn/dsa_indexer_bwd_sum/reduce_sum", 2e6),
    ("%fusion.13 = bf16[1,16384,1024] fusion(%a)",
     FWD + SCAN + "/blk/attn/dsa_index_proj/dot_general", 5e6),
    ("%moe_gmm.14 = bf16[16384,768] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/blk/mlp/moe_gmm/pallas_call", 15e6),
    ("%fusion.15 = bf16[16384,2048] fusion(%a)", FWD + SCAN + "/blk/mlp/add",
     150e6),
]


def _record(family, config=CONFIG):
    text = "HloModule jit_train_batch_fn\n\nENTRY %main (a: f32[8]) -> f32[8] {\n"
    events, t = [], 0.0
    for name, op_name, ns in OPS:
        text += f'  {name}, metadata={{op_name="{op_name}"}}\n'
        events.append(tr.Event(name, t, t + ns))
        t += ns
    text += "}\n"
    record = harness.Record(
        cell={"name": CELL, "chips": 1}, config=config, family=family,
        rehearse=False, compiled_text=text,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    plane = "/device:TPU:0"
    record.trace = tr.Trace({plane: {
        "XLA Ops": events,
        "XLA Modules": [tr.Event("jit_train_batch_fn(1)", 0.0, t)]}}, {})
    record.slice = (0.0, t)
    record.extra.update(step_module="jit_train_batch_fn", global_batch=1,
                        seq_len=S, tokens_per_step=S)
    return record


def test_the_readers_on_a_hand_made_scope_table(monkeypatch):
    monkeypatch.setitem(keye_vl2._LIVE, "gauges", {
        "attention/dsa_tile_overcompute": 4.4,
        "attention/dsa_selected_share": 0.2344})
    record = _record(keye_vl2)
    assert sr.busiest_chip(record)["busy_ms"] == pytest.approx(1000.0)
    # every kind under the dsa_* tags; the indexer's projections are not
    assert dsa_attn_share.read(record) == pytest.approx(100 * 830 / 1000)
    assert dsa_indexer_ms.read(record) == pytest.approx(60.0)
    assert dsa_select_ms.read(record) == pytest.approx(54.0)
    assert dsa_kl_ms.read(record) == pytest.approx(60 + 60 + 8 + 80 + 2)
    f = keye_vl2
    flops = f.train_attention_flops_per_step(CONFIG, 1, S)
    assert dsa_fwd_roofline.read(record) == pytest.approx(
        100 * flops / 3 / 197e12 / 0.150)
    assert dsa_bwd_roofline.read(record) == pytest.approx(
        100 * 2 * flops / 3 / 197e12 / 0.350)
    assert dsa_fwd_roofline.read(record) < 100 / 4.4
    fwd, bwd = f.indexer_flops_per_step(CONFIG, 1, S)
    # the kernel ran in two passes (forward, recompute): twice a call's work
    assert dsa_indexer_roofline.read(record) == pytest.approx(
        100 * 2 * fwd / 197e12 / 0.060)
    assert dsa_indexer_bwd_roofline.read(record) == pytest.approx(
        100 * bwd / 197e12 / 0.080)
    assert dsa_kl_roofline.read(record) == pytest.approx(
        100 * 2 * f.kl_flops_per_step(CONFIG, 1, S) / 197e12 / 0.120)
    assert dsa_select_roofline.read(record) == pytest.approx(
        100 * f.select_bytes_per_step(CONFIG, 1, S) / 819e9 / 0.050)
    for reader in NEW:
        if reader.UNIT == "%" and "roofline" in reader.NAME:
            assert 0 < reader.read(record) < 100, reader.NAME
    assert dsa_tile_overcompute.read(record) == 4.4
    assert dsa_selected_share.read(record) == pytest.approx(23.44)
    assert moe_gmm_roofline.read(record) == pytest.approx(
        100 * f.moe_gmm_flops_per_step(CONFIG, S) / 197e12 / 0.015)
    # no kernel of this step runs under the flash or the bd scopes
    assert flash_fwd_roofline.read(record) is None
    assert bd_fwd_roofline.read(record) is None


def test_a_program_without_the_scopes_reads_nothing_and_does_not_raise(
        monkeypatch):
    """The parent's side of a traced run: another family's record has no
    ``dsa_*`` scope, tag or gauge."""
    monkeypatch.setitem(olmoe._LIVE, "gauges", {})
    with open(os.path.join(manifest.HERE, "configs",
                           "olmoe-1b-7b-0125-depth1.json")) as f:
        record = _record(olmoe, json.load(f))
    for reader in NEW:
        assert reader.read(record) is None, reader.NAME
    bare = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                          family=keye_vl2, rehearse=False, peaks=None)
    monkeypatch.setitem(keye_vl2._LIVE, "gauges", {})
    for reader in NEW:
        assert reader.read(bare) is None, reader.NAME


# ---------------------------------------------------------- the rehearsal

def test_the_cell_rehearses_on_the_cpu():
    """The whole control flow at the rehearsal's sizes, traced: the flow's
    own checks pass (the tolerances are the chip's, set for bf16 at the
    published widths: the float32 comparison is ``tests/test_keye_vl2.py``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--rehearse-cpu", "--trace", "1", "--seconds", "1", "--seed",
         "6500000007"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}
    names = set(line["rehearsal_metric_names"])
    # no device plane on the CPU: of the new readers the step's gauge reads
    assert {"dsa_selected_share", "moe_rows_held_share"} <= names
    checks = json.loads(next(
        ln for ln in out.stderr.splitlines()
        if ln.startswith("[benchmark] checks: ")).split(
            "checks: ", 1)[1].split("} {", 1)[0] + "}")
    for name in ("no_routed_row_dropped", "keys_were_pruned",
                 "indexer_takes_no_ce_gradient", "trunk_takes_no_kl_gradient",
                 "window_losses_finite", "no_compile_in_window",
                 "compared_gradients_are_the_steps"):
        assert checks[name], (name, checks)
