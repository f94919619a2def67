"""The Laguna family's counts, its cell's parameters, its tolerance and its
four new readers, on hand-worked numbers and a hand-made scope table; a CPU
rehearsal of the cell and of its fp8 control. No chip, no TPU compile (the
system against the reference: ``tests/test_laguna.py``).
"""

import json
import os
import types

import pytest

from benchmark import harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import gpt2, laguna, olmoe, qwen3_next
from benchmark.layer_metrics import (flash_attn_share, flash_bwd_roofline,
                                     flash_fwd_roofline, moe_dispatch_ms,
                                     moe_gmm_roofline, moe_rows_held_share,
                                     swa_attn_share, swa_bwd_roofline,
                                     swa_fwd_roofline, swa_tile_overcompute)

CELL = "laguna-train-1chip-s16384"
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs",
                       "laguna-xs2-33b-a3b-ep8-depth5.json")) as f:
    CONFIG = json.load(f)

# by hand, from the published shapes: matmul parameters
FULL_MIXER = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48        # 48 heads
SWA_MIXER = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64         # 64 heads
DENSE_MLP = 3 * 2048 * 8192
MOE_HERE = 2048 * 256 + 3 * 2048 * 512 + 2048 \
    + 8 / 8 * 3 * 2048 * 512                              # 8 x 32 / 256 rows
HEAD = 12544 * 2048
S = 16384
BAND = S * 512 - 512 * 511 // 2                           # scores a head


def test_flops_a_token_count_what_this_rank_multiplies():
    """Two full mixers (48 heads), three sliding ones (64), one dense MLP,
    four MoE layers with the held share 8 x 32 / 256 of expert rows, the
    sliced head; + causal attention in the two full layers + the band in
    the three sliding ones."""
    active = 2 * FULL_MIXER + 3 * SWA_MIXER + DENSE_MLP + 4 * MOE_HERE + HEAD
    assert laguna.active_matmul_params(CONFIG) == active == 275_849_216
    assert laguna.train_flops_per_token(CONFIG, S) == pytest.approx(
        6 * active + 6 * 2 * S * 48 * 128
        + 6 * 2 * 3 * 64 * BAND * 128 / S)
    # ~49.3 TFLOP a step of 16,384 tokens: matmuls 27.1, causal 19.8, band 2.4
    total = laguna.train_flops_per_token(CONFIG, S) * S
    assert total == pytest.approx(49.34e12, rel=0.002)
    assert 6 * active * S == pytest.approx(27.12e12, rel=0.002)


def test_kernel_flops_a_step():
    # six S x S x D matmuls a head, halved: 48 heads of 128, TWO full layers
    assert laguna.train_attention_flops_per_step(CONFIG, 1, S) == \
        2 * 6 * 48 * S * S * 128 == 19_791_209_299_968
    # the band: S*W - W(W-1)/2 scores a head, 3 x 64 heads, 2 x 128 a
    # product; QK^T and PV forward, dV, dP, dQ, dK backward
    assert BAND == 8_257_792
    fwd, bwd = laguna.swa_flops_per_step(CONFIG, 1, S)
    assert fwd == 2 * 2 * 192 * BAND * 128 == 811_773_984_768
    assert bwd == 2 * fwd
    # 16 x less than the same layers under full causal attention
    assert 3 * 6 * 64 * S * S * 128 / (fwd + bwd) == pytest.approx(16.25,
                                                                   abs=0.01)
    # a window that covers the sequence is the causal count
    assert laguna._band(256, 512) == 256 * 257 // 2
    # rows held: 16,384 x 8 / 8 = 16,384; nine products, four sparse layers
    assert laguna.moe_gmm_flops_per_step(CONFIG, S) == \
        4 * 9 * 2 * 16_384 * 2048 * 512 == 1_236_950_581_248


def the_cell_is_the_one_issue_33_names(bench):
    """Held on ``bench`` by NAME, never by a position or a count: what a
    later PR appends leaves it true (``test_bm_manifest_rules.py`` runs it
    over the manifest with a cell, a configuration and a metric appended)."""
    cell = manifest.cell_of(bench, CELL)       # raises where it is not IN
    traffic = manifest.traffic_of(cell)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        "laguna-xs2-33b-a3b-ep8-depth5", 1, "pretrain-b1x16384")
    assert {k: traffic[k] for k in (
        "kind", "global_batch", "seq_len", "batch_pool", "token_below",
        "warmup_steps", "fence_lag_steps", "trace_steps")} == {
        "kind": "train_steps", "global_batch": 1, "seq_len": 16384,
        "batch_pool": 16, "token_below": 12544, "warmup_steps": 3,
        "fence_lag_steps": 2, "trace_steps": 3}
    for words in ("512", "1 of 8 EP ranks", "4,096", "1 chip"):
        assert words in cell["why"], words
    assert traffic["users"] and len(traffic["why_in_full"]) > 500
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"] \
        == CONFIG["reduced"]
    published = CONFIG["published"]
    assert (CONFIG["num_hidden_layers"], published["num_hidden_layers"]) \
        == (5, 40)
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert CONFIG[key] == published[key][:5] and len(published[key]) == 40
    assert CONFIG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    # the router is as wide as published, and 1/8 of the vocabulary is held
    assert CONFIG["num_experts"] * CONFIG["expert_parallel_size"] == 256 \
        == published["num_experts"]
    assert CONFIG["vocab_size"] * 8 == 100352 == published["vocab_size"]
    for key in ("changed_why", "assumed", "deployment", "weights"):
        assert CONFIG[key], key
    assert set(CONFIG["changed_why"]) == set(entry["reduced"])
    assert {"a_gate", "b_qk_norm", "c_router", "d_shared_expert_gate",
            "e_norm", "f_aux_loss"} <= set(CONFIG["assumed"])
    assert "8 chips share each layer" in CONFIG["deployment"]
    names = {m["name"] for m in manifest.metrics_for(bench, cell, "per_layer")}
    assert {"swa_attn_share", "swa_fwd_roofline", "swa_bwd_roofline",
            "swa_tile_overcompute", "moe_gmm_roofline",
            "moe_gmm_share", "moe_dispatch_ms", "moe_rows_max_over_mean",
            "moe_rows_held_share",
            "flash_attn_share", "flash_attn_roofline", "flash_fwd_roofline",
            "flash_bwd_roofline", "train_mfu", "train_step_ms",
            "train_program_hbm_gb", "train_unscoped_share",
            "train_device_idle_share", "train_compiles_in_window"} <= names
    # ... and not the other families' (a later window model may LIST itself
    # under the four ``swa_*`` readers: they are held to list this cell)
    assert not names & {"collective_exposed_share", "collectives_per_step",
                        "gdn_scan_share", "gdn_scan_roofline", "gdn_layer_ms"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("swa_attn_share", "swa_fwd_roofline", "swa_bwd_roofline",
                 "swa_tile_overcompute"):
        assert CELL in by_name[name]["workloads"], name


def test_the_cell_is_the_one_issue_33_names():
    the_cell_is_the_one_issue_33_names(BENCH)


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key; depth with its three lists, experts held and vocabulary differ, and
    are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    assert CONFIG["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"])
    for key in ("head_dim", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sliding_window"):
        assert key in laguna.WIDTH_KEYS or key.endswith("_dim")
        assert CONFIG[key] == CONFIG["published"][key] == row["config"][key]
    assert CONFIG["rope_parameters"] == row["config"]["rope_parameters"]
    assert CONFIG["moe_routed_scaling_factor"] == 2.5


def test_the_parameter_arithmetic_is_the_initialised_trees():
    """``changed_why``'s numbers against ``jax.eval_shape`` of the model the
    configuration builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = laguna._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == model.config.num_params() == 691_632_128
    assert count(shapes["lead_0"]["attn"]) == 29_458_432
    assert count(shapes["layers"]["l0"]["attn"]) == 37_879_808
    assert count(shapes["lead_0"]["mlp"]) == 50_331_648
    moe = shapes["layers"]["l0"]["mlp"]
    routed = count({k: moe[k] for k in ("gate_proj", "up_proj", "down_proj")})
    assert routed == 32 * 3_145_728 and count(moe) - routed == 3_672_064
    why = CONFIG["changed_why"]["num_hidden_layers"]
    for number in ("29,458,432", "37,879,808", "3,145,728", "3,672,064",
                   "50,331,648", "691,632,128", "9.68 GB"):
        assert number in why, number
    assert 691_632_128 * 14 / 1e9 == pytest.approx(9.68, abs=0.005)


# --------------------------------------------------------- the tolerance

LOSS, NORM = 9.47, 1.5        # of the order the chip shows
TOL = CONFIG["train"]["tolerance"]
LEAVES = {"embed", "lm_head", "norm", "input_norm", "post_attn_norm",
          "mlp_gate", "mlp_up", "mlp_down", "router", "gate", "up", "down",
          "shared_gate", "shared_up", "shared_down", "shared_expert_gate"} \
    | {f"{n}.{k}" for n in "qkvgo" for k in ("full", "swa")}
# an honest run: half of every limit
DIFFERENCES = {
    "routing_differs": int(0.3 * TOL["routing_differs_share"] * 524_288),
    "routing_assignments": 524_288,
    "full_out_rel": 0.5 * TOL["full_out_rel"],
    "swa_out_rel": 0.5 * TOL["swa_out_rel"],
    "dense_out_rel": 0.5 * TOL["dense_out_rel"],
    "ffn_out_rel": 0.5 * TOL["ffn_out_rel"], "system_grad_norm": NORM,
    "own_stream_by_layer": [
        ["full_attention", "dense", 0.005, 0.007, 0.0],
        ["sliding_attention", "sparse", 0.02, 0.03,
         0.5 * TOL["own_stream_first_layer"]["routing_share"]]],
    "stream_add_rel": 0.0025, "window_vs_causal_rel": 0.9,
    "window_leak_rel": 0.0, "causal_leak_rel": 1.1,
    "grad_leaf_rel": {name: 0.5 * TOL["grad_leaf_rel"][name]
                      for name in LEAVES}}


def _passes(loss=LOSS, norm=NORM, **differences):
    leaves = dict(DIFFERENCES["grad_leaf_rel"],
                  **differences.pop("grad_leaf_rel", {}))
    checks, _ = laguna.judge_train(
        CONFIG, loss, norm, LOSS, NORM,
        dict(DIFFERENCES, grad_leaf_rel=leaves, **differences))
    return all(checks.values())


def test_an_honest_step_passes_with_room():
    # about three times the largest honest reading on the chip, each
    assert TOL["loss_abs"] <= 1.2e-3 and TOL["grad_norm_rel"] <= 0.004
    assert _passes()
    # a limit for every gradient leaf of the model, and no other
    assert set(TOL["grad_leaf_rel"]) == LEAVES
    assert TOL["why"] and len(TOL["why"]) > 500
    for key in ("loss_abs", "grad_norm_rel", "routing_differs_share",
                "full_out_rel", "swa_out_rel", "dense_out_rel", "ffn_out_rel",
                "own_stream_first_layer", "stream_add_rel",
                "window_vs_causal_rel_min", "window_leak_rel",
                "grad_leaf_rel"):
        assert key in TOL["why"], f"no reason given for {key}"


@pytest.mark.parametrize("fault,kw", [
    # 0.001 x 4 sparse layers x E sum f P ~ 0.001 x 4 x 8
    ("the balance loss left out", dict(loss=LOSS - 0.032)),
    ("the window not applied", dict(swa_out_rel=0.5)),
    ("the window not applied, by the check no mask can hide",
     dict(window_vs_causal_rel=0.0)),
    ("attention that reaches past its window", dict(window_leak_rel=0.2)),
    ("a leak test without teeth", dict(causal_leak_rel=0.0)),
    ("YaRN's scaling / the per-head gate left out", dict(full_out_rel=0.5)),
    ("a sliding layer alone wrong", dict(swa_out_rel=2 * TOL["swa_out_rel"])),
    ("the dense layer's MLP wrong", dict(dense_out_rel=0.5)),
    ("the routed scaling factor left out / the shared expert ungated",
     dict(ffn_out_rel=0.6)),
    ("a router that picks other experts", dict(routing_differs=52_000)),
    ("no expert weight gradient",
     dict(grad_leaf_rel={"gate": 1.0, "up": 1.0, "down": 1.0})),
    ("the window kernels' dk wrong", dict(grad_leaf_rel={"k.swa": 0.5})),
    ("a first layer that is wrong where a pinned pass cannot see",
     dict(own_stream_by_layer=[["full_attention", "dense", 0.2, 0.007, 0.0],
                               ["sliding_attention", "sparse", 0, 0, 0.0]])),
    ("the first sparse layer's routing on its own stream",
     dict(own_stream_by_layer=[["full_attention", "dense", 0.005, 0.007, 0.0],
                               ["sliding_attention", "sparse", 0, 0, 0.2]])),
    ("a residual add that loses a tenth of a branch",
     dict(stream_add_rel=0.07)),
    ("a leaf the comparison never saw",
     dict(grad_leaf_rel={"g.swa": float("nan")})),
    ("the compared gradients are not the step's",
     dict(system_grad_norm=NORM * 1.01)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_wrong_step_fails(fault, kw):
    loss, norm = kw.pop("loss", LOSS), kw.pop("norm", NORM)
    assert not _passes(loss, norm, **kw), fault


# ------------------------------------------------------------ the readers

STEP = "jit(train_batch_fn)/ds_fwd_bwd"
FWD = STEP + "/jvp(LagunaForCausalLM)"
BWD = STEP + "/transpose(jvp(LagunaForCausalLM))"
SCAN = "/layers/while/body/closed_call"
REMAT = BWD + SCAN + "/l0/checkpoint/rematted_computation"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, op_name, ns): one step of 1000 ms on one chip
OPS = [
    ("%swa_fwd.1 = f32[64,16384,128] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/l0/attn/swa_fwd/pallas_call", 20e6),
    ("%swa_fwd.2 = f32[64,16384,128] custom-call(%a)" + PALLAS,
     REMAT + "/attn/swa_fwd/pallas_call", 20e6),
    # ONE backward call since PR 53: dq, dk and dv from one kernel
    ("%swa_bwd.3 = f32[64,16384,128] custom-call(%a)" + PALLAS,
     BWD + SCAN + "/l0/attn/swa_bwd/pallas_call", 80e6),
    ("%flash_fwd_chunk.5 = f32[48,16384,128] custom-call(%a)" + PALLAS,
     FWD + "/lead_0/attn/flash_fwd_chunk/pallas_call", 100e6),
    ("%flash_bwd_dq.6 = f32[48,16384,128] custom-call(%a)" + PALLAS,
     BWD + "/lead_0/attn/flash_bwd_dq/pallas_call", 200e6),
    ("%fusion.7 = bf16[1,16384,48,128] fusion(%a)",
     FWD + "/lead_0/attn/attn_gate/mul", 2e6),
    ("%fusion.8 = bf16[16384,8192] fusion(%a)",
     FWD + "/lead_0/mlp/dense_mlp/gate_proj/dot_general", 40e6),
    ("%moe_gmm.9 = bf16[32768,512] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/l3/mlp/moe_gmm/pallas_call", 8e6),
    ("%sort.10 = s32[131072] sort(%a)",
     FWD + SCAN + "/l3/mlp/moe_dispatch/sort", 7e6),
    ("%fusion.11 = bf16[16384,2048] fusion(%a)", FWD + SCAN + "/l3/mlp/add",
     523e6),
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
        rehearse=False, compiled_text=text,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    plane = "/device:TPU:0"
    record.trace = tr.Trace({plane: {
        "XLA Ops": events,
        "XLA Modules": [tr.Event("jit_train_batch_fn(1)", 0.0, t)]}}, {})
    record.slice = (0.0, t)
    record.extra.update(step_module="jit_train_batch_fn", global_batch=1,
                        seq_len=S, tokens_per_step=S, **(extra or {}))
    return record


def test_the_new_readers_on_a_hand_made_scope_table():
    record = _record(laguna)
    chip = sr.busiest_chip(record)
    assert chip["busy_ms"] == pytest.approx(1000.0)
    # forward 20 + recomputed 20 + dq 30 + dkv 50, of 1000
    assert swa_attn_share.read(record) == pytest.approx(12.0)
    # 811.8 GFLOP of band forward in 40 ms; 1,623.5 backward in 80 ms
    assert swa_fwd_roofline.read(record) == pytest.approx(
        100 * 811_773_984_768 / 197e12 / 0.040)
    assert swa_bwd_roofline.read(record) == pytest.approx(
        100 * 1_623_547_969_536 / 197e12 / 0.080)
    assert swa_fwd_roofline.read(record) == pytest.approx(10.30, abs=0.01)
    # the full layers' kernels keep their own tags and their own count
    assert flash_attn_share.read(record) == pytest.approx(30.0)
    assert flash_fwd_roofline.read(record) == pytest.approx(
        100 * 19_791_209_299_968 / 3 / 197e12 / 0.100)
    assert flash_bwd_roofline.read(record) < 100
    assert moe_dispatch_ms.read(record) == pytest.approx(7.0)
    assert moe_gmm_roofline.read(record) == pytest.approx(
        100 * 1_236_950_581_248 / 197e12 / 0.008)
    rows = {(p, t): ms for p, t, _, ms in chip["rows"]}
    assert rows[("forward", "attn_gate")] == pytest.approx(2.0)
    assert rows[("forward", "dense_mlp")] == pytest.approx(40.0)
    assert rows[("recompute", "swa_fwd")] == pytest.approx(20.0)
    assert rows[("backward", "swa_bwd")] == pytest.approx(80.0)


@pytest.mark.parametrize("family", [gpt2, olmoe, qwen3_next],
                         ids=["gpt2", "olmoe", "qwen3_next"])
def test_a_program_without_the_layer_reads_nothing(family):
    """The parent's programs under this PR's benchmark files: no family
    there lists a ``swa_*`` scope, counts such work or sets the gauge, so
    every new reader returns None and raises nothing."""
    record = _record(family)
    for reader in (swa_attn_share, swa_fwd_roofline, swa_bwd_roofline,
                   swa_tile_overcompute):
        assert reader.read(record) is None, reader.NAME
    untraced = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                              family=laguna, rehearse=False, peaks=None)
    untraced.extra.update(tokens_per_step=S, global_batch=1, seq_len=S)
    for reader in (swa_attn_share, swa_fwd_roofline, swa_bwd_roofline):
        assert reader.read(untraced) is None, reader.NAME


def test_the_gauges_are_read_through_the_family(monkeypatch):
    fake = types.SimpleNamespace(
        program_gauges=lambda: {"attention/window_tile_overcompute": 1.5,
                                "moe/rows_held_share": 0.125})
    record = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                            family=fake, rehearse=False, peaks=None)
    assert swa_tile_overcompute.read(record) == 1.5
    assert moe_rows_held_share.read(record) == 12.5
    assert laguna.rows_held_share(CONFIG) == 0.125
    # before any engine was built there is no gauge to read
    monkeypatch.setattr(laguna, "_LIVE", {})
    record.family = laguna
    assert swa_tile_overcompute.read(record) is None
    monkeypatch.setattr(laguna, "_LIVE", {"gauges": {
        "attention/window_tile_overcompute": 1.4979}})
    assert swa_tile_overcompute.read(record) == 1.4979
    record.family = qwen3_next
    assert swa_tile_overcompute.read(record) is None


# ------------------------------------------------- the cell, rehearsed

@pytest.fixture(scope="module")
def control():
    """``tools/precision_control`` on the cell at the rehearsal's size: one
    JSON object a pass (the honest one, then fp8 weight matrices)."""
    import contextlib
    import io
    from benchmark.tools import precision_control
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        code = precision_control.main([CELL, "--seed", "4400000017",
                                       "--rehearse-cpu"])
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()
             if ln.startswith("{")]
    return code, lines


def test_a_cpu_rehearsal_of_the_cell_is_correct(control):
    """The cell's set-up at the rehearsal's size, on the timed batch: the
    program's step against the float32 reference passes every check."""
    _, (honest, _) = control
    assert honest["system"] == "as the cell runs"
    assert honest["correct"], [k for k, v in honest["checks"].items()
                               if not v]
    assert {"window_is_applied_and_nothing_reaches_past_it",
            "dense_branch_matches_reference",
            "first_layer_matches_reference_on_its_own_stream",
            "residual_stream_adds_up", "routing_matches_reference",
            "gradients_match_reference_leaf_by_leaf"} <= set(honest["checks"])
    diffs = honest["detail"]["differences"]
    assert diffs["window_leak_rel"] == 0.0
    assert diffs["window_vs_causal_rel"] > 0.3


def test_the_fp8_control_is_not_correct(control):
    """Every weight matrix rounded to fp8 must fail ``correct``, by at least
    one of the limits (and the tool's exit code says the pair came out as
    it must: honest correct, fp8 not)."""
    code, (_, fp8) = control
    assert fp8["system"] == "fp8 weight matrices" and not fp8["correct"]
    assert [k for k, v in fp8["checks"].items() if not v]
    assert code == 0
