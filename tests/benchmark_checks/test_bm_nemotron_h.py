"""The Nemotron-3-Nano cell (ISSUE 40): the manifest's entries found by NAME,
the catalog's numbers, the parameter arithmetic, the family's contract and
its counts of operations and bytes by hand at one size, the comparison that
decides ``correct`` on hand-made readings, and the three new readers
(``ssd_scan_share``, ``ssd_scan_roofline``, ``ssm_layer_ms``) on a hand-made
scope table and on programs that lack the scope."""

import json
import os

import pytest

from benchmark import families, harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import (gpt2, laguna, nemotron_h, olmoe, qwen3_next,
                                smallthinker)
from benchmark.layer_metrics import (moe_dispatch_ms, moe_gmm_roofline,
                                     moe_router_ms, ssd_scan_roofline,
                                     ssd_scan_share, ssm_layer_ms)

CELL = "nemotron3nano-train-1chip-s16384"
NAME = "nemotron-3-nano-30b-a3b-ep16-depth9"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
          "/blob/main/config.json")
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
TRAFFIC = manifest.traffic_of({"name": CELL})

S = 16384
H = 2688
MAMBA = H * 10304 + 4096 * H              # the two projections
ATTENTION = 2 * H * 4096 + 2 * H * 256
EXPERT = 2 * H * 1856
SHARED = 2 * H * 3712
HEAD = 16384 * H
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]
NEW = ("ssd_scan_share", "ssd_scan_roofline", "ssm_layer_ms")


def the_cell_is_the_one_issue_40_names(bench):
    """Entries by name: a later PR appends and this stays true."""
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain-b1x16384", 1)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert sorted(entry["reduced"]) == sorted(REDUCED) \
        == sorted(CONFIG["reduced"])
    names = {m["name"] for m in manifest.metrics_for(bench, cell, "per_layer")}
    assert {*NEW, "moe_gmm_roofline", "moe_gmm_share", "moe_dispatch_ms",
            "moe_rows_max_over_mean", "moe_rows_held_share", "moe_router_ms",
            "flash_attn_share", "flash_attn_roofline", "flash_fwd_roofline",
            "flash_bwd_roofline", "train_mfu", "train_step_ms",
            "train_fwd_ms", "train_bwd_ms", "train_recompute_ms",
            "train_optimizer_ms", "train_peak_hbm_gb",
            "train_program_hbm_gb", "train_unscoped_share",
            "train_device_idle_share", "train_compiles_in_window",
            "setup_engine_init_s", "setup_first_step_s",
            "setup_outside_program_s", "setup_compile_s",
            "setup_programs_compiled", "setup_cache_misses"} <= names
    assert not [n for n in names if n.startswith(("swa_", "gdn_",
                                                  "collective"))]
    e2e = {m["name"] for m in manifest.metrics_for(bench, cell, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    for name, module in zip(NEW, (ssd_scan_share, ssd_scan_roofline,
                                  ssm_layer_ms)):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"]
        assert (m["name"], m["unit"], m["layer"], m["moves"], m["source"]) \
            == (module.NAME, module.UNIT, module.LAYER, module.MOVES,
                module.SOURCE) and m["layer"] == "state-space mixer"
    assert [m["unit"] for m in bench["per_layer"] if m["name"] in NEW] \
        == ["%", "%", "ms"]


def test_the_cell_is_the_one_issue_40_names():
    the_cell_is_the_one_issue_40_names(BENCH)
    assert manifest.problems(BENCH) == []
    assert (TRAFFIC["kind"], TRAFFIC["global_batch"], TRAFFIC["seq_len"],
            TRAFFIC["token_below"], TRAFFIC["batch_pool"],
            TRAFFIC["warmup_steps"], TRAFFIC["fence_lag_steps"],
            TRAFFIC["trace_steps"]) == ("train_steps", 1, S, 16384, 16, 3, 2,
                                        3)
    for key in ("users", "why_in_full"):
        assert TRAFFIC[key], key
    for said in ("32k", "768 an expert", "12,288", "random router",
                 "Nine layers", "batch 1", "moe_gmm_roofline"):
        assert said in TRAFFIC["why_in_full"], said
    assert "32k" in TRAFFIC["why"] and "12,288" in TRAFFIC["why"]


def test_the_family_keeps_the_contract():
    for member in families.TRAINING + families.TAGS:
        assert hasattr(nemotron_h, member), member
    assert not [m for m in families.SERVING if hasattr(nemotron_h, m)]
    assert "ssd_scan" in nemotron_h.KERNEL_TAGS
    assert {"ssd_scan", "mamba", "ssm_conv", "ssm_gates", "ssm_norm"} \
        == set(nemotron_h.SSM_LAYER_TAGS)
    tags = nemotron_h.MODULE_TAGS
    # a path under ``mamba`` is tagged by its own scope first
    assert max(tags.index(t) for t in ("ssm_conv", "ssm_gates", "ssm_norm")) \
        < tags.index("mamba")
    shapes = nemotron_h.traffic_shapes(CONFIG, False)
    assert shapes == {"vocab_size": 16384, "max_positions": 262144,
                      "seq_scale": 1.0}
    assert nemotron_h.traffic_shapes(CONFIG, True)["seq_scale"] == 1 / 64


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key; depth with its pattern, experts held and vocabulary differ, and
    are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert CONFIG["source"] == row["source_url"] == SOURCE
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    published = CONFIG["published"]
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                "n_groups", "ssm_state_size", "conv_kernel", "expand",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "intermediate_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "num_experts_per_tok"):
        assert key in nemotron_h.WIDTH_KEYS or key.endswith("_dim")
        assert CONFIG[key] == published[key] == row["config"][key]
    for key in REDUCED:
        assert published[key] == row["config"][key], key
    assert CONFIG["hybrid_override_pattern"] == "MEMEM*EME" \
        == published["hybrid_override_pattern"][:9]
    assert len(published["hybrid_override_pattern"]) == 52
    assert CONFIG["n_routed_experts"] * CONFIG["expert_parallel_size"] \
        == 128 == published["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == 131072 == published["vocab_size"]
    assert set(CONFIG["changed_why"]) == set(REDUCED)
    assert {"a_attention_no_rotation", "b_mamba_init", "c_conv",
            "e_selection_bias", "f_no_aux_loss",
            "i_rescale_prenorm_residual"} <= set(CONFIG["assumed"])
    assert "16 chips share each layer's experts" in CONFIG["deployment"]
    assert CONFIG["model"]["remat"] and CONFIG["rehearse_cpu"]


def test_the_parameter_arithmetic_is_the_initialised_trees():
    """``changed_why``'s numbers against ``jax.eval_shape`` of the model the
    configuration builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = nemotron_h._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == model.config.num_params() == 666_963_456
    assert count(shapes["layer_0"]) == 38_744_896
    assert count(shapes["layer_5"]) == 23_399_040
    assert count(shapes["layer_1"]) == 100_125_440
    assert count(shapes["layer_1"]["mixer"]["up_proj"]) * 2 == 8 * EXPERT
    assert EXPERT == 9_977_856 and SHARED == 19_955_712
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) \
        == 88_080_384
    why = " ".join(CONFIG["changed_why"].values())
    for number in ("38,744,896", "23,399,040", "100,125,440", "9,977,856",
                   "19,955,712", "88,080,384", "666,963,456", "319,291,392",
                   "9.34 GB"):
        assert number in why, number
    assert 666_963_456 * 14 / 1e9 == pytest.approx(9.34, abs=0.005)
    assert "31,577,940,288" in CONFIG["published"]["parameters"]


def test_flops_and_bytes_count_what_this_rank_needs(monkeypatch):
    f = nemotron_h
    monkeypatch.setitem(f._LIVE, "gauges", {})
    assert f.rows_held_share(CONFIG) == 1 / 16
    assert f.active_matmul_params(CONFIG) == HEAD + 4 * MAMBA + ATTENTION \
        + 4 * (H * 128 + SHARED + 6 / 16 * EXPERT)
    scan = 5 * 64 * 128 * 64                # a token a layer, forward
    assert f.train_flops_per_token(CONFIG, S) == \
        6 * f.active_matmul_params(CONFIG) + 6 * S * 32 * 128 + 4 * 3 * scan
    assert f.train_attention_flops_per_step(CONFIG, 1, S) == \
        6 * 32 * S * S * 128
    flops, nbytes = f.ssd_scan_flops_and_bytes(CONFIG, S)
    assert flops == 4 * S * 3 * scan == 515_396_075_520
    # x, y 8,192 B; B + C 4,096 B; dt 256 B a token a layer
    assert nbytes == 4 * S * (3 * (8192 + 4096 + 256) + 2 * 8192)
    assert nbytes / 819e9 > flops / 197e12          # the bytes bind
    # the grouped matmuls: 6,144 rows a layer at 1,856, not 1,920
    rows = S * 6 / 16
    assert f.moe_gmm_flops_per_step(CONFIG, S) == \
        4 * 3 * 2 * 2 * rows * H * 1856
    monkeypatch.setitem(f._LIVE, "gauges", {"moe/rows_held_share": 0.07})
    assert f.moe_gmm_flops_per_step(CONFIG, S) == pytest.approx(
        4 * 3 * 2 * 2 * S * 6 * 0.07 * H * 1856)


# --------------------------------------------- the comparison, by hand

TOL = CONFIG["train"]["tolerance"]
LOSS, NORM = 9.7, 1.4


def _differences(**over):
    """An honest step's readings (each a third of its limit), or with
    ``over``."""
    own = [[k, TOL["own_stream_first_layers"]["ssm_rel"] / 3 if k == "M"
            else TOL["own_stream_first_layers"]["ffn_rel"] / 3,
            TOL["own_stream_first_layers"]["routing_share"] / 3
            if k == "E" else 0.0] for k in "MEMEM*EME"]
    out = dict(
        own_stream_by_layer=own, stream_add_rel=TOL["stream_add_rel"] / 3,
        system_grad_norm=NORM, bias_grad_abs=0.0,
        ssm_out_rel=TOL["ssm_out_rel"] / 3,
        attn_out_rel=TOL["attn_out_rel"] / 3,
        ffn_out_rel=TOL["ffn_out_rel"] / 3, routing_differs=10,
        routing_assignments=4 * S * 6,
        grad_leaf_rel={k: v / 3 for k, v in TOL["grad_leaf_rel"].items()})
    out.update(over)
    return out


def _passes(loss=LOSS, norm=NORM, **over):
    checks, _ = nemotron_h.judge_train(CONFIG, loss, norm, LOSS, NORM,
                                       _differences(**over))
    return checks


def test_an_honest_step_passes_with_room(monkeypatch):
    monkeypatch.setitem(nemotron_h._LIVE, "engine", None)
    checks = _passes()
    assert all(checks.values()), checks
    assert {"state_space_branch_matches_reference",
            "selection_bias_takes_no_gradient",
            "first_layers_match_reference_on_their_own_stream",
            "residual_stream_adds_up", "attention_branch_matches_reference",
            "expert_branch_matches_reference", "routing_matches_reference",
            "gradients_match_reference_leaf_by_leaf"} <= set(checks)


def _own(kind, column, value):
    rows = _differences()["own_stream_by_layer"]
    first = next(r for r in rows if r[0] == kind)
    first[column] = value
    return rows


@pytest.mark.parametrize("fault,kw,check", [
    ("the scan's branch off", {"ssm_out_rel": 3 * TOL["ssm_out_rel"]},
     "state_space_branch_matches_reference"),
    ("a gradient reaches the bias", {"bias_grad_abs": 1e-9},
     "selection_bias_takes_no_gradient"),
    ("the experts' branch off", {"ffn_out_rel": 3 * TOL["ffn_out_rel"]},
     "expert_branch_matches_reference"),
    ("the attention branch off", {"attn_out_rel": 3 * TOL["attn_out_rel"]},
     "attention_branch_matches_reference"),
    ("one leaf off", {"grad_leaf_rel": dict(
        {k: 0.0 for k in TOL["grad_leaf_rel"]},
        **{"ssm.A_log": 2 * TOL["grad_leaf_rel"]["ssm.A_log"]})},
     "gradients_match_reference_leaf_by_leaf"),
    ("a leaf missing", {"grad_leaf_rel": {
        k: 0.0 for k in TOL["grad_leaf_rel"] if k != "ssm.D"}},
     "gradients_match_reference_leaf_by_leaf"),
    ("the first scan on its own stream", {"own_stream_by_layer": _own(
        "M", 1, 2 * TOL["own_stream_first_layers"]["ssm_rel"])},
     "first_layers_match_reference_on_their_own_stream"),
    ("the first router on its own stream", {"own_stream_by_layer": _own(
        "E", 2, 2 * TOL["own_stream_first_layers"]["routing_share"])},
     "first_layers_match_reference_on_their_own_stream"),
    ("a branch lost from the stream", {"stream_add_rel": 0.5},
     "residual_stream_adds_up"),
    ("the routing off", {"routing_differs": int(
        2 * TOL["routing_differs_share"] * 4 * S * 6)},
     "routing_matches_reference"),
], ids=lambda v: v if isinstance(v, str) and " " in v else "")
def test_a_wrong_step_fails(monkeypatch, fault, kw, check):
    monkeypatch.setitem(nemotron_h._LIVE, "engine", None)
    checks = _passes(**kw)
    assert not checks[check], fault
    assert [k for k, v in checks.items() if not v] == [check]


def test_a_wrong_loss_or_norm_fails(monkeypatch):
    monkeypatch.setitem(nemotron_h._LIVE, "engine", None)
    assert not _passes(loss=LOSS + 2 * TOL["loss_abs"])[
        "first_loss_matches_reference"]
    assert not _passes(norm=NORM * (1 + 2 * TOL["grad_norm_rel"]))[
        "first_grad_norm_matches_reference"]


# ------------------------------------------- the readers, on a hand-made run

JIT = "jit(train_batch_fn)/ds_fwd_bwd/"
FWD = JIT + "jvp(NemotronHForCausalLM)/layer_0/checkpoint"
REC = JIT + "transpose(jvp(NemotronHForCausalLM))/layer_0/checkpoint" \
    "/rematted_computation"
BWD = JIT + "transpose(jvp(NemotronHForCausalLM))/layer_0/checkpoint"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, the path it was traced under, ns in a step of 1 s)
OPS = [
    ("%fusion.1 = bf16[16384,10304] fusion(%a)", FWD + "/mamba/in_proj/dot",
     40e6),
    ("%fusion.2 = bf16[16384,6144] fusion(%a)", FWD + "/mamba/ssm_conv/mul",
     6e6),
    ("%fusion.3 = f32[16384,64] fusion(%a)", FWD + "/mamba/ssm_gates/exp",
     1e6),
    ("%fusion.4 = f32[1,8,128,8,128] fusion(%a)",
     FWD + "/mamba/ssd_scan_prep/cumsum", 2e6),
    ("%ssd.5 = bf16[1,16384,4096] custom-call(%a)" + PALLAS,
     FWD + "/mamba/ssd_scan_fwd/pallas_call", 8e6),
    ("%ssd.6 = bf16[1,16384,4096] custom-call(%a)" + PALLAS,
     REC + "/mamba/ssd_scan_fwd/pallas_call", 9e6),
    ("%ssd.7 = bf16[1,16384,4096] custom-call(%a)" + PALLAS,
     BWD + "/mamba/ssd_scan_bwd/pallas_call", 21e6),
    ("%fusion.8 = bf16[16384,4096] fusion(%a)", BWD + "/mamba/ssm_norm/mul",
     7e6),
    ("%fusion.9 = f32[16384,128] fusion(%a)",
     FWD.replace("layer_0", "layer_1") + "/mixer/moe_router/dot", 3e6),
    ("%gmm.10 = bf16[12288,1920] custom-call(%a)" + PALLAS,
     FWD.replace("layer_0", "layer_1") + "/mixer/moe_gmm/pallas_call", 10e6),
    ("%fusion.11 = bf16[16384,2688] fusion(%a)",
     FWD.replace("layer_0", "layer_5") + "/mixer/o_proj/dot", 893e6),
]


def _record(family):
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
                        seq_len=S, tokens_per_step=S)
    return record


def test_the_readers_on_a_hand_made_scope_table(monkeypatch):
    monkeypatch.setitem(nemotron_h._LIVE, "gauges", {})
    record = _record(nemotron_h)
    chip = sr.busiest_chip(record)
    assert chip["busy_ms"] == pytest.approx(1000.0)
    # the scan: the re-layout and the three kernel calls, 2 + 8 + 9 + 21
    assert ssd_scan_share.scan_ms(record) == pytest.approx(40.0)
    assert ssd_scan_share.read(record) == pytest.approx(4.0)
    _, nbytes = nemotron_h.ssd_scan_flops_and_bytes(CONFIG, S)
    assert ssd_scan_roofline.read(record) == pytest.approx(
        100 * nbytes / 819e9 / 0.040)
    assert 0 < ssd_scan_roofline.read(record) < 100
    # the mixer: the scan, the projection, conv, gates and norm
    assert ssm_layer_ms.read(record) == pytest.approx(40 + 40 + 6 + 1 + 7)
    rows = {}
    for p, t, _, ms in chip["rows"]:        # a kernel's and an op's row
        rows[p, t] = rows.get((p, t), 0.0) + ms
    assert rows[("forward", "ssd_scan")] == pytest.approx(10.0)
    assert rows[("recompute", "ssd_scan")] == pytest.approx(9.0)
    assert rows[("backward", "ssd_scan")] == pytest.approx(21.0)
    assert rows[("forward", "mamba")] == pytest.approx(40.0)
    assert chip["kernel_ms"]["ssd_scan"] == pytest.approx(38.0)
    assert moe_router_ms.read(record) == pytest.approx(3.0)
    assert moe_dispatch_ms.read(record) == pytest.approx(3.0)
    assert moe_gmm_roofline.read(record) == pytest.approx(
        100 * nemotron_h.moe_gmm_flops_per_step(CONFIG, S) / 197e12 / 0.010)


@pytest.mark.parametrize("family", [gpt2, olmoe, qwen3_next, laguna,
                                    smallthinker],
                         ids=["gpt2", "olmoe", "qwen3_next", "laguna",
                              "smallthinker"])
def test_a_program_without_the_scopes_reads_nothing(family):
    """The new readers on the other families' programs (the parent's, too:
    it has no family with these tags) and on a run without a trace: None,
    and nothing raised."""
    record = _record(family)
    for reader in (ssd_scan_share, ssd_scan_roofline, ssm_layer_ms):
        assert reader.read(record) is None, reader.NAME
    untraced = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                              family=nemotron_h, rehearse=False, peaks=None)
    untraced.extra.update(tokens_per_step=S, global_batch=1, seq_len=S)
    for reader in (ssd_scan_share, ssd_scan_roofline, ssm_layer_ms):
        assert reader.read(untraced) is None, reader.NAME


def test_the_gauges_are_read_through_the_family(monkeypatch):
    from benchmark.layer_metrics import (moe_rows_held_share,
                                         moe_rows_max_over_mean)
    record = _record(nemotron_h)
    monkeypatch.setitem(nemotron_h._LIVE, "gauges", {})
    assert moe_rows_held_share.read(record) is None
    monkeypatch.setitem(nemotron_h._LIVE, "gauges", {
        "moe/rows_held_share": 0.0631, "moe/rows_max_over_mean": 1.7})
    assert moe_rows_held_share.read(record) == pytest.approx(6.31)
    assert moe_rows_max_over_mean.read(record) == pytest.approx(1.7)


def test_set_up_levels_the_routers_loads():
    """``balanced_selection_bias`` at the rehearsal's widths: the worst
    expert's rows over the mean fall in every expert layer and only the
    selection biases move; the engine ``build_train`` returns holds the
    moved tree."""
    import copy
    import jax
    import numpy as np
    config = copy.deepcopy(CONFIG)
    config["rehearse_cpu"]["train"]["selection_bias_balance"] = {
        "seq_len": 512, "rounds": 24}
    seed = 4000000123
    drawn = jax.jit(lambda key: nemotron_h._model(config, True).init(
        key, np.zeros((1, 64), np.int32))["params"])(jax.random.PRNGKey(seed))
    moved, found = nemotron_h.balanced_selection_bias(config, drawn, 1, seed,
                                                      True)
    worst = found["rows_max_over_mean"]
    assert len(worst["first_round"]) == 4
    assert max(worst["last_round"]) < 1.4 < min(worst["first_round"])
    for (path, before), after in zip(
            jax.tree_util.tree_leaves_with_path(drawn),
            jax.tree_util.tree_leaves(moved)):
        bias = path[-1].key == "e_score_correction_bias"
        assert np.array_equal(before, after) != bias, path

    engine, params = nemotron_h.build_train(config, 1, seed,
                                            jax.devices()[:1], True)
    assert nemotron_h._LIVE["balance"]["rows_max_over_mean"]
    for mine, theirs in zip(jax.tree_util.tree_leaves(engine.state.params),
                            jax.tree_util.tree_leaves(params)):
        assert mine is theirs
