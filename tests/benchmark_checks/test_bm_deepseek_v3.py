"""The Kanana-2 cell (ISSUE 47): the manifest's entries found by NAME, the
catalog's numbers, the parameter arithmetic, the family's contract and its
counts of operations by hand at one size, the comparison that decides
``correct`` on hand-made readings, and the two new readers (``mla_layer_ms``,
``mla_expand_ms``) on a hand-made scope table and on programs that lack the
scopes."""

import json
import os

import pytest

from benchmark import families, harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import (deepseek_v3, gpt2, laguna, nemotron_h, olmoe,
                                qwen3_next, smallthinker)
from benchmark.layer_metrics import (flash_bwd_roofline, flash_fwd_roofline,
                                     mla_expand_ms, mla_layer_ms,
                                     moe_dispatch_ms, moe_gmm_roofline,
                                     moe_router_ms)

CELL = "kanana2-train-1chip-s16384"
NAME = "kanana-2-30b-a3b-ep8-depth6"
SOURCE = ("https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601"
          "/blob/main/config.json")
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
TRAFFIC = manifest.traffic_of({"name": CELL})

S = 16384
H = 2048
ATTENTION = H * 32 * 192 + H * 576 + 512 * 32 * 256 + 4096 * H
DENSE = 3 * H * 6144
EXPERT = 3 * H * 768
SHARED = 3 * H * 1536
HEAD = 16032 * H
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW = ("mla_layer_ms", "mla_expand_ms")


def test_the_cell_is_the_one_issue_47_names():
    """Entries by name: a later PR appends and this stays true."""
    cell = manifest.cell_of(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain-b1x16384", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert sorted(entry["reduced"]) == sorted(REDUCED) \
        == sorted(CONFIG["reduced"])
    names = {m["name"] for m in manifest.metrics_for(BENCH, cell, "per_layer")}
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
    assert not [n for n in names if n.startswith(("swa_", "gdn_", "ssd_",
                                                  "ssm_", "collective"))]
    e2e = {m["name"] for m in manifest.metrics_for(BENCH, cell, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    for name, module in zip(NEW, (mla_layer_ms, mla_expand_ms)):
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"]
        assert (m["name"], m["unit"], m["layer"], m["moves"], m["source"]) \
            == (module.NAME, module.UNIT, module.LAYER, module.MOVES,
                module.SOURCE) == (name, "ms", "latent attention",
                                   "train_tokens_per_s", "device_trace")
    assert manifest.problems(BENCH) == []
    assert (TRAFFIC["kind"], TRAFFIC["global_batch"], TRAFFIC["seq_len"],
            TRAFFIC["token_below"], TRAFFIC["batch_pool"],
            TRAFFIC["warmup_steps"], TRAFFIC["fence_lag_steps"],
            TRAFFIC["trace_steps"]) == ("train_steps", 1, S, 16032, 16, 3, 2,
                                        3)
    for key in ("users", "why_in_full"):
        assert TRAFFIC[key], key
    for said in ("63 %", "768 an expert", "6,144", "8 x its share",
                 "Six layers", "moe_gmm_roofline", "192", "ROADMAP R4"):
        assert said in TRAFFIC["why_in_full"], said
    assert "6,144" in TRAFFIC["why"] and "8x its share" in TRAFFIC["why"]


def test_the_family_keeps_the_contract():
    for member in families.TRAINING + families.TAGS:
        assert hasattr(deepseek_v3, member), member
    assert not [m for m in families.SERVING if hasattr(deepseek_v3, m)]
    assert deepseek_v3.KERNEL_TAGS == ("flash_fwd", "flash_bwd", "moe_gmm")
    assert {"qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank", "qk_head_dim"} <= set(deepseek_v3.WIDTH_KEYS)
    assert set(deepseek_v3.MLA_EXPAND_TAGS) == {"mla_latent", "mla_expand",
                                                "mla_rope"}
    assert set(deepseek_v3.MLA_LAYER_TAGS) == {
        "flash_fwd", "flash_bwd", "mla_attn", *deepseek_v3.MLA_EXPAND_TAGS}
    tags = deepseek_v3.MODULE_TAGS
    # a path under ``mla_attn`` is tagged by its own scope first
    assert max(tags.index(t) for t in deepseek_v3.MLA_EXPAND_TAGS) \
        < tags.index("mla_attn")
    assert tags.index("dense_mlp") < tags.index("mlp")
    shapes = deepseek_v3.traffic_shapes(CONFIG, False)
    assert shapes == {"vocab_size": 16032, "max_positions": 32768,
                      "seq_scale": 1.0}
    assert deepseek_v3.traffic_shapes(CONFIG, True)["seq_scale"] == 1 / 8


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key; depth, experts held and vocabulary differ, and are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert CONFIG["source"] == row["source_url"] == SOURCE
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    published = CONFIG["published"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "kv_lora_rank", "q_lora_rank",
                "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "head_dim", "n_shared_experts",
                "num_experts_per_tok"):
        assert key in deepseek_v3.WIDTH_KEYS
        assert CONFIG[key] == published[key] == row["config"][key]
    for key in REDUCED:
        assert published[key] == row["config"][key], key
    assert CONFIG["n_routed_experts"] * CONFIG["expert_parallel_size"] \
        == 128 == published["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == 128256 == published["vocab_size"]
    assert CONFIG["num_hidden_layers"] == 6 \
        == CONFIG["first_k_dense_replace"] + 5
    assert set(CONFIG["changed_why"]) == set(REDUCED)
    assert {"a_initializer_range", "b_no_mtp_no_aux_loss", "c_rope_layout",
            "d_head_dim", "e_selection_bias", "f_shared_experts"} \
        <= set(CONFIG["assumed"])
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert CONFIG["model"]["remat"] and CONFIG["rehearse_cpu"]


def test_the_parameter_arithmetic_is_the_initialised_trees():
    """``changed_why``'s numbers against ``jax.eval_shape`` of the model the
    configuration builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = deepseek_v3._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == model.config.num_params() == 687_502_976
    assert ATTENTION == 26_345_472
    assert count(shapes["layer_0"]["mla_attn"]) == ATTENTION + 512
    assert count(shapes["layer_0"]) == 26_350_080 + DENSE
    assert DENSE == 37_748_736
    assert count(shapes["layer_1"]) == 111_547_008
    assert count(shapes["layer_1"]["mlp"]["up_proj"]) * 3 == 16 * EXPERT \
        == 75_497_472
    assert EXPERT == 4_718_592 and SHARED == 9_437_184
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) \
        + count(shapes["norm"]) == 65_669_120
    why = " ".join(CONFIG["changed_why"].values())
    for number in ("26,350,080", "37,748,736", "75,497,472", "9,437,184",
                   "262,144", "111,547,008", "65,669,120", "687,502,976",
                   "377,487,360", "6.88 GB", "9.63 GB"):
        assert number in why, number
    assert 687_502_976 * 14 / 1e9 == pytest.approx(9.63, abs=0.005)
    assert "30,670,815,104" in CONFIG["published"]["parameters"]


def test_flops_count_what_this_rank_needs(monkeypatch):
    f = deepseek_v3
    monkeypatch.setitem(f._LIVE, "gauges", {})
    assert f.rows_held_share(CONFIG) == 1 / 8
    assert f.active_matmul_params(CONFIG) == HEAD + 6 * ATTENTION + DENSE \
        + 5 * (H * 128 + SHARED + 6 / 8 * EXPERT)
    attention = 6 * 32 * S * S * (3 * 192 + 3 * 128)
    assert f.train_attention_flops_per_step(CONFIG, 1, S) == attention
    # the forward's two products are a third of the six, exactly
    assert (192 + 128) * 3 == 3 * 192 + 3 * 128
    assert f.train_flops_per_token(CONFIG, S) == \
        6 * f.active_matmul_params(CONFIG) + attention / S
    # attention is ~63 % of the needed flops at 1 x 16,384
    share = attention / (f.train_flops_per_token(CONFIG, S) * S)
    assert 0.60 < share < 0.66
    rows = S * 6 / 8
    assert f.moe_gmm_flops_per_step(CONFIG, S) == \
        5 * 3 * 3 * 2 * rows * H * 768
    monkeypatch.setitem(f._LIVE, "gauges", {"moe/rows_held_share": 0.13})
    assert f.moe_gmm_flops_per_step(CONFIG, S) == pytest.approx(
        5 * 3 * 3 * 2 * S * 6 * 0.13 * H * 768)


# --------------------------------------------- the comparison, by hand

TOL = CONFIG["train"]["tolerance"]
LOSS, NORM = 10.09, 1.69
KINDS = ["dense"] + ["sparse"] * 5


def _differences(**over):
    """An honest step's readings (each a third of its limit), or with
    ``over``."""
    first = TOL["own_stream_first_layer"]
    own = [[k, first["mixer_rel"] / 3, first["ffn_rel"] / 3,
            first["routing_share"] / 3 if k == "sparse" else 0.0]
           for k in KINDS]
    out = dict(
        own_stream_by_layer=own, stream_add_rel=TOL["stream_add_rel"] / 3,
        system_grad_norm=NORM, bias_grad_abs=0.0,
        mla_out_rel=TOL["mla_out_rel"] / 3,
        dense_out_rel=TOL["dense_out_rel"] / 3,
        ffn_out_rel=TOL["ffn_out_rel"] / 3, routing_differs=10,
        routing_assignments=5 * S * 6,
        grad_leaf_rel={k: v / 3 for k, v in TOL["grad_leaf_rel"].items()})
    out.update(over)
    return out


def _passes(loss=LOSS, norm=NORM, **over):
    checks, _ = deepseek_v3.judge_train(CONFIG, loss, norm, LOSS, NORM,
                                        _differences(**over))
    return checks


def test_an_honest_step_passes_with_room(monkeypatch):
    monkeypatch.setitem(deepseek_v3._LIVE, "engine", None)
    checks = _passes()
    assert all(checks.values()), checks
    assert {"attention_branch_matches_reference",
            "dense_branch_matches_reference",
            "expert_branch_matches_reference",
            "selection_bias_takes_no_gradient",
            "first_layer_matches_reference_on_its_own_stream",
            "residual_stream_adds_up", "routing_matches_reference",
            "gradients_match_reference_leaf_by_leaf"} <= set(checks)
    assert {"attn.q", "attn.kv_a", "attn.kv_a_norm", "attn.kv_b", "attn.o"} \
        <= set(TOL["grad_leaf_rel"])


def _own(row, column, value):
    rows = _differences()["own_stream_by_layer"]
    rows[row][column] = value
    return rows


@pytest.mark.parametrize("fault,kw,check", [
    ("the latent attention branch off",
     {"mla_out_rel": 3 * TOL["mla_out_rel"]},
     "attention_branch_matches_reference"),
    ("the dense branch off", {"dense_out_rel": 3 * TOL["dense_out_rel"]},
     "dense_branch_matches_reference"),
    ("the experts' branch off", {"ffn_out_rel": 3 * TOL["ffn_out_rel"]},
     "expert_branch_matches_reference"),
    ("a gradient reaches the bias", {"bias_grad_abs": 1e-9},
     "selection_bias_takes_no_gradient"),
    ("one leaf off", {"grad_leaf_rel": dict(
        {k: 0.0 for k in TOL["grad_leaf_rel"]},
        **{"attn.kv_b": 2 * TOL["grad_leaf_rel"]["attn.kv_b"]})},
     "gradients_match_reference_leaf_by_leaf"),
    ("a leaf missing", {"grad_leaf_rel": {
        k: 0.0 for k in TOL["grad_leaf_rel"] if k != "attn.kv_a_norm"}},
     "gradients_match_reference_leaf_by_leaf"),
    ("the first attention on its own stream", {"own_stream_by_layer": _own(
        0, 1, 2 * TOL["own_stream_first_layer"]["mixer_rel"])},
     "first_layer_matches_reference_on_its_own_stream"),
    ("the first router on its own stream", {"own_stream_by_layer": _own(
        1, 3, 2 * TOL["own_stream_first_layer"]["routing_share"])},
     "first_layer_matches_reference_on_its_own_stream"),
    ("a branch lost from the stream", {"stream_add_rel": 0.5},
     "residual_stream_adds_up"),
    ("the routing off", {"routing_differs": int(
        2 * TOL["routing_differs_share"] * 5 * S * 6)},
     "routing_matches_reference"),
], ids=lambda v: v if isinstance(v, str) and " " in v else "")
def test_a_wrong_step_fails(monkeypatch, fault, kw, check):
    monkeypatch.setitem(deepseek_v3._LIVE, "engine", None)
    checks = _passes(**kw)
    assert not checks[check], fault
    assert [k for k, v in checks.items() if not v] == [check]


def test_a_wrong_loss_or_norm_fails(monkeypatch):
    monkeypatch.setitem(deepseek_v3._LIVE, "engine", None)
    assert not _passes(loss=LOSS + 2 * TOL["loss_abs"])[
        "first_loss_matches_reference"]
    assert not _passes(norm=NORM * (1 + 2 * TOL["grad_norm_rel"]))[
        "first_grad_norm_matches_reference"]


# ------------------------------------------- the readers, on a hand-made run

JIT = "jit(train_batch_fn)/ds_fwd_bwd/"
FWD = JIT + "jvp(DeepseekV3ForCausalLM)/layer_1/checkpoint"
REC = JIT + "transpose(jvp(DeepseekV3ForCausalLM))/layer_1/checkpoint" \
    "/rematted_computation"
BWD = JIT + "transpose(jvp(DeepseekV3ForCausalLM))/layer_1/checkpoint"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, the path it was traced under, ns in a step of 1 s)
OPS = [
    ("%fusion.1 = bf16[16384,6144] fusion(%a)", FWD + "/mla_attn/q_proj/dot",
     30e6),
    ("%fusion.2 = bf16[16384,576] fusion(%a)",
     FWD + "/mla_attn/mla_latent/kv_a_proj/dot", 3e6),
    ("%fusion.3 = bf16[16384,512] fusion(%a)",
     FWD + "/mla_attn/mla_latent/kv_a_norm/mul", 1e6),
    ("%fusion.4 = bf16[16384,8192] fusion(%a)",
     FWD + "/mla_attn/mla_expand/kv_b_proj/dot", 8e6),
    ("%fusion.5 = bf16[1,16384,32,64] fusion(%a)",
     FWD + "/mla_attn/mla_rope/mul", 5e6),
    ("%fusion.6 = bf16[1,32,16384,192] fusion(%a)",
     REC + "/mla_attn/mla_expand/concatenate", 6e6),
    ("%flash.7 = f32[32,16384,128] custom-call(%a)" + PALLAS,
     FWD + "/mla_attn/flash_fwd_chunk/pallas_call", 45e6),
    ("%flash.8 = f32[32,16384,192] custom-call(%a)" + PALLAS,
     BWD + "/mla_attn/flash_bwd_dq/pallas_call", 55e6),
    ("%flash.9 = f32[32,16384,192] custom-call(%a)" + PALLAS,
     BWD + "/mla_attn/flash_bwd_dkv/pallas_call", 65e6),
    ("%fusion.10 = bf16[16384,2048] fusion(%a)",
     BWD + "/mla_attn/o_proj/dot", 20e6),
    ("%fusion.11 = f32[16384,128] fusion(%a)", FWD + "/mlp/moe_router/dot",
     3e6),
    ("%gmm.12 = bf16[12288,768] custom-call(%a)" + PALLAS,
     FWD + "/mlp/moe_gmm/pallas_call", 10e6),
    ("%fusion.13 = bf16[16384,6144] fusion(%a)",
     FWD.replace("layer_1", "layer_0") + "/mlp/dense_mlp/gate_proj/dot",
     749e6),
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
    monkeypatch.setitem(deepseek_v3._LIVE, "gauges", {})
    record = _record(deepseek_v3)
    chip = sr.busiest_chip(record)
    assert chip["busy_ms"] == pytest.approx(1000.0)
    # round the kernels: latent 3 + 1, expansion 8 + 6, rotation 5
    assert mla_expand_ms.read(record) == pytest.approx(23.0)
    # the module: that, the kernels 45 + 55 + 65, q_proj 30, o_proj 20
    assert mla_layer_ms.read(record) == pytest.approx(23 + 165 + 50)
    rows = {}
    for p, t, _, ms in chip["rows"]:
        rows[p, t] = rows.get((p, t), 0.0) + ms
    assert rows[("forward", "mla_latent")] == pytest.approx(4.0)
    assert rows[("recompute", "mla_expand")] == pytest.approx(6.0)
    assert rows[("forward", "mla_attn")] == pytest.approx(30.0)
    assert rows[("forward", "dense_mlp")] == pytest.approx(749.0)
    assert chip["kernel_ms"]["flash_fwd"] == pytest.approx(45.0)
    assert chip["kernel_ms"]["flash_bwd"] == pytest.approx(120.0)
    needed = deepseek_v3.train_attention_flops_per_step(CONFIG, 1, S)
    assert flash_fwd_roofline.read(record) == pytest.approx(
        100 * needed / 3 / 197e12 / 0.045)
    assert flash_bwd_roofline.read(record) == pytest.approx(
        100 * needed * 2 / 3 / 197e12 / 0.120)
    assert moe_router_ms.read(record) == pytest.approx(3.0)
    assert moe_dispatch_ms.read(record) == pytest.approx(3.0)
    assert moe_gmm_roofline.read(record) == pytest.approx(
        100 * deepseek_v3.moe_gmm_flops_per_step(CONFIG, S) / 197e12 / 0.010)


@pytest.mark.parametrize("family", [gpt2, olmoe, qwen3_next, laguna,
                                    smallthinker, nemotron_h],
                         ids=["gpt2", "olmoe", "qwen3_next", "laguna",
                              "smallthinker", "nemotron_h"])
def test_a_program_without_the_scopes_reads_nothing(family):
    """The new readers on the other families' programs (the parent's, too:
    it has no family with these tags) and on a run without a trace: None,
    and nothing raised."""
    record = _record(family)
    for reader in (mla_layer_ms, mla_expand_ms):
        assert reader.read(record) is None, reader.NAME
    untraced = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                              family=deepseek_v3, rehearse=False, peaks=None)
    untraced.extra.update(tokens_per_step=S, global_batch=1, seq_len=S)
    for reader in (mla_layer_ms, mla_expand_ms):
        assert reader.read(untraced) is None, reader.NAME


def test_the_gauges_are_read_through_the_family(monkeypatch):
    from benchmark.layer_metrics import (moe_rows_held_share,
                                         moe_rows_max_over_mean)
    record = _record(deepseek_v3)
    monkeypatch.setitem(deepseek_v3._LIVE, "gauges", {})
    assert moe_rows_held_share.read(record) is None
    monkeypatch.setitem(deepseek_v3._LIVE, "gauges", {
        "moe/rows_held_share": 0.1263, "moe/rows_max_over_mean": 1.7})
    assert moe_rows_held_share.read(record) == pytest.approx(12.63)
    assert moe_rows_max_over_mean.read(record) == pytest.approx(1.7)


def test_set_up_levels_the_routers_loads():
    """``balanced_selection_bias`` at the rehearsal's widths: the worst
    expert's rows over the mean fall in every expert layer — from EVERY
    token on the same experts (4.0 = 8 experts / top-2) to under 2.5, no
    further: at these widths the stream under a router is a batch's running
    mean more than its tokens (PERF.md Findings PR 47) — and only the
    selection biases move; the engine ``build_train`` returns holds the
    moved tree."""
    import copy
    import jax
    import numpy as np
    config = copy.deepcopy(CONFIG)
    config["rehearse_cpu"]["train"]["selection_bias_balance"] = {
        "seq_len": 512, "rounds": 24}
    seed = 4000000123
    drawn = jax.jit(lambda key: deepseek_v3._model(config, True).init(
        key, np.zeros((1, 64), np.int32))["params"])(jax.random.PRNGKey(seed))
    moved, found = deepseek_v3.balanced_selection_bias(config, drawn, 1, seed,
                                                       True)
    worst = found["rows_max_over_mean"]
    assert len(worst["first_round"]) == 5
    assert len(worst["worst_layer_by_round"]) == 24
    assert all(last < first for last, first in zip(worst["last_round"],
                                                   worst["first_round"]))
    assert max(worst["last_round"]) < 2.5 < max(worst["first_round"])
    for (path, before), after in zip(
            jax.tree_util.tree_leaves_with_path(drawn),
            jax.tree_util.tree_leaves(moved)):
        bias = path[-1].key == "e_score_correction_bias"
        assert np.array_equal(before, after) != bias, path

    engine, params = deepseek_v3.build_train(config, 1, seed,
                                             jax.devices()[:1], True)
    assert deepseek_v3._LIVE["balance"]["rows_max_over_mean"]
    for mine, theirs in zip(jax.tree_util.tree_leaves(engine.state.params),
                            jax.tree_util.tree_leaves(params)):
        assert mine is theirs
