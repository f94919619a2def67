"""The Olmo-Hybrid-7B cell (ISSUE 68): the manifest's entries found by NAME,
the catalog's numbers, the parameter arithmetic, the family's contract and
its counts of operations and bytes by hand and by brute force, the
comparison that decides ``correct`` on hand-made readings, the four new
readers (``gdn_lane_overcompute``, ``gdn_elementwise_ms``,
``gdn_elementwise_roofline``, ``gdn_xla_sites``) and the scan's readers on a
hand-made scope table and on programs that lack the scopes and gauges, and
the cell's CPU rehearsal."""

import json
import os

import pytest

from benchmark import families, harness, manifest, run, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import granite_hybrid, olmo_hybrid, qwen3_next
from benchmark.layer_metrics import (dense_mlp_ms, gdn_elementwise_ms,
                                     gdn_elementwise_roofline,
                                     gdn_lane_overcompute, gdn_layer_ms,
                                     gdn_scan_roofline, gdn_scan_share,
                                     gdn_xla_sites)

CELL = "olmohybrid-train-1chip-s8192"
NAME = "olmo-hybrid-7b-vp8-depth4"
SOURCE = ("https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main"
          "/config.json")
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
TRAFFIC = manifest.traffic_of({"name": CELL})

S = 8192
H = 3840
KEY, VAL = 30 * 96, 30 * 192
DELTANET = H * (2 * KEY + 2 * VAL + 60) + VAL * H   # the three projections
ATTENTION = 4 * H * H
MLP = 3 * H * 11008
HEAD = 12544 * H
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
NEW = {"gdn_lane_overcompute": (gdn_lane_overcompute, "ratio", "lower",
                                "program_counter"),
       "gdn_elementwise_ms": (gdn_elementwise_ms, "ms", "lower",
                              "device_trace"),
       "gdn_elementwise_roofline": (gdn_elementwise_roofline, "%", "higher",
                                    "device_trace"),
       "gdn_xla_sites": (gdn_xla_sites, "count", "lower", "program_counter")}


def test_the_cell_is_the_one_issue_68_names():
    """Entries by name: a later PR appends and this stays true."""
    assert manifest.problems(BENCH) == []
    cell = manifest.cell_of(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain-b1x8192", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED) \
        == sorted(CONFIG["reduced"])
    names = {m["name"] for m in manifest.metrics_for(BENCH, cell, "per_layer")}
    assert names >= {
        *NEW, "gdn_scan_share", "gdn_scan_roofline", "gdn_layer_ms",
        "dense_mlp_ms", "loss_head_ms", "flash_attn_share",
        "flash_attn_roofline", "flash_fwd_roofline", "flash_bwd_roofline",
        "train_mfu", "train_step_ms", "train_fwd_ms", "train_bwd_ms",
        "train_recompute_ms", "train_optimizer_ms", "train_peak_hbm_gb",
        "train_program_hbm_gb", "train_unscoped_share",
        "train_device_idle_share", "train_compiles_in_window",
        "setup_engine_init_s", "setup_first_step_s",
        "setup_outside_program_s", "setup_compile_s",
        "setup_programs_compiled", "setup_cache_misses"}
    assert not [n for n in names if n.startswith((
        "swa_", "ssd_", "ssm_", "moe_", "mla_", "dsa_", "bd_", "mhc_",
        "collective"))]
    e2e = {m["name"] for m in manifest.metrics_for(BENCH, cell, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    for name, (module, unit, better, source) in NEW.items():
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"]
        assert (m["name"], m["unit"], m["layer"], m["moves"], m["source"],
                m["better"]) == (
            module.NAME, module.UNIT, module.LAYER, module.MOVES,
            module.SOURCE, better) == (
            name, unit, "linear attention", "train_tokens_per_s", source,
            better)


def test_the_traffic_is_the_one_chip_cells_at_one_sequence_of_8192():
    other = manifest.traffic_of({"name": "granite4hmicro-train-1chip-s16384"})
    same = ("kind", "global_batch", "batch_pool", "warmup_steps",
            "fence_lag_steps", "trace_steps", "chips", "token_below")
    assert {k: TRAFFIC[k] for k in same} == {k: other[k] for k in same}
    assert (TRAFFIC["kind"], TRAFFIC["global_batch"], TRAFFIC["seq_len"],
            TRAFFIC["token_below"], TRAFFIC["traffic"]) == (
        "train_steps", 1, S, 12544, "pretrain-b1x8192")
    for key in ("users", "why_in_full"):
        assert TRAFFIC[key], key
    for said in ("16,384 does not fit", "55 %", "96 x 192", "128 x 256",
                 "beta in (0, 2)", "NOTHING sees more than its share",
                 "packed documents", "gdn_lane_overcompute", "batch 1"):
        assert said in TRAFFIC["why_in_full"], said
    assert TRAFFIC["why"] == manifest.cell_of(BENCH, CELL)["why"]
    for said in ("1x8192", "ONE whole period", "30x96x192", "beta<2",
                 "NoPE MHA 30x128", "SwiGLU 11,008", "1/8 vocabulary",
                 "13.0 GB", "nothing over its share"):
        assert said in TRAFFIC["why"], said


def test_the_family_keeps_the_contract():
    f = olmo_hybrid
    for member in families.TRAINING + families.TAGS:
        assert hasattr(f, member), member
    assert not [m for m in families.SERVING if hasattr(f, m)]
    assert f.KERNEL_TAGS == ("flash_fwd", "flash_bwd", "gdn_scan")
    assert set(f.GDN_LAYER_TAGS) == set(qwen3_next.GDN_LAYER_TAGS)
    assert set(f.GDN_ELEMENTWISE_TAGS) < set(f.GDN_LAYER_TAGS)
    tags = f.MODULE_TAGS
    # a path under ``linear_attn`` is tagged by its own scope first
    assert max(tags.index(t) for t in (
        "gdn_conv", "gdn_gates", "gdn_out_norm")) < tags.index("linear_attn")
    assert tags.index("qk_norm") < tags.index("attn")
    assert f.MLP_TAG == "mlp" in tags
    assert f.CONTROLS == f.ref.CONTROLS and len(f.CONTROLS) >= 6
    assert f.traffic_shapes(CONFIG, False) == {
        "vocab_size": 12544, "max_positions": 65536, "seq_scale": 1.0}
    assert f.traffic_shapes(CONFIG, True)["seq_scale"] == 1 / 128
    # the rehearsal keeps the published heads: Dv = 2 Dk off the lane grid
    assert "linear_key_head_dim" not in CONFIG["rehearse_cpu"]
    # no other family's name
    with open(f.__file__) as src:
        text = src.read()
    assert "from benchmark.families import common\n" in text
    assert not [other for other in ("nemotron_h", "olmoe", "laguna",
                                    "qwen3_next", "smallthinker", "gpt2",
                                    "deepseek_v3", "granite_hybrid")
                if f"import {other}" in text
                or f"families.{other}" in text.replace(
                    "``families/", "").replace("``", "")]
    # the reference imports nothing of the program
    with open(f.ref.__file__) as src:
        ref_text = src.read()
    assert "deepspeed_tpu" not in ref_text.replace("``deepspeed_tpu/``", "")
    assert "pallas" not in ref_text and "lax.scan" in ref_text


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key; depth with its list and the vocabulary differ, and are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert CONFIG["source"] == row["source_url"] == SOURCE
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    published = CONFIG["published"]
    for key in olmo_hybrid.WIDTH_KEYS:
        assert CONFIG[key] == published[key] == row["config"][key], key
    for key in REDUCED:
        assert published[key] == row["config"][key], key
    assert not [k for k in REDUCED if k in olmo_hybrid.WIDTH_KEYS
                or k.endswith(("_dim", "_rank"))]
    assert CONFIG["layer_types"] == published["layer_types"][:4] \
        == ["linear_attention"] * 3 + ["full_attention"]
    assert published["layer_types"] == CONFIG["layer_types"] * 8
    assert CONFIG["vocab_size"] * 8 == 100352 == published["vocab_size"]
    assert CONFIG["rope_parameters"] == {"rope_theta": None}
    assert CONFIG["linear_allow_neg_eigval"] is True
    assert set(CONFIG["changed_why"]) == set(REDUCED)
    assert {"a_reordered_norms", "b_qk_norm", "c_no_rotation", "d_deltanet",
            "e_deltanet_init", "f_initializer_range", "g_mlp", "h_head"} \
        <= set(CONFIG["assumed"])
    assert "modeling_olmo3.py" in CONFIG["assumed"]["a_reordered_norms"]
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert "Nothing stands in for the absent chips" in CONFIG["deployment"]
    assert CONFIG["model"]["remat"] and CONFIG["rehearse_cpu"]
    # the engine block of the other one-chip share cells, copied
    nemotron = manifest.config_of(BENCH, manifest.cell_of(
        BENCH, "nemotron3nano-train-1chip-s16384"))
    assert CONFIG["train"]["engine"] == nemotron["train"]["engine"]
    assert {k: CONFIG["model"][k] for k in ("dtype", "param_dtype", "remat",
                                            "remat_policy", "loss_chunk")} \
        == {k: nemotron["model"][k] for k in (
            "dtype", "param_dtype", "remat", "remat_policy", "loss_chunk")}


def test_the_parameter_arithmetic_is_the_initialised_trees():
    """``changed_why``'s numbers against ``jax.eval_shape`` of the model the
    configuration builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = olmo_hybrid._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    layers = shapes["layers"]
    assert count(shapes) == model.config.num_params() == 928_862_196
    assert count(layers["l0"]) == 215_570_172
    assert count(layers["l0"]["linear_attn"]) == 88_750_332
    assert count(layers["l3"]) == 185_809_920
    assert count(layers["l3"]["attn"]) == 58_990_080 == ATTENTION + 2 * H
    assert count(layers["l3"]["mlp"]) == 126_812_160 == MLP
    assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) == HEAD
    why = " ".join(CONFIG["changed_why"].values())
    for number in ("215,570,172", "88,750,332", "185,809,920", "58,990,080",
                   "126,812,160", "832,520,436", "96,337,920", "928,862,196",
                   "9.29 GB", "13.00 GB"):
        assert number in why, number
    assert 928_862_196 * 14 / 1e9 == pytest.approx(13.00, abs=0.005)
    assert 928_862_196 * 10 / 1e9 == pytest.approx(9.29, abs=0.005)
    assert "7,430,870,688" in CONFIG["published"]["parameters"]
    assert 24 * 215_570_172 + 8 * 185_809_920 + 2 * 100352 * H + H \
        == 7_430_870_688


def test_flops_and_bytes_count_what_this_chip_needs():
    f = olmo_hybrid
    assert f.active_matmul_params(CONFIG) \
        == HEAD + 3 * DELTANET + ATTENTION + 4 * MLP
    # the issue's shares of the needed forward flops a token at 8,192
    rule = 3 * 6 * 96 * 192 * 30
    attention = 2 * ATTENTION + 2 * S * H      # projections + causal scores
    total = 2 * (HEAD + 3 * DELTANET + 4 * MLP) + attention + rule
    assert 2 * 4 * MLP / total == pytest.approx(0.55, abs=0.01)
    assert (2 * 3 * DELTANET + rule) / total == pytest.approx(0.30, abs=0.015)
    assert attention / total == pytest.approx(0.10, abs=0.005)
    assert 2 * HEAD / total == pytest.approx(0.05, abs=0.005)
    scan = 6 * 96 * 192 * 30                   # a token a layer, forward
    assert f.train_flops_per_token(CONFIG, S) == \
        6 * f.active_matmul_params(CONFIG) + 6 * S * H + 3 * 3 * scan
    assert f.train_attention_flops_per_step(CONFIG, 1, S) == \
        6 * 30 * S * S * 128
    flops, nbytes = f.gdn_scan_flops_and_bytes(CONFIG, S)
    assert flops == 3 * S * 3 * scan
    assert flops / 1e12 == pytest.approx(0.245, abs=0.001)   # the issue's
    # q, k 5,760 B each, v, o 11,520 B each, g and beta 120 B each a token
    assert nbytes == 3 * S * (3 * (2 * 5760 + 11520 + 240) + 2 * 11520)
    assert nbytes / 1e9 == pytest.approx(2.29, abs=0.01)
    assert nbytes / 819e9 > flops / 197e12          # the bytes bind
    # Qwen3-Next's own count at these keys agrees (its reader of the period
    # is another: ``full_attention_interval``)
    theirs = qwen3_next.gdn_scan_flops_and_bytes(
        dict(CONFIG, full_attention_interval=4, **{k: 1 for k in (
            "partial_rotary_factor", "rope_theta", "num_experts", "head_dim",
            "expert_parallel_size", "expert_parallel_rank",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "norm_topk_prob",
            "router_aux_loss_coef")}), S)
    assert theirs == (flops, nbytes)
    # the elementwise stages: 5 arrays of q | k | v and 8 of o's width
    assert f.gdn_elementwise_bytes_per_step(CONFIG, S) \
        == 3 * S * 2 * (5 * (2 * KEY + VAL) + 8 * VAL)


def test_the_rule_counts_what_a_brute_force_count_counts():
    """The recurrence as written, counted operation by operation at a small
    size: 6 Dk Dv flops a token a head."""
    dk, dv, heads, tokens = 3, 5, 2, 7
    flops = 0
    for _ in range(tokens * heads):
        flops += 2 * dk * dv            # read S^T k
        flops += 2 * dk * dv            # S += k (beta (v - read))^T
        flops += 2 * dk * dv            # o = S^T q
    small = dict(CONFIG, linear_key_head_dim=dk, linear_value_head_dim=dv,
                 linear_num_key_heads=heads, linear_num_value_heads=heads,
                 layer_types=["linear_attention"], num_hidden_layers=1)
    got, nbytes = olmo_hybrid.gdn_scan_flops_and_bytes(small, tokens)
    assert got == 3 * flops
    per_token = 2 * (2 * heads * dk + heads * dv) + 8 * heads
    assert nbytes == tokens * (3 * per_token + 2 * 2 * heads * dv)


# --------------------------------------------- the comparison, by hand

TOL = CONFIG["train"]["tolerance"]
LOSS, NORM = 10.2, 12.7


def _differences(**over):
    """An honest step's readings (each a third of its limit), or with
    ``over``."""
    out = dict(
        own_stream_by_layer=[[TOL["own_stream_first_rel"] / 3, 0.01]] * 4,
        stream_add_rel=TOL["stream_add_rel"] / 3,
        stream_start_rel=TOL["stream_start_rel"] / 3,
        system_grad_norm=NORM, gdn_out_rel=TOL["gdn_out_rel"] / 3,
        attn_out_rel=TOL["attn_out_rel"] / 3,
        mlp_out_rel=TOL["mlp_out_rel"] / 3,
        grad_leaf_rel={k: v / 3 for k, v in TOL["grad_leaf_rel"].items()})
    out.update(over)
    return out


def _passes(loss=LOSS, norm=NORM, **over):
    checks, _ = olmo_hybrid.judge_train(CONFIG, loss, norm, LOSS, NORM,
                                        _differences(**over))
    return checks


def test_an_honest_step_passes_with_room(monkeypatch):
    monkeypatch.setitem(olmo_hybrid._LIVE, "engine", None)
    checks = _passes()
    assert all(checks.values()), checks
    assert set(checks) == {
        "first_loss_matches_reference", "first_grad_norm_matches_reference",
        "deltanet_branch_matches_reference",
        "attention_branch_matches_reference", "mlp_branch_matches_reference",
        "compared_gradients_are_the_steps",
        "gradients_match_reference_leaf_by_leaf",
        "first_mixer_matches_reference_on_its_own_stream",
        "stream_starts_from_the_embedding", "residual_stream_adds_up"}
    assert set(TOL["grad_leaf_rel"]) == {
        olmo_hybrid.leaf_name(kind, leaf)
        for kind, leaves in olmo_hybrid.LAYER_LEAVES.items()
        for leaf in leaves} | {"embed", "norm", "lm_head"}
    assert len(TOL["why"]) > 1000


@pytest.mark.parametrize("fault,kw,check", [
    ("the DeltaNet branch off", {"gdn_out_rel": 3 * TOL["gdn_out_rel"]},
     "deltanet_branch_matches_reference"),
    ("the attention branch off", {"attn_out_rel": 3 * TOL["attn_out_rel"]},
     "attention_branch_matches_reference"),
    ("the MLP off", {"mlp_out_rel": 3 * TOL["mlp_out_rel"]},
     "mlp_branch_matches_reference"),
    ("one leaf off", {"grad_leaf_rel": dict(
        {k: 0.0 for k in TOL["grad_leaf_rel"]},
        **{"gdn.A_log": 2 * TOL["grad_leaf_rel"]["gdn.A_log"]})},
     "gradients_match_reference_leaf_by_leaf"),
    ("a leaf missing", {"grad_leaf_rel": {
        k: 0.0 for k in TOL["grad_leaf_rel"] if k != "gdn.in_ba"}},
     "gradients_match_reference_leaf_by_leaf"),
    ("the first mixer on its own stream", {"own_stream_by_layer": [
        [2 * TOL["own_stream_first_rel"], 0.0]] + [[0.0, 0.0]] * 3},
     "first_mixer_matches_reference_on_its_own_stream"),
    ("another stream's start", {"stream_start_rel": 0.5},
     "stream_starts_from_the_embedding"),
    ("a branch lost", {"stream_add_rel": 0.5}, "residual_stream_adds_up"),
    ("other gradients than the step's", {"system_grad_norm": 1.1 * NORM},
     "compared_gradients_are_the_steps"),
], ids=lambda v: v if isinstance(v, str) and " " in v else "")
def test_a_wrong_step_fails(monkeypatch, fault, kw, check):
    monkeypatch.setitem(olmo_hybrid._LIVE, "engine", None)
    checks = _passes(**kw)
    assert not checks[check], fault
    assert [k for k, v in checks.items() if not v] == [check]


def test_a_wrong_loss_or_norm_fails(monkeypatch):
    monkeypatch.setitem(olmo_hybrid._LIVE, "engine", None)
    assert TOL["loss_abs"] <= 0.002 and TOL["grad_norm_rel"] <= 0.005
    assert not _passes(loss=LOSS + 2 * TOL["loss_abs"])[
        "first_loss_matches_reference"]
    assert not _passes(norm=NORM * (1 + 2 * TOL["grad_norm_rel"]))[
        "first_grad_norm_matches_reference"]


# ------------------------------------------- the readers, on a hand-made run

JIT = "jit(train_batch_fn)/ds_fwd_bwd/"
FWD = JIT + "jvp(OlmoHybridForCausalLM)/layers/l0/checkpoint"
REC = JIT + "transpose(jvp(OlmoHybridForCausalLM))/layers/l0/checkpoint" \
    "/rematted_computation"
BWD = JIT + "transpose(jvp(OlmoHybridForCausalLM))/layers/l0/checkpoint"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, the path it was traced under, ns in a step of 1 s)
OPS = [
    ("%fusion.1 = bf16[8192,17280] fusion(%a)",
     FWD + "/linear_attn/in_proj_qkvz/dot_general", 30e6),
    ("%fusion.2 = bf16[1,8192,23040] fusion(%a)",
     FWD + "/linear_attn/gdn_conv/concatenate", 5e6),
    ("%conv.3 = bf16[1,8192,3840] custom-call(%a)" + PALLAS,
     FWD + "/linear_attn/gdn_conv/mixer_conv_fwd/pallas_call", 4e6),
    ("%conv.4 = bf16[1,8192,3840] custom-call(%a)" + PALLAS,
     BWD + "/linear_attn/gdn_conv/mixer_conv_bwd/pallas_call", 7e6),
    ("%fusion.5 = f32[1,15,128,2,64] fusion(%a)",
     FWD + "/linear_attn/gdn_scan_prep/cumsum", 1e6),
    ("%gdn.6 = bf16[1,8192,7680] custom-call(%a)" + PALLAS,
     FWD + "/linear_attn/gdn_scan_fwd/pallas_call", 15e6),
    ("%gdn.7 = bf16[1,8192,7680] custom-call(%a)" + PALLAS,
     REC + "/linear_attn/gdn_scan_fwd/pallas_call", 15e6),
    ("%gdn.8 = bf16[1,8192,7680] custom-call(%a)" + PALLAS,
     BWD + "/linear_attn/gdn_scan_bwd/pallas_call", 12e6),
    ("%norm.9 = bf16[1,8192,7680] custom-call(%a)" + PALLAS,
     FWD + "/linear_attn/gdn_out_norm/mixer_norm_fwd/pallas_call", 2e6),
    ("%fusion.10 = bf16[1,8192,5760] fusion(%a)",
     FWD + "/linear_attn/gdn_out_norm/slice", 2e6),
    ("%norm.11 = bf16[1,8192,7680] custom-call(%a)" + PALLAS,
     BWD + "/linear_attn/gdn_out_norm/mixer_norm_bwd/pallas_call", 3e6),
    ("%fusion.12 = f32[8192,30] fusion(%a)",
     FWD + "/linear_attn/gdn_gates/mul", 1e6),
    ("%fusion.13 = bf16[8192,11008] fusion(%a)",
     FWD + "/mlp/gate_proj/dot_general", 100e6),
    ("%fusion.14 = bf16[8192,11008] fusion(%a)", REC + "/mlp/mul", 50e6),
    ("%fusion.15 = bf16[8192,3840] fusion(%a)",
     BWD + "/mlp/down_proj/dot_general", 250e6),
    ("%fusion.16 = bf16[8192,3840] fusion(%a)",
     FWD.replace("l0", "l3") + "/attn/o_proj/dot_general", 503e6),
]


def _record(family, gauges=None):
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
    record = _record(olmo_hybrid)
    chip = sr.busiest_chip(record)
    assert chip["busy_ms"] == pytest.approx(1000.0)
    # the SwiGLU: forward, recomputation and backward
    assert dense_mlp_ms.read(record) == pytest.approx(400.0)
    # the elementwise stages: the kernels AND the re-layout round them
    assert gdn_elementwise_ms.read(record) == pytest.approx(5 + 4 + 7 + 2
                                                            + 2 + 3)
    nbytes = olmo_hybrid.gdn_elementwise_bytes_per_step(CONFIG, S)
    assert gdn_elementwise_roofline.read(record) == pytest.approx(
        100 * nbytes / 819e9 / 0.023)
    assert 0 < gdn_elementwise_roofline.read(record) < 100
    # the rule's readers, as on the Qwen3-Next cell, from the published heads
    assert gdn_scan_share.scan_ms(record) == pytest.approx(43.0)
    assert gdn_scan_share.read(record) == pytest.approx(4.3)
    _, scan_bytes = olmo_hybrid.gdn_scan_flops_and_bytes(CONFIG, S)
    assert gdn_scan_roofline.read(record) == pytest.approx(
        100 * scan_bytes / 819e9 / 0.043)
    assert 0 < gdn_scan_roofline.read(record) < 100
    assert gdn_layer_ms.read(record) == pytest.approx(30 + 23 + 43 + 1)
    # the gauges, as ``judge_train`` folded them
    monkeypatch.setitem(olmo_hybrid._LIVE, "gauges", {
        "linear_attn/gdn_lane_overcompute": 33280 / 18816,
        "linear_attn/gdn_kernel_heads_per_step": 2.0,
        "mixer/conv_xla_sites": 0.0, "mixer/norm_xla_sites": 0.0})
    assert gdn_lane_overcompute.read(record) == pytest.approx(1.7687, abs=1e-4)
    assert gdn_xla_sites.read(record) == 0
    monkeypatch.setitem(olmo_hybrid._LIVE, "gauges", {
        "linear_attn/gdn_kernel_heads_per_step": 0.0,
        "mixer/conv_xla_sites": 3.0, "mixer/norm_xla_sites": 6.0})
    assert gdn_xla_sites.read(record) == 10
    assert gdn_lane_overcompute.read(record) is None


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(
        monkeypatch):
    """On the parent's program (no DeltaNet tags, no gauges) and on a family
    that has neither the code to read a new metric returns None and does
    not raise: the line leaves the metric out."""
    monkeypatch.setitem(olmo_hybrid._LIVE, "gauges", {})
    for family in (granite_hybrid, olmo_hybrid):
        record = _record(family)
        if family is olmo_hybrid:
            record.compiled_text = record.compiled_text.replace(
                "gdn_", "other_").replace("linear_attn", "mixer")
            record._scope = None
        for reader in (gdn_lane_overcompute, gdn_xla_sites):
            assert reader.read(record) is None, (family, reader)
    record = _record(granite_hybrid)
    assert gdn_elementwise_ms.read(record) is None
    assert gdn_elementwise_roofline.read(record) is None
    empty = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                           family=olmo_hybrid, rehearse=True, peaks=None)
    empty.extra.update(tokens_per_step=S, global_batch=1, seq_len=S)
    for reader, *_ in NEW.values():
        assert reader.read(empty) is None


def test_the_cells_rehearsal_runs_and_its_checks_pass(capsys, monkeypatch):
    """``--rehearse-cpu`` of the cell, traced: the whole flow at the file's
    tiny sizes with the PUBLISHED heads of 96 x 192 through the engine
    (every stage in its kernel, in the interpreter, on whole tiles); the
    line is well formed, holds no metric value and is never ``correct``. The
    limits are the chip's, so the flow runs here with the rehearsal's dtypes
    set to float32, where every check against the reference must pass."""
    import copy
    config = copy.deepcopy(CONFIG)
    config["rehearse_cpu"]["model"]["dtype"] = "float32"
    engine = config["rehearse_cpu"]["train"]["engine"]
    engine["bf16"] = {"enabled": False}
    engine["data_types"] = {"grad_dtype": "fp32"}
    theirs = manifest.config_of
    monkeypatch.setattr(manifest, "config_of", lambda bench, cell: config
                        if cell["name"] == CELL else theirs(bench, cell))
    from deepspeed_tpu.telemetry.registry import default_registry
    # the site gauges count a process's traces: other tests' are in them
    fell = [default_registry().peek_gauge(f"mixer/{stage}_xla_sites") or 0
            for stage in ("conv", "norm")]
    rc = run.main(["--workload", CELL, "--seed", "4123456789", "--seconds",
                   "1", "--trace", "1", "--rehearse-cpu"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert line["metrics"] == {} and line["correct"] is False
    assert line["rehearsal"] is True and line["rehearsal_checks_passed"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"train_compiles_in_window", "setup_compile_s",
            "gdn_lane_overcompute", "gdn_xla_sites"} \
        <= set(line["rehearsal_metric_names"])
    assert not [n for n in line["rehearsal_metric_names"]
                if "roofline" in n or "mfu" in n or n.endswith("_ms")]
    gauges = olmo_hybrid.program_gauges()
    assert gauges["linear_attn/gdn_lane_overcompute"] > 1.7
    assert [gauges["mixer/conv_xla_sites"],
            gauges["mixer/norm_xla_sites"]] == fell
