"""The granite-4.0-h-micro cell (ISSUE 50): the manifest's entries found by
NAME, the catalog's numbers, the parameter arithmetic, the family's contract
and its counts of operations and bytes by hand, the comparison that decides
``correct`` on hand-made readings, the two new readers (``dense_mlp_ms``,
``ssm_norm_roofline``) and the scan's readers on a hand-made scope table and
on programs that lack the scopes, and the cell's CPU rehearsal."""

import json
import os

import pytest

from benchmark import families, harness, manifest, run, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import (deepseek_v3, gpt2, granite_hybrid, laguna,
                                nemotron_h, olmoe, qwen3_next, smallthinker)
from benchmark.layer_metrics import (dense_mlp_ms, ssd_scan_roofline,
                                     ssd_scan_share, ssm_layer_ms,
                                     ssm_norm_roofline)

CELL = "granite4hmicro-train-1chip-s16384"
NAME = "granite-4-h-micro-3b-vp8-depth10"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main"
          "/config.json")
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
TRAFFIC = manifest.traffic_of({"name": CELL})

S = 16384
H = 2048
MAMBA = H * 8512 + 4096 * H               # the two projections
ATTENTION = 2 * H * H + 2 * H * 512
MLP = 3 * H * 8192
HEAD = 12544 * H
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
NEW = ("dense_mlp_ms", "ssm_norm_roofline")


def test_the_cell_is_the_one_issue_50_names():
    """Entries by name: a later PR appends and this stays true."""
    assert manifest.problems(BENCH) == []
    cell = manifest.cell_of(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain-b1x16384", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED) \
        == sorted(CONFIG["reduced"])
    names = {m["name"] for m in manifest.metrics_for(BENCH, cell, "per_layer")}
    assert names >= {
        *NEW, "ssd_scan_share", "ssd_scan_roofline", "ssm_layer_ms",
        "flash_attn_share", "flash_attn_roofline", "flash_fwd_roofline",
        "flash_bwd_roofline", "train_mfu", "train_step_ms", "train_fwd_ms",
        "train_bwd_ms", "train_recompute_ms", "train_optimizer_ms",
        "train_peak_hbm_gb", "train_program_hbm_gb", "train_unscoped_share",
        "train_device_idle_share", "train_compiles_in_window",
        "setup_engine_init_s", "setup_first_step_s",
        "setup_outside_program_s", "setup_compile_s",
        "setup_programs_compiled", "setup_cache_misses"}
    assert not [n for n in names if n.startswith((
        "swa_", "gdn_", "moe_", "mla_", "collective"))]
    e2e = {m["name"] for m in manifest.metrics_for(BENCH, cell, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    for name, module, unit, layer in zip(
            NEW, (dense_mlp_ms, ssm_norm_roofline), ("ms", "%"),
            ("dense hybrid block", "state-space mixer")):
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"]
        assert (m["name"], m["unit"], m["layer"], m["moves"], m["source"]) \
            == (module.NAME, module.UNIT, module.LAYER, module.MOVES,
                module.SOURCE) == (name, unit, layer, "train_tokens_per_s",
                                   "device_trace")


def test_the_traffic_is_the_nemotron_cells_with_the_slices_bound():
    other = manifest.traffic_of({"name": "nemotron3nano-train-1chip-s16384"})
    same = ("traffic", "kind", "global_batch", "seq_len", "batch_pool",
            "warmup_steps", "fence_lag_steps", "trace_steps", "chips")
    assert {k: TRAFFIC[k] for k in same} == {k: other[k] for k in same}
    assert (TRAFFIC["kind"], TRAFFIC["global_batch"], TRAFFIC["seq_len"],
            TRAFFIC["token_below"]) == ("train_steps", 1, S, 12544)
    for key in ("users", "why_in_full"):
        assert TRAFFIC[key], key
    for said in ("32k", "ONE group", "3.3 %", "6.4 %", "Ten layers",
                 "batch 1", "ssm_norm_roofline", "packed documents",
                 "sharded_init"):
        assert said in TRAFFIC["why_in_full"], said
    assert TRAFFIC["why"] == manifest.cell_of(BENCH, CELL)["why"]
    assert "ONE group" in TRAFFIC["why"] and "SwiGLU" in TRAFFIC["why"]


def test_the_family_keeps_the_contract():
    f = granite_hybrid
    for member in families.TRAINING + families.TAGS:
        assert hasattr(f, member), member
    assert not [m for m in families.SERVING if hasattr(f, m)]
    assert f.KERNEL_TAGS == ("flash_fwd", "flash_bwd", "ssd_scan")
    assert set(f.SSM_LAYER_TAGS) == set(nemotron_h.SSM_LAYER_TAGS)
    tags = f.MODULE_TAGS
    # a path under ``mamba`` is tagged by its own scope first
    assert max(tags.index(t) for t in ("ssm_conv", "ssm_gates", "ssm_norm")) \
        < tags.index("mamba")
    assert f.MLP_TAG == "shared_mlp" in tags and "attn" in tags
    assert f.SSM_NORM_TAG == "ssm_norm"
    assert f.traffic_shapes(CONFIG, False) == {
        "vocab_size": 12544, "max_positions": 131072, "seq_scale": 1.0}
    assert f.traffic_shapes(CONFIG, True)["seq_scale"] == 1 / 128
    # no other family's private name
    with open(f.__file__) as src:
        text = src.read()
    assert "from benchmark.families import common\n" in text
    assert not [other for other in ("nemotron_h", "olmoe", "laguna",
                                    "qwen3_next", "smallthinker", "gpt2",
                                    "deepseek_v3")
                if f"families.{other}" in text.replace(
                    "``families/", "").replace("``", "")
                or f"import {other}" in text]


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key; depth with its list and the vocabulary differ, and are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert CONFIG["source"] == row["source_url"] == SOURCE
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    published = CONFIG["published"]
    for key in granite_hybrid.WIDTH_KEYS:
        assert CONFIG[key] == published[key] == row["config"][key], key
    for key in REDUCED:
        assert published[key] == row["config"][key], key
    assert not [k for k in REDUCED if k in granite_hybrid.WIDTH_KEYS
                or k.endswith(("_dim", "_rank"))]
    assert CONFIG["layer_types"] == published["layer_types"][:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert published["layer_types"] == CONFIG["layer_types"] * 4
    assert CONFIG["vocab_size"] * 8 == 100352 == published["vocab_size"]
    assert set(CONFIG["changed_why"]) == set(REDUCED)
    assert {"a_initialisation", "b_mamba_init", "c_conv", "d_mamba_norm",
            "e_no_dt_clamp", "f_mamba_chunk_size", "g_attention", "h_mlp",
            "i_head"} <= set(CONFIG["assumed"])
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert "Nothing stands in for the absent chips" in CONFIG["deployment"]
    assert CONFIG["model"]["remat"] and CONFIG["rehearse_cpu"]
    # the engine block of the other one-chip cells, copied
    nemotron = manifest.config_of(BENCH, manifest.cell_of(
        BENCH, "nemotron3nano-train-1chip-s16384"))
    theirs = dict(nemotron["train"]["engine"])
    theirs.pop("scheduler")
    assert CONFIG["train"]["engine"] == theirs
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
    model = granite_hybrid._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == model.config.num_params() == 772_160_448
    assert count(shapes["layer_0"]) == 76_182_976
    assert count(shapes["layer_0"]["mamba"]) == 25_847_232
    assert count(shapes["layer_5"]) == 60_821_504
    assert count(shapes["layer_5"]["attn"]) == 10_485_760 == ATTENTION
    assert count(shapes["layer_5"]["shared_mlp"]) == 50_331_648 == MLP
    assert count(shapes["embed_tokens"]) == 25_690_112 == HEAD
    assert "lm_head" not in shapes
    why = " ".join(CONFIG["changed_why"].values())
    for number in ("76,182,976", "25,847,232", "60,821,504", "10,485,760",
                   "50,331,648", "746,468,288", "25,690,112", "772,160,448",
                   "7.72 GB", "10.81 GB"):
        assert number in why, number
    assert 772_160_448 * 14 / 1e9 == pytest.approx(10.81, abs=0.005)
    assert 772_160_448 * 10 / 1e9 == pytest.approx(7.72, abs=0.005)
    assert "3,191,396,096" in CONFIG["published"]["parameters"]
    assert 36 * 76_182_976 + 4 * 60_821_504 + 100352 * H + H \
        == 3_191_396_096


def test_flops_and_bytes_count_what_this_chip_needs():
    f = granite_hybrid
    assert f.active_matmul_params(CONFIG) \
        == HEAD + 9 * MAMBA + ATTENTION + 10 * MLP
    # the head's share of the matmul flops, here and published
    assert HEAD / f.active_matmul_params(CONFIG) == pytest.approx(
        0.033, abs=0.001)
    assert 100352 * H / (100352 * H + 36 * MAMBA + 4 * ATTENTION
                         + 40 * MLP) == pytest.approx(0.064, abs=0.001)
    scan = 5 * 64 * 128 * 64                # a token a layer, forward
    assert f.train_flops_per_token(CONFIG, S) == \
        6 * f.active_matmul_params(CONFIG) + 6 * S * 32 * 64 + 9 * 3 * scan
    assert f.train_attention_flops_per_step(CONFIG, 1, S) == \
        6 * 32 * S * S * 64
    flops, nbytes = f.ssd_scan_flops_and_bytes(CONFIG, S)
    assert flops == 9 * S * 3 * scan
    # x, y 8,192 B; B + C 512 B (ONE group); dt 256 B a token a layer
    assert nbytes == 9 * S * (3 * (8192 + 512 + 256) + 2 * 8192)
    assert nbytes / 819e9 > flops / 197e12          # the bytes bind
    # Nemotron's count at these keys: 9 layers, B and C an eighth as wide
    theirs = nemotron_h.ssd_scan_flops_and_bytes(manifest.config_of(
        BENCH, manifest.cell_of(BENCH, "nemotron3nano-train-1chip-s16384")),
        S)
    assert flops * 4 == theirs[0] * 9
    # the norm: eight arrays of 4,096 bf16 columns a token a layer
    assert f.ssm_norm_bytes_per_step(CONFIG, S) == 9 * S * 8 * 2 * 4096
    # the ten MLPs, forward and backward: the issue's "49 TF a step"
    assert 6 * 10 * MLP * S / 1e12 == pytest.approx(49.5, abs=0.1)


# --------------------------------------------- the comparison, by hand

TOL = CONFIG["train"]["tolerance"]
LOSS, NORM = 9.44, 1.2


def _differences(**over):
    """An honest step's readings (each a third of its limit), or with
    ``over``."""
    out = dict(
        own_stream_by_layer=[[TOL["own_stream_first_rel"] / 3, 0.01]] * 10,
        stream_add_rel=TOL["stream_add_rel"] / 3,
        stream_start_rel=TOL["stream_start_rel"] / 3,
        system_grad_norm=NORM, ssm_out_rel=TOL["ssm_out_rel"] / 3,
        attn_out_rel=TOL["attn_out_rel"] / 3,
        mlp_out_rel=TOL["mlp_out_rel"] / 3,
        grad_leaf_rel={k: v / 3 for k, v in TOL["grad_leaf_rel"].items()})
    out.update(over)
    return out


def _passes(loss=LOSS, norm=NORM, **over):
    checks, _ = granite_hybrid.judge_train(CONFIG, loss, norm, LOSS, NORM,
                                           _differences(**over))
    return checks


def test_an_honest_step_passes_with_room(monkeypatch):
    monkeypatch.setitem(granite_hybrid._LIVE, "engine", None)
    checks = _passes()
    assert all(checks.values()), checks
    assert set(checks) == {
        "first_loss_matches_reference", "first_grad_norm_matches_reference",
        "state_space_branch_matches_reference",
        "attention_branch_matches_reference", "mlp_branch_matches_reference",
        "compared_gradients_are_the_steps",
        "gradients_match_reference_leaf_by_leaf",
        "first_mixer_matches_reference_on_its_own_stream",
        "stream_starts_from_the_scaled_embedding", "residual_stream_adds_up"}
    assert set(TOL["grad_leaf_rel"]) == {
        granite_hybrid.leaf_name(kind, leaf)
        for kind, leaves in granite_hybrid.LAYER_LEAVES.items()
        for leaf in leaves} | {"embed", "norm"}
    assert len(TOL["why"]) > 1000


@pytest.mark.parametrize("fault,kw,check", [
    ("the scan's branch off", {"ssm_out_rel": 3 * TOL["ssm_out_rel"]},
     "state_space_branch_matches_reference"),
    ("the attention branch off", {"attn_out_rel": 3 * TOL["attn_out_rel"]},
     "attention_branch_matches_reference"),
    ("the MLP off", {"mlp_out_rel": 3 * TOL["mlp_out_rel"]},
     "mlp_branch_matches_reference"),
    ("one leaf off", {"grad_leaf_rel": dict(
        {k: 0.0 for k in TOL["grad_leaf_rel"]},
        **{"ssm.A_log": 2 * TOL["grad_leaf_rel"]["ssm.A_log"]})},
     "gradients_match_reference_leaf_by_leaf"),
    ("a leaf missing", {"grad_leaf_rel": {
        k: 0.0 for k in TOL["grad_leaf_rel"] if k != "ssm.D"}},
     "gradients_match_reference_leaf_by_leaf"),
    ("the first mixer on its own stream", {"own_stream_by_layer": [
        [2 * TOL["own_stream_first_rel"], 0.0]] + [[0.0, 0.0]] * 9},
     "first_mixer_matches_reference_on_its_own_stream"),
    ("the embedding not scaled", {"stream_start_rel": 11 / 12},
     "stream_starts_from_the_scaled_embedding"),
    ("a branch added without its multiplier", {"stream_add_rel": 0.5},
     "residual_stream_adds_up"),
    ("other gradients than the step's", {"system_grad_norm": 1.1 * NORM},
     "compared_gradients_are_the_steps"),
], ids=lambda v: v if isinstance(v, str) and " " in v else "")
def test_a_wrong_step_fails(monkeypatch, fault, kw, check):
    monkeypatch.setitem(granite_hybrid._LIVE, "engine", None)
    checks = _passes(**kw)
    assert not checks[check], fault
    assert [k for k, v in checks.items() if not v] == [check]


def test_a_wrong_loss_or_norm_fails(monkeypatch):
    monkeypatch.setitem(granite_hybrid._LIVE, "engine", None)
    assert TOL["loss_abs"] <= 0.001 and TOL["grad_norm_rel"] <= 0.005
    assert not _passes(loss=LOSS + 2 * TOL["loss_abs"])[
        "first_loss_matches_reference"]
    assert not _passes(norm=NORM * (1 + 2 * TOL["grad_norm_rel"]))[
        "first_grad_norm_matches_reference"]


# ------------------------------------------- the readers, on a hand-made run

JIT = "jit(train_batch_fn)/ds_fwd_bwd/"
FWD = JIT + "jvp(GraniteHybridForCausalLM)/layer_0/checkpoint"
REC = JIT + "transpose(jvp(GraniteHybridForCausalLM))/layer_0/checkpoint" \
    "/rematted_computation"
BWD = JIT + "transpose(jvp(GraniteHybridForCausalLM))/layer_0/checkpoint"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, the path it was traced under, ns in a step of 1 s)
OPS = [
    ("%fusion.1 = bf16[16384,8512] fusion(%a)", FWD + "/mamba/in_proj/dot",
     30e6),
    ("%fusion.2 = bf16[16384,4352] fusion(%a)", FWD + "/mamba/ssm_conv/mul",
     4e6),
    ("%fusion.3 = f32[1,8,128,8,128] fusion(%a)",
     FWD + "/mamba/ssd_scan_prep/cumsum", 2e6),
    ("%ssd.4 = bf16[1,16384,4096] custom-call(%a)" + PALLAS,
     FWD + "/mamba/ssd_scan_fwd/pallas_call", 8e6),
    ("%ssd.5 = bf16[1,16384,4096] custom-call(%a)" + PALLAS,
     BWD + "/mamba/ssd_scan_bwd/pallas_call", 20e6),
    ("%norm.6 = bf16[1,16384,4096] custom-call(%a)" + PALLAS,
     FWD + "/mamba/ssm_norm/mixer_norm_fwd/pallas_call", 3e6),
    ("%norm.7 = bf16[1,16384,4096] custom-call(%a)" + PALLAS,
     REC + "/mamba/ssm_norm/mixer_norm_fwd/pallas_call", 3e6),
    ("%norm.8 = bf16[1,16384,4096] custom-call(%a)" + PALLAS,
     BWD + "/mamba/ssm_norm/mixer_norm_bwd/pallas_call", 5e6),
    ("%fusion.9 = f32[4096] fusion(%a)", BWD + "/mamba/ssm_norm/reduce_sum",
     1e6),
    ("%fusion.10 = bf16[16384,16384] fusion(%a)",
     FWD + "/shared_mlp/input_linear/dot_general", 100e6),
    ("%fusion.11 = bf16[16384,8192] fusion(%a)", REC + "/shared_mlp/mul",
     50e6),
    ("%fusion.12 = bf16[16384,2048] fusion(%a)",
     BWD + "/shared_mlp/output_linear/dot_general", 250e6),
    ("%fusion.13 = bf16[16384,2048] fusion(%a)",
     FWD.replace("layer_0", "layer_5") + "/attn/o_proj/dot_general", 524e6),
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


def test_the_readers_on_a_hand_made_scope_table():
    record = _record(granite_hybrid)
    chip = sr.busiest_chip(record)
    assert chip["busy_ms"] == pytest.approx(1000.0)
    # the MLP: forward, recomputation and backward
    assert dense_mlp_ms.read(record) == pytest.approx(400.0)
    # the norm: two forward kernels (one the recomputation), the backward
    # kernel and the XLA sum beside it
    nbytes = granite_hybrid.ssm_norm_bytes_per_step(CONFIG, S)
    assert ssm_norm_roofline.read(record) == pytest.approx(
        100 * nbytes / 819e9 / 0.012)
    assert 0 < ssm_norm_roofline.read(record) < 100
    # the scan's readers, as on the Nemotron cell
    assert ssd_scan_share.scan_ms(record) == pytest.approx(30.0)
    assert ssd_scan_share.read(record) == pytest.approx(3.0)
    _, scan_bytes = granite_hybrid.ssd_scan_flops_and_bytes(CONFIG, S)
    assert ssd_scan_roofline.read(record) == pytest.approx(
        100 * scan_bytes / 819e9 / 0.030)
    assert 0 < ssd_scan_roofline.read(record) < 100
    assert ssm_layer_ms.read(record) == pytest.approx(30 + 30 + 4 + 12)
    rows = {}
    for p, t, _, ms in chip["rows"]:
        rows[p, t] = rows.get((p, t), 0.0) + ms
    assert rows[("forward", "attn")] == pytest.approx(524.0)
    assert rows[("recompute", "shared_mlp")] == pytest.approx(50.0)
    assert rows[("recompute", "ssm_norm")] == pytest.approx(3.0)


@pytest.mark.parametrize("family", [gpt2, olmoe, qwen3_next, laguna,
                                    smallthinker, nemotron_h, deepseek_v3],
                         ids=lambda f: f.__name__.rsplit(".", 1)[-1])
def test_a_program_without_the_scopes_reads_nothing(family):
    """The two new readers on the other families' programs (the parent's,
    too: it has no family with these tags) and on a run without a trace:
    None, and nothing raised."""
    record = _record(family)
    for reader in (dense_mlp_ms, ssm_norm_roofline):
        assert reader.read(record) is None, reader.NAME
    untraced = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                              family=granite_hybrid, rehearse=False,
                              peaks=None)
    untraced.extra.update(tokens_per_step=S, global_batch=1, seq_len=S)
    for reader in (dense_mlp_ms, ssm_norm_roofline):
        assert reader.read(untraced) is None, reader.NAME


def test_the_cells_rehearsal_runs_and_its_checks_pass(capsys, monkeypatch):
    """``--rehearse-cpu`` of the cell, traced: the whole flow at the file's
    tiny sizes; the line is well formed, holds no metric value and is never
    ``correct``. The limits are the chip's: in bfloat16 at the rehearsal's
    64 tokens the gradient norm reads 0.19 % off on every seed against the
    chip's 0.074 %, so the flow runs here with the rehearsal's dtypes set
    to float32, where every check against the reference must pass."""
    import copy
    config = copy.deepcopy(CONFIG)
    config["rehearse_cpu"]["model"]["dtype"] = "float32"
    engine = config["rehearse_cpu"]["train"]["engine"]
    engine["bf16"] = {"enabled": False}
    engine["data_types"] = {"grad_dtype": "fp32"}
    theirs = manifest.config_of
    monkeypatch.setattr(manifest, "config_of", lambda bench, cell: config
                        if cell["name"] == CELL else theirs(bench, cell))
    rc = run.main(["--workload", CELL, "--seed", "4000000007", "--seconds",
                   "1.5", "--trace", "1", "--rehearse-cpu"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert line["metrics"] == {} and line["correct"] is False
    assert line["rehearsal"] is True and line["rehearsal_checks_passed"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"train_compiles_in_window", "setup_compile_s"} \
        <= set(line["rehearsal_metric_names"])
    assert not [n for n in line["rehearsal_metric_names"]
                if "roofline" in n or "mfu" in n or n in NEW]
