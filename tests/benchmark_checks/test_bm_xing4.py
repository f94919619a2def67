"""The Xing4.0 cell (ISSUE 56): the manifest's entries found by NAME, the
catalog's numbers, the parameter arithmetic against the initialised tree, the
family's contract and its counts of operations and bytes by hand, the
comparison that decides ``correct`` on hand-made readings, the three new
readers (``mhc_stream_ms``, ``mhc_stream_roofline``, ``mtp_ms``) on a
hand-made scope table and on programs that lack the scopes, and the cell's
CPU rehearsal end to end."""

import json
import os

import pytest

from benchmark import families, harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import (deepseek_v3, gpt2, granite_hybrid, laguna,
                                nemotron_h, olmoe, qwen3_next, smallthinker,
                                xing4)
from benchmark.layer_metrics import (loss_head_ms, mhc_stream_ms,
                                     mhc_stream_roofline, mla_expand_ms,
                                     mla_layer_ms, mtp_ms)

CELL = "xing4-train-1chip-s4096"
NAME = "xing4-29b-a4b-ep8-depth5"
SOURCE = ("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B"
          "/blob/main/config.json")
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
TRAFFIC = manifest.traffic_of({"name": CELL})

S = 4096
H = 3584
ATTENTION = H * 768 + 768 * 32 * 192 + H * 576 + 512 * 32 * 256 + 4096 * H
MIXER = 4 * H * 24
DENSE = 3 * H * 9216
EXPERT = 3 * H * 1024
HEAD = 16384 * H
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]
NEW = ("mhc_stream_ms", "mhc_stream_roofline", "mtp_ms")


def test_the_cell_is_the_one_issue_56_names():
    """Entries by name: a later PR appends and this stays true."""
    cell = manifest.cell_of(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain-b1x4096", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert sorted(entry["reduced"]) == sorted(REDUCED) \
        == sorted(CONFIG["reduced"])
    names = {m["name"] for m in manifest.metrics_for(BENCH, cell, "per_layer")}
    assert {*NEW, "mla_layer_ms", "mla_expand_ms", "loss_head_ms",
            "moe_gmm_roofline", "moe_gmm_share", "moe_dispatch_ms",
            "moe_rows_max_over_mean", "moe_rows_held_share", "moe_router_ms",
            "flash_attn_share", "flash_attn_roofline", "flash_fwd_roofline",
            "flash_bwd_roofline", "train_mfu", "train_step_ms",
            "train_fwd_ms", "train_bwd_ms", "train_recompute_ms",
            "train_optimizer_ms", "train_peak_hbm_gb",
            "train_program_hbm_gb", "train_unscoped_share",
            "train_device_idle_share", "train_compiles_in_window",
            "setup_engine_init_s", "setup_first_step_s",
            "setup_outside_program_s", "setup_compile_s",
            "setup_programs_compiled", "setup_cache_misses"} == names
    assert {m["name"] for m in manifest.metrics_for(BENCH, cell,
                                                    "end_to_end")} \
        == {"train_tokens_per_s", "setup_s"}
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["source"] == "device_trace"
            assert m["moves"] == "train_tokens_per_s"
    assert {k: TRAFFIC[k] for k in (
        "kind", "global_batch", "seq_len", "batch_pool", "token_below",
        "warmup_steps", "fence_lag_steps", "trace_steps")} == {
        "kind": "train_steps", "global_batch": 1, "seq_len": 4096,
        "batch_pool": 16, "token_below": 16384, "warmup_steps": 3,
        "fence_lag_steps": 2, "trace_steps": 3}
    assert cell["why"] == TRAFFIC["why"] and TRAFFIC["users"]
    assert not manifest.problems(BENCH)


def test_the_family_keeps_the_contract():
    for member in families.TRAINING + families.TAGS:
        assert hasattr(xing4, member), member
    assert not [m for m in families.SERVING if hasattr(xing4, m)]
    assert xing4.KERNEL_TAGS == deepseek_v3.KERNEL_TAGS
    assert set(deepseek_v3.WIDTH_KEYS) | {"hc_mult"} == set(xing4.WIDTH_KEYS)
    assert xing4.MHC_TAGS == ("mhc_coeff", "mhc_read", "mhc_write")
    tags = xing4.MODULE_TAGS
    assert set(tags) == set(deepseek_v3.MODULE_TAGS) | set(xing4.MHC_TAGS) \
        | {"mtp"}
    # what runs inside the prediction module keeps its own tag: ``mtp`` is
    # matched last, and the second head pass is ``ds_loss_head``
    assert tags[-1] == xing4.MTP_SCOPE == "mtp"
    assert xing4.MLA_LAYER_TAGS == deepseek_v3.MLA_LAYER_TAGS
    assert xing4.traffic_shapes(CONFIG, False) == {
        "vocab_size": 16384, "max_positions": 262144, "seq_scale": 1.0}
    assert xing4.traffic_shapes(CONFIG, True)["seq_scale"] == 1 / 64
    # the scheduler block is the other share cells', letter for letter
    # (tests/benchmark_checks/test_bm_share_cells_warm_up.py names its five)
    with open(os.path.join(manifest.HERE, "configs",
                           "nemotron-3-nano-30b-a3b-ep16-depth9.json")) as f:
        nemotron = json.load(f)
    assert CONFIG["train"]["engine"] == nemotron["train"]["engine"]
    assert CONFIG["train"]["engine"]["scheduler"]["params"][
        "warmup_num_steps"] == 2000
    assert "warms up" in CONFIG["train"]["scheduler_why"]


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key; depth, leading dense layers, experts held and vocabulary differ,
    and are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert CONFIG["source"] == row["source_url"] == SOURCE
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    published = CONFIG["published"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "kv_lora_rank", "q_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "n_shared_experts", "num_experts_per_tok", "hc_mult"):
        assert key in xing4.WIDTH_KEYS
        assert CONFIG[key] == published[key] == row["config"][key]
    for key in REDUCED:
        assert published[key] == row["config"][key], key
    assert CONFIG["n_routed_experts"] * CONFIG["expert_parallel_size"] \
        == 64 == published["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == 131072 == published["vocab_size"]
    assert CONFIG["num_hidden_layers"] == 5 \
        == CONFIG["first_k_dense_replace"] + 4
    assert CONFIG["num_nextn_predict_layers"] == 1
    assert set(CONFIG["changed_why"]) == set(REDUCED)
    assert {"a_initializer_range", "b_rope_layout", "c_mtp", "d_streams",
            "e_stream_mixer_draw", "f_selection_bias", "g_shared_expert",
            "per_device_batch"} <= set(CONFIG["assumed"])
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert CONFIG["model"]["remat"] and CONFIG["rehearse_cpu"]


def test_the_parameter_arithmetic_is_the_initialised_trees():
    """``changed_why``'s numbers against ``jax.eval_shape`` of the model the
    configuration builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = xing4._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == model.config.num_params() == 913_473_668
    assert ATTENTION == 28_409_856
    assert count(shapes["layer_0"]["mla_attn"]) == ATTENTION + 768 + 512 \
        == 28_411_136
    assert count(shapes["layer_0"]["attn_hc"]) == MIXER + 24 + 3 == 344_091
    assert DENSE == 99_090_432 and EXPERT == 11_010_048
    assert count(shapes["layer_0"]) == 28_411_136 + 7_168 + 2 * 344_091 \
        + DENSE == 128_196_918
    assert count(shapes["layer_1"]) == 28_411_136 + 7_168 + 2 * 344_091 \
        + 9 * EXPERT + H * 64 + 64 == 128_426_358
    assert count(shapes["mtp_layer"]) == 128_426_358
    assert count({k: shapes[k] for k in ("mtp_eh_proj", "mtp_hnorm",
                                         "mtp_enorm", "mtp_norm",
                                         "mtp_layer")}) \
        == 2 * H * H + 3 * H + 128_426_358 == 154_127_222
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) \
        + count(shapes["norm"]) == 2 * HEAD + H == 117_444_096
    why = " ".join(CONFIG["changed_why"].values())
    for number in ("28,411,136", "344,091", "99,090,432", "11,010,048",
                   "128,196,918", "128,426,358", "154,127,222",
                   "117,444,096", "913,473,668", "9.13 GB", "12.79 GB"):
        assert number in why, number
    assert 913_473_668 * 14 / 1e9 == pytest.approx(12.79, abs=0.005)
    assert 913_473_668 * 10 / 1e9 == pytest.approx(9.13, abs=0.005)
    assert "30,276,195,174" in CONFIG["published"]["parameters"]


def test_flops_and_bytes_count_what_this_rank_needs(monkeypatch):
    f = xing4
    monkeypatch.setitem(f._LIVE, "gauges", {})
    assert f.rows_held_share(CONFIG) == 1 / 8
    block = ATTENTION + 2 * MIXER
    assert f.active_matmul_params(CONFIG) == 2 * HEAD + 2 * H * H \
        + 6 * block + DENSE + 5 * (H * 64 + EXPERT + 4 / 8 * EXPERT)
    # ~0.5 G matmul parameters a token (ISSUE 56: 500.5 M without the
    # mixers' 0.7 M a branch)
    assert 500e6 < f.active_matmul_params(CONFIG) < 506e6
    attention = 6 * 32 * S * S * (3 * 192 + 3 * 128)
    assert f.train_attention_flops_per_step(CONFIG, 1, S) == attention
    assert f.train_flops_per_token(CONFIG, S) == \
        6 * f.active_matmul_params(CONFIG) + attention / S
    share = attention / (f.train_flops_per_token(CONFIG, S) * S)
    assert 0.19 < share < 0.21            # 3.1 of 15.5 TFLOP a step
    rows = S * 4 / 8
    assert f.moe_gmm_flops_per_step(CONFIG, S) == \
        5 * 3 * 3 * 2 * rows * H * 1024
    monkeypatch.setitem(f._LIVE, "gauges", {"moe/rows_held_share": 0.13})
    assert f.moe_gmm_flops_per_step(CONFIG, S) == pytest.approx(
        5 * 3 * 3 * 2 * S * 4 * 0.13 * H * 1024)
    # a branch, a token: forward X + y in, X_new out (9 C), backward dX_new,
    # X, y in, dX, dy out (14 C), bf16; 24 float32 coefficients each way;
    # 12 branches; two chains' ends (copy and sum, both directions)
    branch = 2 * H * (9 + 14) + 2 * 4 * 24
    ends = 2 * 2 * 2 * 2 * H * 5
    assert f.mhc_stream_bytes_per_step(CONFIG, S) == S * (12 * branch + ends)
    assert 9.2e9 < f.mhc_stream_bytes_per_step(CONFIG, S) < 9.4e9
    assert f.mhc_stream_bytes_per_step(CONFIG, S) / 819e9 \
        == pytest.approx(0.0113, abs=2e-4)        # ~11 ms a step at the peak


# --------------------------------------------- the comparison, by hand

TOL = CONFIG["train"]["tolerance"]
LOSS, NORM = 12.6, 2.1


def _differences(**over):
    """An honest step's readings (each a third of its limit), or with
    ``over``."""
    first = TOL["own_stream_first_layer"]
    out = {
        "routing_differs": int(TOL["routing_differs_share"] / 3 * 81920),
        "routing_assignments": 81920,
        "mla_out_rel": TOL["mla_out_rel"] / 3,
        "dense_out_rel": TOL["dense_out_rel"] / 3,
        "ffn_out_rel": TOL["ffn_out_rel"] / 3,
        "mhc_coeff_abs": TOL["mhc_coeff_abs"] / 3,
        "system_grad_norm": NORM, "bias_grad_abs": 0.0,
        "system_mtp_loss": 9.7, "reference_mtp_ce": 9.7
        + TOL["mtp_loss_abs"] / 3,
        "grad_leaf_rel": {k: v / 3 for k, v in TOL["grad_leaf_rel"].items()},
        "own_stream_kinds": ["dense"] + ["sparse"] * 4,
        "own_stream_by_layer": [
            [first["mixer_rel"] / 3, first["ffn_rel"] / 3, 0.0],
            [0.5, 0.5, first["routing_share"] / 3]] + [[0.5, 0.5, 0.5]] * 3,
        "stream_mix_rel": TOL["stream_mix_rel"] / 3}
    out.update(over)
    return out


def _passes(loss=LOSS, norm=NORM, **over):
    checks, _ = xing4.judge_train(CONFIG, loss, norm, LOSS, NORM,
                                  _differences(**over))
    return checks


def test_an_honest_step_passes_with_room(monkeypatch):
    monkeypatch.setattr(xing4, "_LIVE", {})
    checks = _passes()
    assert all(checks.values()), checks
    assert {"stream_coefficients_match_reference", "stream_mixes_add_up",
            "prediction_loss_matches_reference",
            "selection_bias_takes_no_gradient",
            "first_layer_matches_reference_on_its_own_stream",
            "gradients_match_reference_leaf_by_leaf"} <= set(checks)
    # later layers drift on their own streams: reported, not held
    assert all(_passes(own_stream_by_layer=_differences()[
        "own_stream_by_layer"][:2] + [[9.0, 9.0, 9.0]] * 3).values())


@pytest.mark.parametrize("fault,kw,check", [
    ("a coefficient off", {"mhc_coeff_abs": TOL["mhc_coeff_abs"] * 1.5},
     "stream_coefficients_match_reference"),
    ("a stream mix lost", {"stream_mix_rel": 0.3}, "stream_mixes_add_up"),
    ("the prediction loss off",
     {"reference_mtp_ce": 9.7 + 2 * TOL["mtp_loss_abs"]},
     "prediction_loss_matches_reference"),
    ("the attention branch off", {"mla_out_rel": 2 * TOL["mla_out_rel"]},
     "attention_branch_matches_reference"),
    ("the dense branch off", {"dense_out_rel": 2 * TOL["dense_out_rel"]},
     "dense_branch_matches_reference"),
    ("the expert branch off", {"ffn_out_rel": 2 * TOL["ffn_out_rel"]},
     "expert_branch_matches_reference"),
    ("a gradient reaches the bias", {"bias_grad_abs": 1e-9},
     "selection_bias_takes_no_gradient"),
    ("a stream mixer's leaf off", {"grad_leaf_rel": dict(
        _differences()["grad_leaf_rel"],
        **{"hc.phi": 2 * TOL["grad_leaf_rel"]["hc.phi"]})},
     "gradients_match_reference_leaf_by_leaf"),
    ("a leaf missing", {"grad_leaf_rel": {
        k: v for k, v in _differences()["grad_leaf_rel"].items()
        if k != "mtp.eh_proj"}}, "gradients_match_reference_leaf_by_leaf"),
    ("the first layer off on its own stream", {"own_stream_by_layer": [
        [1.0, 0.0, 0.0], [0.0, 0.0, 0.0]]},
     "first_layer_matches_reference_on_its_own_stream"),
], ids=lambda v: v if isinstance(v, str) and " " in v else "")
def test_a_wrong_step_fails(monkeypatch, fault, kw, check):
    monkeypatch.setattr(xing4, "_LIVE", {})
    checks = _passes(**kw)
    assert not checks[check], fault
    assert [k for k, ok in checks.items() if not ok] == [check], fault


def test_a_wrong_loss_or_norm_fails(monkeypatch):
    monkeypatch.setattr(xing4, "_LIVE", {})
    assert not _passes(loss=LOSS + 2 * TOL["loss_abs"])[
        "first_loss_matches_reference"]
    assert not _passes(norm=NORM * (1 + 2 * TOL["grad_norm_rel"]))[
        "first_grad_norm_matches_reference"]


def test_set_ups_garbage_is_frozen_ahead_of_the_window(monkeypatch):
    """With a live engine (the cell's run, after warm-up) ``judge_train``
    collects once and freezes what is left, and says what it cost; without
    one (a test, the control's comparison of hand-made readings) the
    collector is left alone."""
    import gc
    calls = []
    monkeypatch.setattr(gc, "collect", lambda: calls.append("collect"))
    monkeypatch.setattr(gc, "freeze", lambda: calls.append("freeze"))
    monkeypatch.setattr(xing4, "_LIVE", {})
    _, detail = xing4.judge_train(CONFIG, LOSS, NORM, LOSS, NORM,
                                  _differences())
    assert not calls and "collector_settled" not in detail

    class Engine:
        def telemetry_flush(self):
            return {"gauges": {"moe/dropped_rows": 0.0, "mtp/loss": 9.7}}
    monkeypatch.setattr(xing4, "_LIVE", {"engine": Engine()})
    checks, detail = xing4.judge_train(CONFIG, LOSS, NORM, LOSS, NORM,
                                       _differences())
    assert calls == ["collect", "freeze"]
    assert detail["collector_settled"]["objects"] > 0
    assert detail["collector_settled"]["seconds"] >= 0
    assert checks["no_routed_row_dropped"]
    assert detail["moe_gauges"] == {"moe/dropped_rows": 0.0, "mtp/loss": 9.7}


# ------------------------------------------------------------ the readers

FWD = "jit(train_batch_fn)/ds_fwd_bwd/jvp(DeepseekV3ForCausalLM)"
BWD = "jit(train_batch_fn)/ds_fwd_bwd/transpose(jvp(DeepseekV3ForCausalLM))"
REMAT = BWD + "/layer_1/checkpoint/rematted_computation"
# (instruction, the path it was traced under, ns)
OPS = [
    ("%fusion.1 = f32[4096,24] fusion(%a)",
     FWD + "/layer_1/attn_hc/mhc_coeff/dot_general", 2e6),
    ("%fusion.2 = bf16[4096,3584] fusion(%a)",
     FWD + "/layer_1/mhc_read/mul", 3e6),
    ("%fusion.3 = bf16[4096,14336] fusion(%a)",
     FWD + "/layer_1/mhc_write/concatenate", 5e6),
    ("%fusion.4 = bf16[4096,14336] fusion(%a)", REMAT + "/mhc_write/add", 4e6),
    ("%fusion.5 = bf16[4096,14336] fusion(%a)",
     BWD + "/layer_1/mhc_write/mul", 6e6),
    ("%fusion.6 = bf16[4096,14336] fusion(%a)", FWD + "/mhc_write/tile", 1e6),
    ("%fusion.7 = bf16[4096,3584] fusion(%a)",
     FWD + "/layer_1/mla_attn/mla_latent/q_a_proj/dot_general", 7e6),
    ("%fusion.8 = f32[1024,16384] fusion(%a)",
     FWD + "/ds_loss_head/while/body/dot_general", 30e6),
    ("%fusion.9 = f32[1024,16384] fusion(%a)",
     FWD + "/mtp/ds_loss_head/while/body/dot_general", 31e6),
    ("%fusion.10 = bf16[4096,3584] fusion(%a)",
     FWD + "/mtp/mtp_eh_proj/dot_general", 8e6),
    ("%fusion.11 = bf16[4096,14336] fusion(%a)",
     FWD + "/mtp/mtp_layer/ffn_hc/mhc_coeff/exp", 9e6),
    ("%custom-call.1 = bf16[32,4096,128] custom-call(%a), "
     'custom_call_target="tpu_custom_call"',
     FWD + "/mtp/mtp_layer/mla_attn/flash_fwd_chunk/pallas_call", 20e6),
    ("%fusion.12 = bf16[4096,3584] fusion(%a)",
     BWD + "/mtp/mtp_layer/mla_attn/o_proj/dot_general", 11e6),
    ("%fusion.13 = bf16[4096,9216] fusion(%a)",
     FWD + "/layer_0/mlp/dense_mlp/gate_proj/dot_general", 863e6),
]


def _record(family):
    text = ("HloModule jit_train_batch_fn\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n")
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
    monkeypatch.setitem(xing4._LIVE, "gauges", {})
    record = _record(xing4)
    chip = sr.busiest_chip(record)
    assert chip["busy_ms"] == pytest.approx(1000.0)
    # the three scopes in every phase, the chains' ends and the prediction
    # module's mixers among them: 2 + 3 + 5 + 4 + 6 + 1 + 9
    assert mhc_stream_ms.read(record) == pytest.approx(30.0)
    assert mhc_stream_roofline.read(record) == pytest.approx(
        100 * xing4.mhc_stream_bytes_per_step(CONFIG, S) / 819e9 / 0.030)
    assert mhc_stream_roofline.read(record) < 100
    # everything under ``mtp``, whatever its tag: 31 + 8 + 9 + 20 + 11
    assert mtp_ms.read(record) == pytest.approx(79.0)
    # ... while the tags keep what it runs through: both head passes, all
    # attention modules
    assert loss_head_ms.read(record) == pytest.approx(61.0)
    assert mla_layer_ms.read(record) == pytest.approx(7 + 20 + 11)
    assert mla_expand_ms.read(record) == pytest.approx(7.0)
    rows = {}
    for p, t, _, ms in chip["rows"]:
        rows[p, t] = rows.get((p, t), 0.0) + ms
    assert rows[("forward", "mtp")] == pytest.approx(8.0)
    assert rows[("recompute", "mhc_write")] == pytest.approx(4.0)
    assert rows[("forward", "mhc_coeff")] == pytest.approx(11.0)


@pytest.mark.parametrize("family", [gpt2, olmoe, qwen3_next, laguna,
                                    smallthinker, nemotron_h, deepseek_v3,
                                    granite_hybrid],
                         ids=["gpt2", "olmoe", "qwen3_next", "laguna",
                              "smallthinker", "nemotron_h", "deepseek_v3",
                              "granite_hybrid"])
def test_a_program_without_the_scopes_reads_nothing(family):
    """The new readers on the other families' programs (the parent's, too: it
    has no family with these tags) and on a run without a trace: None, and
    nothing raised."""
    record = _record(family)
    for reader in (mhc_stream_ms, mhc_stream_roofline, mtp_ms):
        assert reader.read(record) is None, reader.NAME
    untraced = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                              family=xing4, rehearse=False, peaks=None)
    untraced.extra.update(tokens_per_step=S, global_batch=1, seq_len=S)
    for reader in (mhc_stream_ms, mhc_stream_roofline, mtp_ms):
        assert reader.read(untraced) is None, reader.NAME


def test_the_gauges_are_read_through_the_family(monkeypatch):
    from benchmark.layer_metrics import moe_rows_held_share
    record = _record(xing4)
    monkeypatch.setitem(xing4._LIVE, "gauges", {})
    assert moe_rows_held_share.read(record) is None
    monkeypatch.setitem(xing4._LIVE, "gauges", {
        "moe/rows_held_share": 0.1263, "mhc/res_sum_err": 2e-6,
        "mtp/loss": 9.7})
    assert moe_rows_held_share.read(record) == pytest.approx(12.63)
    assert xing4.program_gauges()["mhc/res_sum_err"] == 2e-6


# ---------------------------------------------------------- the rehearsal

def test_the_cells_rehearsal_runs_and_its_checks_pass(capsys, monkeypatch):
    """``--rehearse-cpu`` of the cell, traced: the whole flow at the file's
    tiny sizes — set-up levels both routers (the prediction module's among
    them), the reference's two programs run — and the line is well formed,
    holds no metric value and is never ``correct``. The limits are the
    chip's, so the flow runs with the rehearsal's dtypes set to float32,
    where every check against the reference must pass."""
    import copy
    from benchmark import run
    config = copy.deepcopy(CONFIG)
    config["rehearse_cpu"]["model"]["dtype"] = "float32"
    engine = config["rehearse_cpu"]["train"]["engine"]
    engine["bf16"] = {"enabled": False}
    engine["data_types"] = {"grad_dtype": "fp32"}
    theirs = manifest.config_of
    monkeypatch.setattr(manifest, "config_of", lambda bench, cell: config
                        if cell["name"] == CELL else theirs(bench, cell))
    rc = run.main(["--workload", CELL, "--seed", "4000000311", "--seconds",
                   "1", "--trace", "1", "--rehearse-cpu"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert line["metrics"] == {} and line["correct"] is False
    assert line["rehearsal"] is True and line["rehearsal_checks_passed"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"train_compiles_in_window", "setup_compile_s",
            "moe_rows_held_share"} <= set(line["rehearsal_metric_names"])
    assert not [n for n in line["rehearsal_metric_names"]
                if "roofline" in n or "mfu" in n or n in NEW]
    assert len(xing4._LIVE["balance"]["rows_max_over_mean"]["last_round"]) \
        == 2
    gauges = xing4.program_gauges()
    assert 0 <= gauges["mhc/res_sum_err"] < 1e-4
    assert 5.5 < gauges["mtp/loss"] < 7.0
