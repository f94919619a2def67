"""The SmallThinker cell (ISSUE 38): the manifest's entries found by NAME,
the catalog's numbers, the parameter arithmetic, the family's counts of
operations, the comparison that decides ``correct`` on hand-made readings,
and the one new reader (``moe_router_ms``) on a hand-made scope table and on
programs that lack the scope."""

import json
import os

import pytest

from benchmark import harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import gpt2, laguna, olmoe, qwen3_next, smallthinker
from benchmark.layer_metrics import (flash_fwd_roofline, moe_dispatch_ms,
                                     moe_gmm_roofline, moe_router_ms,
                                     swa_attn_share, swa_bwd_roofline,
                                     swa_fwd_roofline)

CELL = "smallthinker-train-1chip-s16384"
NAME = "smallthinker-21b-a3b-ep4-depth4"
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
TRAFFIC = manifest.traffic_of({"name": CELL})

S = 16384
MIXER = 2 * 2560 * 3584 + 2 * 2560 * 512
EXPERT = 3 * 2560 * 768
HEAD = 37984 * 2560
BAND = S * 4096 - 4096 * 4095 // 2                        # scores a head
REDUCED = ["num_hidden_layers", "sliding_window_layout", "rope_layout",
           "moe_num_primary_experts", "vocab_size"]


def the_cell_is_the_one_issue_38_names(bench):
    """Entries by name: a later PR appends and this stays true."""
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain-b1x16384", 1)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"]
    assert sorted(entry["reduced"]) == sorted(REDUCED) \
        == sorted(CONFIG["reduced"])
    names = {m["name"] for m in manifest.metrics_for(bench, cell, "per_layer")}
    assert {"moe_router_ms", "swa_attn_share", "swa_fwd_roofline",
            "swa_bwd_roofline", "swa_tile_overcompute", "moe_gmm_roofline",
            "moe_gmm_share", "moe_dispatch_ms", "moe_rows_max_over_mean",
            "moe_rows_held_share", "flash_attn_share", "flash_attn_roofline",
            "flash_fwd_roofline", "flash_bwd_roofline", "train_mfu",
            "train_step_ms", "train_program_hbm_gb", "train_unscoped_share",
            "train_device_idle_share", "train_compiles_in_window",
            "setup_engine_init_s", "setup_first_step_s",
            "setup_outside_program_s", "setup_compile_s",
            "setup_programs_compiled", "setup_cache_misses"} <= names
    assert not names & {"collective_exposed_share", "collectives_per_step",
                        "gdn_scan_share", "gdn_scan_roofline", "gdn_layer_ms"}
    e2e = {m["name"] for m in manifest.metrics_for(bench, cell, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    router = next(m for m in bench["per_layer"]
                  if m["name"] == "moe_router_ms")
    assert CELL in router["workloads"]
    assert (router["layer"], router["moves"], router["source"]) == (
        moe_router_ms.LAYER, moe_router_ms.MOVES, moe_router_ms.SOURCE)


def test_the_cell_is_the_one_issue_38_names():
    the_cell_is_the_one_issue_38_names(BENCH)
    assert manifest.problems(BENCH) == []
    assert (TRAFFIC["kind"], TRAFFIC["global_batch"], TRAFFIC["seq_len"],
            TRAFFIC["token_below"], TRAFFIC["batch_pool"],
            TRAFFIC["warmup_steps"], TRAFFIC["fence_lag_steps"],
            TRAFFIC["trace_steps"]) == ("train_steps", 1, S, 37984, 16, 3, 2,
                                        3)
    for key in ("users", "why_in_full"):
        assert TRAFFIC[key], key
    for weakness in ("1,536 rows an expert", "28 %", "published ratio",
                     "random router"):
        assert weakness in TRAFFIC["why_in_full"], weakness


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key; depth with its two lists, experts held and vocabulary differ, and
    are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    published = CONFIG["published"]
    for key in ("head_dim", "hidden_size", "moe_ffn_hidden_size",
                "num_attention_heads", "num_key_value_heads",
                "moe_num_active_primary_experts", "sliding_window_size"):
        assert key in smallthinker.WIDTH_KEYS or key.endswith("_dim")
        assert CONFIG[key] == published[key] == row["config"][key]
    for key in REDUCED:
        assert published[key] == row["config"][key], key
    for key in ("sliding_window_layout", "rope_layout"):
        assert CONFIG[key] == published[key][:4] == [0, 1, 1, 1]
        assert len(published[key]) == 52
    assert CONFIG["moe_num_primary_experts"] * CONFIG[
        "expert_parallel_size"] == 64 == published["moe_num_primary_experts"]
    assert CONFIG["vocab_size"] * 4 == 151936 == published["vocab_size"]
    assert set(CONFIG["changed_why"]) == set(REDUCED)
    assert {"a_router_input", "b_rope_pairing", "f_aux_loss",
            "init"} <= set(CONFIG["assumed"])
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert CONFIG["model"]["remat"] and CONFIG["rehearse_cpu"]


def test_the_parameter_arithmetic_is_the_initialised_trees():
    """``changed_why``'s numbers against ``jax.eval_shape`` of the model the
    configuration builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = smallthinker._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == model.config.num_params() == 656_529_920
    blk = shapes["layers"]["l0"]
    assert count(blk["attn"]) == MIXER == 20_971_520
    assert count(blk["mlp"]["router"]) == 163_840
    assert count(blk["mlp"]["gate_proj"]) * 3 == 16 * EXPERT
    assert EXPERT == 5_898_240
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) \
        == 194_478_080
    why = " ".join(CONFIG["changed_why"].values())
    for number in ("20,971,520", "163,840", "5,898,240", "656,529,920",
                   "194,478,080", "377,487,360", "9.19 GB"):
        assert number in why, number
    assert 656_529_920 * 14 / 1e9 == pytest.approx(9.19, abs=0.005)


def test_flops_count_what_this_rank_multiplies(monkeypatch):
    f = smallthinker
    monkeypatch.setitem(f._LIVE, "gauges", {})
    assert f.rows_held_share(CONFIG) == 0.25
    assert f.active_matmul_params(CONFIG) == HEAD + 4 * (
        MIXER + 2560 * 64 + 6 * 0.25 * EXPERT)
    fwd, bwd = f.swa_flops_per_step(CONFIG, 1, S)
    assert fwd == 2 * 2 * 3 * 28 * BAND * 128 and bwd == 2 * fwd
    # ISSUE 38's arithmetic: a sliding layer's band 0.84 TF forward, the
    # full layer's causal scores 1.92 TF
    assert fwd / 3 / 1e12 == pytest.approx(0.84, abs=0.005)
    causal = f.train_attention_flops_per_step(CONFIG, 1, S)
    assert causal / 3 / 1e12 == pytest.approx(1.92, abs=0.01)
    assert f.moe_gmm_flops_per_step(CONFIG, S) == 4 * 9 * 2 * 24576 * 2560 \
        * 768
    # ... and the rows the program counted, once a run has folded the gauge
    monkeypatch.setitem(f._LIVE, "gauges", {"moe/rows_held_share": 0.21})
    assert f.moe_gmm_flops_per_step(CONFIG, S) == pytest.approx(
        4 * 9 * 2 * 0.21 * 98304 * 2560 * 768)
    assert f.train_flops_per_token(CONFIG, S) == pytest.approx(
        6 * f.active_matmul_params(CONFIG) + (causal + fwd + bwd) / S)
    # the laguna family's band arithmetic, not a second formula
    assert f._band(S, 4096) == laguna._band(S, 4096) == BAND


# --------------------------------------------------------- the tolerance

LOSS, NORM = 10.58, 1.5
TOL = CONFIG["train"]["tolerance"]
LEAVES = {"embed", "lm_head", "norm", "input_norm", "post_attn_norm",
          "router", "gate", "up", "down"} \
    | {f"{n}.{k}" for n in "qkvo" for k in ("full", "swa")}
FIRST = TOL["own_stream_first_layer"]
# an honest run: half of every limit
DIFFERENCES = {
    "routing_differs": int(0.3 * TOL["routing_differs_share"] * 393_216),
    "routing_assignments": 393_216,
    "full_out_rel": 0.5 * TOL["full_out_rel"],
    "swa_out_rel": 0.5 * TOL["swa_out_rel"],
    "ffn_out_rel": 0.5 * TOL["ffn_out_rel"], "system_grad_norm": NORM,
    "own_stream_by_layer": [
        ["full_attention", "sparse", 0.5 * FIRST["mixer_rel"],
         0.5 * FIRST["ffn_rel"], 0.5 * FIRST["routing_share"]],
        ["sliding_attention", "sparse", 0.5, 0.5, 0.5]],
    "stream_add_rel": 0.5 * TOL["stream_add_rel"],
    "window_vs_causal_rel": 0.9, "window_leak_rel": 0.0,
    "causal_leak_rel": 1.1,
    "grad_leaf_rel": {name: 0.5 * TOL["grad_leaf_rel"][name]
                      for name in LEAVES}}


def _passes(loss=LOSS, norm=NORM, **differences):
    leaves = dict(DIFFERENCES["grad_leaf_rel"],
                  **differences.pop("grad_leaf_rel", {}))
    checks, _ = smallthinker.judge_train(
        CONFIG, loss, norm, LOSS, NORM,
        dict(DIFFERENCES, grad_leaf_rel=leaves, **differences))
    return all(checks.values())


def test_an_honest_step_passes_with_room():
    assert TOL["loss_abs"] <= 1.2e-3 and TOL["grad_norm_rel"] <= 0.004
    assert _passes()
    assert set(TOL["grad_leaf_rel"]) == LEAVES
    assert TOL["why"] and len(TOL["why"]) > 500
    for key in ("loss_abs", "grad_norm_rel", "routing_differs_share",
                "full_out_rel", "swa_out_rel", "ffn_out_rel",
                "own_stream_first_layer", "stream_add_rel",
                "window_vs_causal_rel_min", "window_leak_rel",
                "grad_leaf_rel"):
        assert key in TOL["why"], f"no reason given for {key}"


@pytest.mark.parametrize("fault,kw", [
    # 0.001 x 4 layers x E sum f P ~ 0.001 x 4 x 6
    ("the balance loss left out", dict(loss=LOSS - 0.024)),
    ("the window not applied", dict(swa_out_rel=0.5)),
    ("the window not applied, by the check no mask can hide",
     dict(window_vs_causal_rel=0.0)),
    ("attention that reaches past its window", dict(window_leak_rel=0.2)),
    ("a leak test without teeth", dict(causal_leak_rel=0.0)),
    ("RoPE on the full layer / the wrong KV head", dict(full_out_rel=0.5)),
    ("a sliding layer alone wrong", dict(swa_out_rel=2 * TOL["swa_out_rel"])),
    ("silu for relu / the top-6 not renormalised", dict(ffn_out_rel=0.6)),
    ("a router that reads another tensor", dict(routing_differs=130_000)),
    ("no expert weight gradient",
     dict(grad_leaf_rel={"gate": 1.0, "up": 1.0, "down": 1.0})),
    ("the window kernels' dk wrong", dict(grad_leaf_rel={"k.swa": 0.5})),
    ("a first layer that is wrong where a pinned pass cannot see",
     dict(own_stream_by_layer=[["full_attention", "sparse", 0.2, 0.01, 0.0]])),
    ("the first layer's routing on its own stream",
     dict(own_stream_by_layer=[["full_attention", "sparse", 0.005, 0.01,
                                0.35]])),
    ("a residual add that loses a tenth of a branch",
     dict(stream_add_rel=0.07)),
    ("a leaf the comparison never saw",
     dict(grad_leaf_rel={"o.swa": float("nan")})),
    ("the compared gradients are not the step's",
     dict(system_grad_norm=NORM * 1.01)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_wrong_step_fails(fault, kw):
    loss, norm = kw.pop("loss", LOSS), kw.pop("norm", NORM)
    assert not _passes(loss, norm, **kw), fault


def test_the_laguna_familys_window_check_is_handed_this_model():
    """``as_laguna``: the configuration under Laguna's key names builds a
    Laguna model config whose first sliding layer is this model's — 28
    heads, window 4,096, plain RoPE at theta 1.5e6, no gate — found at the
    same parameter path."""
    from deepspeed_tpu.models.laguna import block_paths
    cfg = laguna.model_config(smallthinker.as_laguna(CONFIG, False), False)
    i = cfg.layer_types.index(laguna.SLIDING)
    assert i == 1 and block_paths(cfg)[i] == ("layers", "l1", 0)
    assert (cfg.sliding_window, cfg.gating, cfg.layer_kinds[i][1],
            cfg.num_key_value_heads, cfg.head_dim) == (4096, False, 28, 4,
                                                       128)
    assert cfg.rope_of(laguna.SLIDING) == {
        "rope_type": "default", "rope_theta": 1500000,
        "partial_rotary_factor": 1}
    tiny = laguna.model_config(smallthinker.as_laguna(CONFIG, True), False)
    assert (tiny.sliding_window, tiny.hidden_size) == (32, 64)


# ------------------------------------------------------------ the readers

STEP = "jit(train_batch_fn)/ds_fwd_bwd"
FWD = STEP + "/jvp(SmallThinkerForCausalLM)"
BWD = STEP + "/transpose(jvp(SmallThinkerForCausalLM))"
SCAN = "/layers/while/body/closed_call"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, op_name, ns): one step of 1000 ms on one chip
OPS = [
    ("%swa_fwd.1 = f32[28,16384,128] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/l1/attn/swa_fwd/pallas_call", 60e6),
    # ONE backward call since PR 53: dq, dk and dv from one kernel
    ("%swa_bwd.2 = f32[28,16384,128] custom-call(%a)" + PALLAS,
     BWD + SCAN + "/l1/attn/swa_bwd/pallas_call", 120e6),
    ("%flash_fwd_chunk.4 = f32[28,16384,128] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/l0/attn/flash_fwd_chunk/pallas_call", 20e6),
    # the router's logits ahead of the mixer, its softmax / top-k, and its
    # backward pass
    ("%fusion.5 = f32[16384,64] fusion(%a)",
     FWD + SCAN + "/l0/mlp/moe_router/dot_general", 3e6),
    ("%sort.6 = f32[16384,64] sort(%a)",
     FWD + SCAN + "/l0/mlp/moe_router/top_k", 2e6),
    ("%fusion.7 = f32[2560,64] fusion(%a)",
     BWD + SCAN + "/l0/mlp/moe_router/dot_general", 4e6),
    ("%sort.8 = s32[98304] sort(%a)",
     FWD + SCAN + "/l0/mlp/moe_dispatch/sort", 7e6),
    ("%fusion.9 = bf16[49152,768] fusion(%a)",
     FWD + SCAN + "/l0/mlp/moe_act/mul", 5e6),
    ("%moe_gmm.10 = bf16[49152,768] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/l3/mlp/moe_gmm/pallas_call", 30e6),
    ("%fusion.11 = bf16[16384,2560] fusion(%a)", FWD + SCAN + "/l3/mlp/add",
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
    monkeypatch.setitem(smallthinker._LIVE, "gauges", {})
    record = _record(smallthinker)
    chip = sr.busiest_chip(record)
    assert chip["busy_ms"] == pytest.approx(1000.0)
    # the router: 3 + 2 forward, 4 backward; the dispatch tags hold it too
    assert moe_router_ms.read(record) == pytest.approx(9.0)
    assert moe_dispatch_ms.read(record) == pytest.approx(16.0)
    rows = {(p, t): ms for p, t, _, ms in chip["rows"]}
    assert rows[("forward", "moe_router")] == pytest.approx(5.0)
    assert rows[("backward", "moe_router")] == pytest.approx(4.0)
    assert rows[("forward", "moe_act")] == pytest.approx(5.0)
    assert swa_attn_share.read(record) == pytest.approx(18.0)
    fwd, bwd = smallthinker.swa_flops_per_step(CONFIG, 1, S)
    assert swa_fwd_roofline.read(record) == pytest.approx(
        100 * fwd / 197e12 / 0.060)
    assert swa_bwd_roofline.read(record) == pytest.approx(
        100 * bwd / 197e12 / 0.120)
    assert 0 < flash_fwd_roofline.read(record) < 100
    assert moe_gmm_roofline.read(record) == pytest.approx(
        100 * smallthinker.moe_gmm_flops_per_step(CONFIG, S) / 197e12
        / 0.030)


@pytest.mark.parametrize("family", [gpt2, olmoe, qwen3_next, laguna],
                         ids=["gpt2", "olmoe", "qwen3_next", "laguna"])
def test_a_program_without_the_scope_reads_nothing(family):
    """The new reader on the other families' programs and on a run without
    a trace: a family without the tag, or a step nothing of which ran under
    it, reads None and raises nothing."""
    record = _record(family)
    if "moe_router" in family.MODULE_TAGS:
        assert moe_router_ms.read(record) == pytest.approx(9.0)
    else:
        assert moe_router_ms.read(record) is None
    untraced = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                              family=smallthinker, rehearse=False, peaks=None)
    untraced.extra.update(tokens_per_step=S, global_batch=1, seq_len=S)
    assert moe_router_ms.read(untraced) is None


def test_the_gauges_are_read_through_the_family(monkeypatch):
    from benchmark.layer_metrics import (moe_rows_held_share,
                                         swa_tile_overcompute)
    record = _record(smallthinker)
    monkeypatch.setitem(smallthinker._LIVE, "gauges", {})
    assert moe_rows_held_share.read(record) is None
    monkeypatch.setitem(smallthinker._LIVE, "gauges", {
        "moe/rows_held_share": 0.2512,
        "attention/window_tile_overcompute": 1.125})
    assert moe_rows_held_share.read(record) == pytest.approx(25.12)
    assert swa_tile_overcompute.read(record) == pytest.approx(1.125)
