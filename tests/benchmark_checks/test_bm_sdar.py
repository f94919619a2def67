"""The SDAR cell (ISSUE 60): the manifest's entries found by NAME, the
catalog's numbers, the parameter arithmetic, the pair count against a brute
force, the family's counts of operations and bytes, the comparison that
decides ``correct`` on hand-made readings, the five new readers on a
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
from benchmark.families import olmoe, sdar
from benchmark.layer_metrics import (bd_attn_share, bd_bwd_roofline,
                                     bd_fwd_roofline, bd_noise_ms,
                                     bd_tile_overcompute, flash_fwd_roofline,
                                     moe_gmm_roofline, moe_router_ms)

CELL = "sdar-train-1chip-s8192"
NAME = "sdar-30b-a3b-chat-ep8-depth6"
BENCH = manifest.load()
with open(os.path.join(manifest.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
TRAFFIC = manifest.traffic_of({"name": CELL})

L = 8192
ATTN = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
EXPERT = 3 * 2048 * 768
PAIRS = L * L + 4 * L
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW = {"bd_attn_share", "bd_fwd_roofline", "bd_bwd_roofline",
       "bd_tile_overcompute", "bd_noise_ms"}


def test_the_cell_is_the_one_issue_60_names():
    """Entries by name: a later PR appends and this stays true."""
    cell = manifest.cell_of(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "blockdiff-b1x8192", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"]
    assert sorted(entry["reduced"]) == sorted(REDUCED) \
        == sorted(CONFIG["reduced"])
    names = {m["name"] for m in manifest.metrics_for(BENCH, cell, "per_layer")}
    assert NEW | {"train_mfu", "train_step_ms", "train_program_hbm_gb",
                  "train_peak_hbm_gb", "train_unscoped_share",
                  "train_device_idle_share", "train_compiles_in_window",
                  "loss_head_ms", "moe_gmm_roofline", "moe_gmm_share",
                  "moe_dispatch_ms", "moe_rows_max_over_mean",
                  "moe_rows_held_share", "moe_router_ms",
                  "setup_engine_init_s", "setup_first_step_s",
                  "setup_outside_program_s", "setup_compile_s",
                  "setup_programs_compiled", "setup_cache_misses"} <= names
    # no kernel of this step runs under another family's scopes
    assert not [n for n in names if n.startswith((
        "flash_", "swa_", "gdn_", "ssd_", "ssm_", "mla_", "mhc_", "mtp_",
        "collective", "dense_mlp"))]
    e2e = {m["name"] for m in manifest.metrics_for(BENCH, cell, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    for reader in (bd_attn_share, bd_fwd_roofline, bd_bwd_roofline,
                   bd_tile_overcompute, bd_noise_ms):
        m = next(m for m in BENCH["per_layer"] if m["name"] == reader.NAME)
        assert CELL in m["workloads"]
        assert (m["unit"], m["layer"], m["moves"], m["source"]) == (
            reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE)
    assert manifest.problems(BENCH) == []
    assert (TRAFFIC["kind"], TRAFFIC["global_batch"], TRAFFIC["seq_len"],
            TRAFFIC["token_below"], TRAFFIC["batch_pool"],
            TRAFFIC["warmup_steps"], TRAFFIC["fence_lag_steps"],
            TRAFFIC["trace_steps"]) == ("train_steps", 1, L, 18991, 16, 3, 2,
                                        3)
    for key in ("users", "why_in_full"):
        assert TRAFFIC[key], key
    # the ids a batch draws never reach the mask id
    assert sdar.traffic_shapes(CONFIG, False) == {
        "vocab_size": 18991, "max_positions": L, "seq_scale": 1.0}


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's ``config`` for this model, under the same
    key; depth, experts held and vocabulary differ, and are listed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert CONFIG["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if CONFIG[k] != v)
    assert differs == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    published = CONFIG["published"]
    for key in ("head_dim", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts_per_tok"):
        assert key in sdar.WIDTH_KEYS
        assert CONFIG[key] == published[key] == row["config"][key]
    for key in REDUCED:
        assert published[key] == row["config"][key], key
    assert CONFIG["num_experts"] * CONFIG["expert_parallel_size"] == 128
    assert CONFIG["vocab_size"] * 8 == 151936
    assert CONFIG["mask_token_id"] == CONFIG["vocab_size"] - 1
    assert set(CONFIG["changed_why"]) == set(REDUCED)
    assert {"a_block_length", "b_noise_schedule", "c_rope_pairing",
            "d_qk_norm", "e_router", "f_init"} <= set(CONFIG["assumed"])
    assert set(row["not_given"]) == {"block length", "noise schedule"}
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert CONFIG["model"]["remat"] and CONFIG["rehearse_cpu"]
    assert CONFIG["train"]["engine"]["scheduler"]["params"][
        "warmup_num_steps"] == 2000


def test_the_parameter_arithmetic_is_the_initialised_trees():
    """``changed_why``'s numbers against ``jax.eval_shape`` of the model the
    configuration builds."""
    import jax
    import jax.numpy as jnp
    model = sdar._model(CONFIG, rehearse=False)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x)["params"],
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == model.config.num_params() == 645_623_296
    blk = shapes["layers"]["blk"]
    attn = blk["attn"]
    assert sum(count(attn[k]) for k in ("q_proj", "k_proj", "v_proj",
                                        "o_proj")) == 6 * ATTN
    assert ATTN == 18_874_368
    assert count(attn["q_norm"]) + count(attn["k_norm"]) == 6 * 256
    assert attn["q_norm"]["scale"].shape == (6, 128)
    assert count(blk["mlp"]["router"]) == 6 * 262_144
    assert blk["mlp"]["router"].shape == (6, 2048, 128)
    assert blk["mlp"]["gate_proj"].shape == (6, 16, 2048, 768)
    assert EXPERT == 4_718_592 and 16 * EXPERT == 75_497_472
    assert count(blk) == 6 * 94_638_336
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) \
        == 77_791_232
    why = " ".join(CONFIG["changed_why"].values())
    for number in ("18,874,368", "262,144", "4,718,592", "75,497,472",
                   "94,638,336", "77,791,232", "645,623,296", "9.04 GB"):
        assert number in why, number
    assert 645_623_296 * 14 / 1e9 == pytest.approx(9.04, abs=0.005)
    # the published model by the same count: 30.5 B
    import dataclasses
    whole = dataclasses.replace(model.config, n_layers=48, experts_held=0,
                                vocab_size=151936)
    assert whole.num_params() / 1e9 == pytest.approx(30.5, abs=0.05)
    # OLMoE's count did not move
    with open(os.path.join(manifest.HERE, "configs",
                           "olmoe-1b-7b-0125-depth1.json")) as f:
        assert olmoe.model_config(json.load(f), False).num_params() \
            == 625_616_896


@pytest.mark.parametrize("seq,block_length", [(16, 4), (24, 2), (32, 32),
                                              (64, 8), (12, 1)])
def test_the_pair_count_is_the_brute_force_count(seq, block_length):
    """``allowed_pairs`` against the three clauses counted pair by pair."""
    count = 0
    for r in range(2 * seq):
        for s in range(2 * seq):
            hr, hs = r // seq, s // seq
            br, bs = (r % seq) // block_length, (s % seq) // block_length
            count += (hr == 0 and hs == 0 and bs == br) \
                or (hr == 0 and hs == 1 and bs < br) \
                or (hr == 1 and hs == 1 and bs <= br)
    assert sdar.allowed_pairs(seq, block_length) == count
    from benchmark.reference.sdar import allowed
    import jax.numpy as jnp
    assert int(allowed(jnp.arange(2 * seq), seq, block_length).sum()) == count


def test_flops_and_bytes_count_what_this_rank_needs():
    f = sdar
    assert f.allowed_pairs(L, 4) == PAIRS == 67_141_632
    assert f.rows_held_share(CONFIG) == 0.125
    layer = ATTN + 2048 * 128 + 8 * 0.125 * EXPERT
    assert f.layer_matmul_params(CONFIG) == layer
    attention = f.train_attention_flops_per_step(CONFIG, 1, L)
    assert attention == 6 * 32 * 12 * PAIRS * 128
    # ISSUE 60's arithmetic: 19.8 TF of attention, 14.1 of matmuls over the
    # 16,384 rows, 1.9 of head: ~35.8 TF a step, the mask kernels ~55 %
    assert attention / 1e12 == pytest.approx(19.8, abs=0.05)
    assert 6 * 2 * L * 6 * layer / 1e12 == pytest.approx(14.1, abs=0.05)
    assert 6 * L * 18992 * 2048 / 1e12 == pytest.approx(1.9, abs=0.02)
    step = f.train_flops_per_token(CONFIG, L) * L
    assert step == pytest.approx(6 * 2 * L * 6 * layer
                                 + 6 * L * 18992 * 2048 + attention)
    assert step / 1e12 == pytest.approx(35.8, abs=0.1)
    assert attention / step == pytest.approx(0.55, abs=0.01)
    # anything larger — the dense (2L)^2 — would let a share read over 100 %
    assert attention < 6 * 32 * 12 * (2 * L) ** 2 * 128 / 3.9
    # two rows a token, an eighth of the T x 8 assignments
    assert f.moe_gmm_flops_per_step(CONFIG, L) == 6 * 9 * 2 * (
        2 * L * 8 / 8) * 2048 * 768
    rows = 2 * L
    assert f.attention_bytes_per_step(CONFIG, 1, L) == 6 * 2 * rows * (
        (2 * 4096 + 2 * 512) + (4 * 4096 + 4 * 512))
    assert attention / f.attention_bytes_per_step(CONFIG, 1, L) > 240


def test_the_placement_levels_every_batch_and_names_the_cells_pool():
    """``train.expert_placement`` repeats the traffic file's pool, so set-up
    counts the loads of the very batches the window cycles through; and
    ``place_by_load`` deals skewed loads so that each rank is near 1 / ranks
    of EVERY batch, where the columns as born are not."""
    how = CONFIG["train"]["expert_placement"]
    assert {k: how[k] for k in ("batch_pool", "seq_len", "token_below")} \
        == {k: TRAFFIC[k] for k in ("batch_pool", "seq_len", "token_below")}
    assert how["rounds"] >= 2 and len(how["why"]) > 300
    rng = np.random.default_rng(0)
    loads = rng.uniform(0.5, 1.0, (16, 128))
    for b in range(16):                 # eight hot experts a batch, its own
        loads[b, rng.choice(128, 8, replace=False)] += 6.0
    perm = sdar.place_by_load(loads, 8)
    assert sorted(perm) == list(range(128))

    def shares(columns):
        return loads[:, columns].reshape(16, 8, 16).sum(axis=2) \
            / loads.sum(axis=1, keepdims=True)

    dealt, born = shares(perm), shares(np.arange(128))
    assert np.abs(dealt.mean(axis=0) - 0.125).max() < 0.004
    assert np.abs(dealt - 0.125).max() < 0.5 * np.abs(born - 0.125).max()
    assert dealt.std() < 0.4 * born.std()


# --------------------------------------------------------- the tolerance

LOSS, NORM = 9.85, 1.4
TOL = CONFIG["train"]["tolerance"]
LEAVES = {"embed", "lm_head", "norm", "input_norm", "post_attn_norm", "q",
          "k", "v", "o", "q_norm", "k_norm", "router", "gate", "up", "down"}
ASSIGNED = 6 * 2 * L * 8
DIFFERENCES = {
    "routing_differs": int(0.3 * TOL["routing_differs_share"] * ASSIGNED),
    "routing_assignments": ASSIGNED,
    "attn_out_noised_rel": 0.5 * TOL["attn_out_noised_rel"],
    "attn_out_clean_rel": 0.5 * TOL["attn_out_clean_rel"],
    "ffn_out_rel": 0.5 * TOL["ffn_out_rel"],
    "ffn_out_worst_row_rel": 0.2,
    "masked_share": 0.5, "system_grad_norm": NORM,
    "grad_leaf_rel": {n: 0.5 * TOL["grad_leaf_rel"][n] for n in LEAVES}}


def _passes(loss=LOSS, norm=NORM, **differences):
    leaves = dict(DIFFERENCES["grad_leaf_rel"],
                  **differences.pop("grad_leaf_rel", {}))
    checks, _ = sdar.judge_train(
        CONFIG, loss, norm, LOSS, NORM,
        dict(DIFFERENCES, grad_leaf_rel=leaves, **differences))
    return all(checks.values())


def test_an_honest_step_passes_with_room(monkeypatch):
    monkeypatch.delitem(sdar._LIVE, "engine", raising=False)
    assert _passes()
    assert set(TOL["grad_leaf_rel"]) == LEAVES
    assert TOL["why"] and len(TOL["why"]) > 500
    for key in ("loss_abs", "grad_norm_rel", "routing_differs_share",
                "attn_out_noised_rel", "attn_out_clean_rel",
                "ffn_out_rel", "grad_leaf_rel"):
        assert key in TOL["why"], f"no reason given for {key}"
    # the six controls are recorded with readings that fail
    controls = dict(CONFIG["train"]["controls"])
    assert "my chip runs, PR 60" in controls.pop("source")
    assert set(controls) == {"own_block_leaked", "causal_in_block",
                             "weight_dropped", "targets_shifted",
                             "one_t_a_sequence", "lower_precision"}
    for name, control in controls.items():
        assert control["fails"] and control["readings"], name


@pytest.mark.parametrize("fault,kw", [
    ("the 1/t weight dropped / one t a sequence", dict(loss=LOSS - 0.3)),
    ("another key's noise", dict(loss=LOSS + 0.05)),
    ("the own block leaked", dict(attn_out_noised_rel=0.3)),
    ("a causal mask inside a block",
     dict(attn_out_noised_rel=0.2, attn_out_clean_rel=0.2)),
    ("the clean half alone wrong",
     dict(attn_out_clean_rel=2 * TOL["attn_out_clean_rel"])),
    ("QK-norm over the whole projection / no RoPE on the clean copy",
     dict(attn_out_clean_rel=0.4)),
    ("the top-8 not renormalised", dict(ffn_out_rel=0.6)),
    ("a router that reads another tensor", dict(routing_differs=200_000)),
    ("targets shifted by one", dict(grad_leaf_rel={"lm_head": 1.2})),
    ("no expert weight gradient",
     dict(grad_leaf_rel={"gate": 1.0, "up": 1.0, "down": 1.0})),
    ("the mask kernels' dk wrong", dict(grad_leaf_rel={"k": 0.5})),
    ("a leaf the comparison never saw",
     dict(grad_leaf_rel={"q_norm": float("nan")})),
    ("the compared gradients are not the step's",
     dict(system_grad_norm=NORM * 1.05)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_wrong_step_fails(monkeypatch, fault, kw):
    monkeypatch.delitem(sdar._LIVE, "engine", raising=False)
    loss, norm = kw.pop("loss", LOSS), kw.pop("norm", NORM)
    assert not _passes(loss, norm, **kw), fault


# ------------------------------------------------------------ the readers

STEP = "jit(train_batch_fn)/ds_fwd_bwd"
FWD = STEP + "/jvp(LlamaForCausalLM)"
BWD = STEP + "/transpose(jvp(LlamaForCausalLM))"
SCAN = "/layers/while/body/closed_call/checkpoint"
PALLAS = ', custom_call_target="tpu_custom_call"'
# (instruction, op_name, ns): one step of 500 ms on one chip
OPS = [
    ("%bd_fwd.1 = f32[32,16384,128] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/blk/attn/bd_fwd/pallas_call", 60e6),
    ("%bd_bwd.2 = f32[32,96,512,128] custom-call(%a)" + PALLAS,
     BWD + SCAN + "/blk/attn/bd_bwd/pallas_call", 110e6),
    # the sum of the dq partials: under bd_bwd's tag, no Pallas call
    ("%fusion.3 = bf16[32,16384,128] fusion(%a)",
     BWD + SCAN + "/blk/attn/bd_bwd_dq_sum/add", 5e6),
    ("%fusion.4 = s32[1,16384] fusion(%a)", FWD + "/bd_noise/concatenate",
     0.5e6),
    ("%fusion.5 = bf16[1,8192,2048] fusion(%a)", BWD + "/bd_noise/pad",
     1.5e6),
    ("%fusion.6 = f32[16384,128] fusion(%a)",
     FWD + SCAN + "/blk/mlp/moe_router/dot_general", 3e6),
    ("%moe_gmm.7 = bf16[32768,768] custom-call(%a)" + PALLAS,
     FWD + SCAN + "/blk/mlp/moe_gmm/pallas_call", 20e6),
    ("%fusion.8 = bf16[16384,2048] fusion(%a)", FWD + SCAN + "/blk/mlp/add",
     300e6),
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
                        seq_len=L, tokens_per_step=L)
    return record


def test_the_readers_on_a_hand_made_scope_table(monkeypatch):
    monkeypatch.setitem(sdar._LIVE, "gauges",
                        {"attention/bd_tile_overcompute": 1.1244})
    record = _record(sdar)
    assert sr.busiest_chip(record)["busy_ms"] == pytest.approx(500.0)
    # the two Pallas calls; the dq sum beside them is no kernel
    assert bd_attn_share.read(record) == pytest.approx(100 * 170 / 500)
    flops = sdar.train_attention_flops_per_step(CONFIG, 1, L)
    assert bd_fwd_roofline.read(record) == pytest.approx(
        100 * flops / 3 / 197e12 / 0.060)
    assert bd_bwd_roofline.read(record) == pytest.approx(
        100 * 2 * flops / 3 / 197e12 / 0.110)
    assert bd_fwd_roofline.read(record) < 100 / 1.1244
    assert bd_noise_ms.read(record) == pytest.approx(2.0)
    assert bd_tile_overcompute.read(record) == 1.1244
    assert moe_router_ms.read(record) == pytest.approx(3.0)
    assert moe_gmm_roofline.read(record) == pytest.approx(
        100 * sdar.moe_gmm_flops_per_step(CONFIG, L) / 197e12 / 0.020)
    # no kernel of this step runs under the flash scopes
    assert flash_fwd_roofline.read(record) is None


def test_a_program_without_the_scopes_reads_nothing_and_does_not_raise(
        monkeypatch):
    """The parent's side of a traced run: another family's record has no
    ``bd_*`` scope, tag or gauge."""
    monkeypatch.setitem(olmoe._LIVE, "gauges", {})
    with open(os.path.join(manifest.HERE, "configs",
                           "olmoe-1b-7b-0125-depth1.json")) as f:
        record = _record(olmoe, json.load(f))
    for reader in (bd_attn_share, bd_fwd_roofline, bd_bwd_roofline,
                   bd_noise_ms, bd_tile_overcompute):
        assert reader.read(record) is None, reader.NAME
    bare = harness.Record(cell={"name": CELL, "chips": 1}, config=CONFIG,
                          family=sdar, rehearse=False, peaks=None)
    monkeypatch.setitem(sdar._LIVE, "gauges", {})
    for reader in (bd_attn_share, bd_fwd_roofline, bd_bwd_roofline,
                   bd_noise_ms, bd_tile_overcompute):
        assert reader.read(bare) is None, reader.NAME


# ---------------------------------------------------------- the rehearsal

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace):
    """The whole control flow at the rehearsal's sizes: the flow's own
    checks pass (the tolerances are the chip's, set for bf16 at the
    published widths: the float32 comparison is ``tests/test_sdar.py``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--rehearse-cpu", "--trace", str(trace), "--seconds", "1", "--seed",
         "6000000007"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}
    names = set(line["rehearsal_metric_names"])
    # no device plane on the CPU: of the new readers the gauge's alone reads
    assert "bd_tile_overcompute" in names if trace \
        else "train_tokens_per_s" in names
    checks = json.loads(next(
        ln for ln in out.stderr.splitlines()
        if ln.startswith("[benchmark] checks: ")).split(
            "checks: ", 1)[1].split("} {", 1)[0] + "}")
    for name in ("first_loss_matches_reference", "no_routed_row_dropped",
                 "rows_were_masked", "window_losses_finite",
                 "no_compile_in_window", "compared_gradients_are_the_steps"):
        assert checks[name], (name, checks)
