"""``scope_reduce``: the join of device events to the compiled text's scopes,
on a hand-written text and hand-made events. No chip, no compile. The tags
are a family's: GPT-2's here, and a stand-in's with a kernel of its own."""

import json
import os
import types

import pytest

from benchmark import harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import gpt2 as family
from benchmark.layer_metrics import (flash_attn_roofline, flash_attn_share,
                                     flash_bwd_roofline, flash_fwd_roofline,
                                     train_bwd_ms, train_fwd_ms,
                                     train_optimizer_ms, train_recompute_ms,
                                     train_unscoped_share)

STEP = "jit(train_batch_fn)"
FWD = STEP + "/ds_fwd_bwd/jvp(GPT2LMHeadModel)"
BWD = STEP + "/ds_fwd_bwd/transpose(jvp(GPT2LMHeadModel))"
BODY = "/while/body/closed_call/h"
# the paths quoted in ISSUE 24, letter for letter as the CPU compile gave them
P_OPT = STEP + "/ds_optimizer/mul"
P_FWD = FWD + BODY + "/blk/attn/c_attn/add"
P_BWD = BWD + BODY + "/h/checkpoint/blk/ln_2/mul"
P_REMAT = BWD + BODY + "/h/checkpoint/rematted_computation/blk/ln_2/mul"
P_FLASH_BWD = BWD + BODY + "/h/checkpoint/blk/attn/flash_bwd/pallas_call"
P_FLASH_FWD_4CHIP = FWD + BODY + "/blk/attn/shard_map/flash_fwd/pallas_call"
P_A2A = BWD + BODY + "/h/checkpoint/blk/mlp/add_any"

TEXT = f"""HloModule jit_train_batch_fn, is_scheduled=true

%fused_computation.7 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  ROOT %multiply.3 = f32[8]{{0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{P_OPT}"}}
}}

%body.2 (arg: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {{
  %arg = (s32[], bf16[8,64]{{1,0}}) parameter(0)
  %fusion.9 = bf16[8,64]{{1,0:T(8,128)(2,1)}} fusion(%arg), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{P_REMAT}" source_file="gpt2.py" source_line=270}}
  %flash_bwd.1 = (f32[4,8,64]{{2,1,0}}, bf16[4,8,64]{{2,1,0}}) custom-call(%fusion.9), custom_call_target="tpu_custom_call", metadata={{op_name="{P_FLASH_BWD}"}}
  %all-to-all.14 = bf16[4,8,4,16]{{3,2,1,0}} all-to-all(%fusion.9), dimensions={{0}}, metadata={{op_name="{P_A2A}"}}
  %copy.5 = bf16[8,64]{{0,1}} copy(%fusion.9)
  ROOT %tuple.4 = (s32[], bf16[8,64]{{1,0}}) tuple(%arg, %copy.5)
}}

ENTRY %main.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  %fusion.1 = bf16[8,64]{{1,0}} fusion(%p), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{P_FWD}"}}
  %while.2 = (s32[], bf16[8,64]{{1,0}}) while(%fusion.1), condition=%cond.2, body=%body.2, metadata={{op_name="{BWD}/while"}}
  ROOT %fusion.7 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{P_OPT}"}}
}}
"""


def test_scope_table_reads_every_instruction_line():
    table = sr.scope_table(TEXT)
    assert set(table) == {
        "%param_0.1", "%multiply.3", "%arg", "%fusion.9", "%flash_bwd.1",
        "%all-to-all.14", "%copy.5", "%tuple.4", "%p", "%fusion.1",
        "%while.2", "%fusion.7"}
    assert table["%fusion.9"] == (P_REMAT, "%fusion.9 fusion bf16[8,64]")
    assert table["%flash_bwd.1"] == (
        P_FLASH_BWD, "%flash_bwd.1 custom-call f32[4,8,64]")
    assert table["%all-to-all.14"] == (
        P_A2A, "%all-to-all.14 all-to-all bf16[4,8,4,16]")
    assert table["%while.2"][0] == BWD + "/while"
    assert table["%copy.5"] == ("", "%copy.5 copy bf16[8,64]")   # no metadata
    assert table["%multiply.3"][0] == P_OPT                       # a ROOT line


@pytest.mark.parametrize("op_name,phase,tag", [
    (P_OPT, "optimizer", "-"),
    (P_FWD, "forward", "attn"),
    (P_BWD, "backward", "ln_2"),
    (P_REMAT, "recompute", "ln_2"),
    (P_FLASH_BWD, "backward", "flash_bwd"),
    (P_FLASH_FWD_4CHIP, "forward", "flash_fwd"),
    (P_A2A, "backward", "mlp"),
    (BWD + "/ds_loss_head/while/body/closed_call/checkpoint/"
     "rematted_computation/reduce_max", "recompute", "ds_loss_head"),
    (FWD + "/ds_embed/gather", "forward", "ds_embed"),
    (FWD + "/ln_f/add", "forward", "ln_f"),
    (BWD + BODY + "/h/checkpoint/blk/attn/flash_bwd_dkv/pallas_call",
     "backward", "flash_bwd"),
    (STEP + "/ds_fwd_bwd/convert_element_type", "unscoped", "-"),
    ("", "unscoped", "-"),
])
def test_phase_and_tag_of_a_path(op_name, phase, tag):
    assert (sr.phase_of(op_name), sr.tag_of(op_name, family)) == (phase, tag)


def ev(name, a, b):
    return tr.Event(name, float(a), float(b))


PALLAS = ', custom_call_target="tpu_custom_call"'
# one step of 1000 ns: the names are the device plane's (operand shapes
# spelled out, no metadata); the while covers its body's events
EVENTS = [
    ev("%fusion.1 = bf16[8,64]{1,0} fusion(f32[8]{0} %p), kind=kOutput",
       0, 100),
    ev("%while.2 = (s32[], bf16[8,64]{1,0}) while((s32[]) %fusion.1), "
       "body=%body.2", 100, 800),
    ev("%fusion.9 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion((s32[]) %arg)",
       110, 310),
    ev("%flash_bwd.1 = (f32[4,8,64]{2,1,0}, bf16[4,8,64]{2,1,0}) "
       "custom-call(bf16[8,64]{1,0} %fusion.9)" + PALLAS, 310, 610),
    ev("%all-to-all.14 = bf16[4,8,4,16]{3,2,1,0} all-to-all(bf16[8,64] "
       "%fusion.9), dimensions={0}", 610, 700),
    ev("%copy.5 = bf16[8,64]{0,1} copy(bf16[8,64]{1,0} %fusion.9)", 700, 780),
    # same name as the text's %fusion.7, another shape: not that program's
    ev("%fusion.7 = f32[16]{0} fusion(f32[16]{0} %p), kind=kLoop", 800, 850),
    ev("%fusion.77 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 850, 900),
    ev("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 900, 1000),
]


def test_events_join_the_table_and_nested_time_counts_once():
    chip = sr.chip_attribution(EVENTS, sr.scope_table(TEXT), 1, family)
    ms = {tuple(r[:3]): r[3] * 1e6 for r in chip["rows"]}      # back to ns
    assert ms == {
        ("forward", "attn", "op"): 100.0,
        ("backward", "-", "op"): pytest.approx(30.0),    # the while's own time
        ("recompute", "ln_2", "op"): 200.0,
        ("backward", "flash_bwd", "pallas"): 300.0,
        ("backward", "mlp", "collective"): 90.0,
        ("unscoped", "-", "op"): pytest.approx(80.0 + 50.0 + 50.0),
        ("optimizer", "-", "op"): 100.0}
    # a label that differs and a name the text lacks are unscoped, by name
    assert {u[0]: u[1] * 1e6 for u in chip["heaviest_unscoped"]} == {
        "%copy.5 copy bf16[8,64]": pytest.approx(80.0),
        "%fusion.7 fusion f32[16]": pytest.approx(50.0),
        "%fusion.77 fusion f32[8]": pytest.approx(50.0)}
    assert chip["heaviest_collectives"] == [[
        "%all-to-all.14 all-to-all bf16[4,8,4,16]", "backward", "mlp", P_A2A,
        pytest.approx(90e-6)]]
    # the five phases are the busy time, and the kernels' time is told apart
    assert sum(chip["phase_ms"].values()) == pytest.approx(chip["busy_ms"])
    assert chip["busy_ms"] == pytest.approx(1000e-6)
    assert chip["kernel_ms"] == {"flash_fwd": 0.0,
                                 "flash_bwd": pytest.approx(300e-6)}


def _record(events_by_plane, modules=2, family=family):
    """A traced training Record of GPT-2 large's cell over hand-made planes:
    each plane runs ``modules`` steps that span its events."""
    with open(os.path.join(manifest.HERE, "configs",
                           "gpt2-large-774m.json")) as f:
        config = json.load(f)
    devices = {}
    for plane, events in events_by_plane.items():
        t0, t1 = events[0].start, max(e.end for e in events)
        half = (t0 + t1) / 2
        mods = [ev("jit_train_batch_fn(1)", t0, half),
                ev("jit_train_batch_fn(1)", half, t1)][:modules]
        devices[plane] = {"XLA Ops": events, "XLA Modules": mods}
    record = harness.Record(
        cell={"name": "gpt2l-train-1chip", "chips": 1}, config=config,
        family=family, rehearse=False,
        peaks={"bf16_flops_per_s": 197e12}, compiled_text=TEXT)
    record.trace = tr.Trace(devices, {})
    record.slice = (0.0, max(e.end for evs in events_by_plane.values()
                             for e in evs))
    record.extra.update(step_module="jit_train_batch_fn", global_batch=8,
                        seq_len=1024)
    return record


def test_phase_metrics_are_ms_a_step_of_the_busiest_chip_and_add_up():
    idle_chip = [ev(EVENTS[0].name, 0, 40)]
    record = _record({"/device:TPU:0": idle_chip, "/device:TPU:1": EVENTS})
    got = {m.NAME: m.read(record) for m in (
        train_fwd_ms, train_bwd_ms, train_recompute_ms, train_optimizer_ms)}
    # two steps in the slice: half of each phase's 100 / 420 / 200 / 100 ns
    assert got == {"train_fwd_ms": pytest.approx(50e-6),
                   "train_bwd_ms": pytest.approx(210e-6),
                   "train_recompute_ms": pytest.approx(100e-6),
                   "train_optimizer_ms": pytest.approx(50e-6)}
    assert train_unscoped_share.read(record) == pytest.approx(18.0)
    kept = record.extra[sr.SLOT]              # what write_detail writes out
    assert kept["chip"] == "/device:TPU:1" and len(kept["chips"]) == 2
    busy = kept["chips"]["/device:TPU:1"]["busy_ms"]
    assert sum(got.values()) + 0.18 * busy == pytest.approx(busy)
    json.dumps(kept)


def test_kernel_rooflines_split_the_attention_flops_one_to_two():
    # forward 30.6 ms and backward 43.3 ms a step (the ledger's breakdown of
    # gpt2l-train-1chip): 36 layers x 64,424,509,440 flops a step, a third
    # forward: 773,094,113,280 / 197e12 / 0.0306 s = 12.82 %, and two thirds
    # backward: 1,546,188,226,560 / 197e12 / 0.0433 s = 18.13 %
    fwd = ("%flash_fwd.1 = bf16[4,8,64]{2,1,0} custom-call(bf16[8,64] %x)"
           + PALLAS)
    text = TEXT.replace("%flash_bwd.1 = (f32[4,8,64]", (
        '%flash_fwd.1 = bf16[4,8,64]{2,1,0} custom-call(%arg), '
        f'metadata={{op_name="{P_FLASH_FWD_4CHIP}"}}\n'
        "  %flash_bwd.1 = (f32[4,8,64]"))
    record = _record({"/device:TPU:0": [
        ev(fwd, 0, 30.6e6), ev(EVENTS[3].name, 30.6e6, 73.9e6)]}, modules=1)
    record.compiled_text = text
    assert flash_fwd_roofline.read(record) == pytest.approx(12.82, abs=0.01)
    assert flash_bwd_roofline.read(record) == pytest.approx(18.13, abs=0.01)
    secs = tr.time_where(record.trace.devices["/device:TPU:0"]["XLA Ops"],
                         tr.is_pallas) / 1e9
    kernels = record.extra[sr.SLOT]["chips"]["/device:TPU:0"]["kernel_ms"]
    assert sum(kernels.values()) / 1e3 == pytest.approx(secs)
    # forward + backward together: every Pallas event here is a flash kernel,
    # so selecting by scope reads what selecting every Pallas call read
    # (the ledger's 16.86 % / 15.93 % of this cell are these 73.9 ms)
    assert flash_attn_share.read(record) == pytest.approx(100.0)
    assert flash_attn_roofline.read(record) == pytest.approx(
        100 * 36 * 64_424_509_440 / 197e12 / secs)
    assert flash_attn_roofline.read(record) == pytest.approx(15.93, abs=0.01)


# a later family: GPT-2's modules under LLaMA-style names, the flash kernels
# and one Pallas kernel of its own under the scope ``moe_gmm``
OTHER = types.SimpleNamespace(
    KERNEL_TAGS=("flash_fwd", "flash_bwd", "moe_gmm"),
    MODULE_TAGS=("ds_loss_head", "self_attn", "experts", "input_norm"),
    train_attention_flops_per_step=family.train_attention_flops_per_step)
P_GMM = (BWD.replace("GPT2LMHeadModel", "Other") + BODY
         + "/layers/checkpoint/blk/experts/moe_gmm_bwd/pallas_call")


def test_a_familys_own_kernel_tag_gets_a_row_and_a_roofline_of_its_count():
    """What a model_config PR needs: its family lists the scope, the shared
    reducer gives it ``kernel_ms``, and its reader passes its own count of
    bytes (or flops) with the peak that bounds the kernel."""
    assert sr.tag_of(P_GMM, OTHER) == "moe_gmm"
    assert sr.tag_of(P_GMM, family) == "-"        # GPT-2 does not list it
    assert sr.tag_of(FWD.replace("GPT2LMHeadModel", "Other") + BODY
                     + "/layers/blk/input_norm/mul", OTHER) == "input_norm"
    gmm = ("%moe_gmm_bwd.2 = bf16[64,2048,1024]{2,1,0} custom-call(bf16[8,64] "
           "%x)" + PALLAS)
    text = TEXT.replace("%flash_bwd.1 = (f32[4,8,64]", (
        '%moe_gmm_bwd.2 = bf16[64,2048,1024]{2,1,0} custom-call(%arg), '
        f'metadata={{op_name="{P_GMM}"}}\n'
        "  %flash_bwd.1 = (f32[4,8,64]"))
    events = {"/device:TPU:0": [ev(gmm, 0, 10e6),
                                ev(EVENTS[3].name, 10e6, 53.3e6)]}
    record = _record(events, modules=1, family=OTHER)
    record.compiled_text = text
    chip = sr.busiest_chip(record)
    assert chip["kernel_ms"] == {"flash_fwd": 0.0,
                                 "flash_bwd": pytest.approx(43.3),
                                 "moe_gmm": pytest.approx(10.0)}
    assert ["backward", "experts", "pallas",
            pytest.approx(10.0)] not in chip["rows"]      # the kernel tag wins
    assert ["backward", "moe_gmm", "pallas", pytest.approx(10.0)] \
        in chip["rows"]
    # 4.095 GB moved in 10 ms is half of 819 GB/s
    assert sr.kernel_roofline(record, "moe_gmm", 4.095e9, 819e9) \
        == pytest.approx(50.0)
    assert sr.kernel_roofline(record, "no_such_scope", 1.0, 1.0) is None
    # the flash readers no longer take every Pallas call for a flash kernel:
    # 43.3 of the 53.3 busy ms, where ``is_pallas`` would read 100 %
    assert flash_attn_share.read(record) == pytest.approx(100 * 43.3 / 53.3)
    assert flash_attn_roofline.read(record) == pytest.approx(
        100 * 36 * 64_424_509_440 / 197e12 / 0.0433)
    # under GPT-2's tags the same trace has no ``moe_gmm`` row at all
    as_gpt2 = _record(events, modules=1)
    as_gpt2.compiled_text = text
    assert set(sr.busiest_chip(as_gpt2)["kernel_ms"]) == {"flash_fwd",
                                                          "flash_bwd"}


def test_a_program_without_the_scopes_or_a_run_without_a_plane_reads_none():
    # the parent's program: both kernels sit under ``attn``, no kernel scope
    parent = TEXT.replace("/flash_bwd/pallas_call", "/pallas_call")
    record = _record({"/device:TPU:0": EVENTS})
    record.compiled_text = parent
    assert flash_bwd_roofline.read(record) is None
    assert flash_fwd_roofline.read(record) is None
    assert flash_attn_share.read(record) is None
    assert flash_attn_roofline.read(record) is None
    assert train_bwd_ms.read(record) == pytest.approx(210e-6)
    rehearsal = _record({"/device:TPU:0": EVENTS})
    rehearsal.trace = tr.Trace({}, {})
    for m in (train_fwd_ms, train_unscoped_share, flash_fwd_roofline,
              flash_attn_share, flash_attn_roofline):
        assert m.read(rehearsal) is None
    assert sr.SLOT not in rehearsal.extra
