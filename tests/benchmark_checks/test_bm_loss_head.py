"""``loss_head_ms`` (ISSUE 51): the manifest's entry found by NAME, the
reader on a hand-made scope table — every phase summed, the busiest chip's
rows and no other's, None where nothing ran under ``ds_loss_head`` or
nothing was traced — and the tag in every family's ``MODULE_TAGS``."""

import pytest

from benchmark import harness, manifest, scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.families import (deepseek_v3, gpt2, granite_hybrid, laguna,
                                nemotron_h, olmoe, qwen3_next, smallthinker)
from benchmark.layer_metrics import loss_head_ms

BENCH = manifest.load()
FAMILIES = [gpt2, olmoe, qwen3_next, laguna, smallthinker, nemotron_h,
            deepseek_v3, granite_hybrid]
JIT = "jit(train_batch_fn)/ds_fwd_bwd/"
FWD = JIT + "jvp(Model)/ds_loss_head/while/body/closed_call"
BWD = JIT + "transpose(jvp(Model))/ds_loss_head"
# what a program that derives the logits twice (before PR 51) also has
REC = BWD + "/while/body/closed_call/checkpoint/rematted_computation"
# (instruction, the path it was traced under, ns in a step of 1 s)
HEAD = [
    ("%fusion.1 = bf16[1024] fusion(%a)", FWD + "/dot_general", 50e6),
    ("%fusion.2 = bf16[50304,2048] fusion(%a)", FWD + "/dot_general", 60e6),
    ("%fusion.3 = bf16[16,1024,2048] fusion(%a)", BWD + "/mul", 4e6),
    ("%fusion.4 = bf16[1024] fusion(%a)", REC + "/dot_general", 30e6),
]
REST = [
    ("%fusion.5 = bf16[16384,2048] fusion(%a)",
     JIT + "jvp(Model)/layers/blk/attn/o_proj/dot_general", 500e6),
    ("%fusion.6 = f32[50304,2048] fusion(%a)",
     "jit(train_batch_fn)/ds_optimizer/mul", 100e6),
]


def _record(family, planes):
    """``planes``: {plane name: [(instruction, op_name, ns)]}; every
    plane runs one step module that covers its ops."""
    text = "HloModule jit_train_batch_fn\n\nENTRY %main (a: f32[8]) -> f32[8] {\n"
    for name, op_name, _ in {op[0]: op for ops in planes.values()
                             for op in ops}.values():
        text += f'  {name}, metadata={{op_name="{op_name}"}}\n'
    text += "}\n"
    record = harness.Record(
        cell={"name": "olmoe-train-1chip-s4096", "chips": len(planes)},
        config={}, family=family, rehearse=False, compiled_text=text,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    lines, end = {}, 0.0
    for plane, ops in planes.items():
        events, t = [], 0.0
        for name, _, ns in ops:
            events.append(tr.Event(name, t, t + ns))
            t += ns
        lines[plane] = {"XLA Ops": events, "XLA Modules": [
            tr.Event("jit_train_batch_fn(1)", 0.0, t)]}
        end = max(end, t)
    record.trace = tr.Trace(lines, {})
    record.slice = (0.0, end)
    record.extra.update(step_module="jit_train_batch_fn", global_batch=4,
                        seq_len=4096, tokens_per_step=16384)
    return record


def test_the_entry_is_the_one_issue_51_names():
    """By name: a later PR appends and this stays true."""
    assert manifest.problems(BENCH) == []
    m = next(m for m in BENCH["per_layer"] if m["name"] == "loss_head_ms")
    assert (m["name"], m["unit"], m["layer"], m["moves"], m["source"]) == (
        loss_head_ms.NAME, loss_head_ms.UNIT, loss_head_ms.LAYER,
        loss_head_ms.MOVES, loss_head_ms.SOURCE) == (
        "loss_head_ms", "ms", "train step program", "train_tokens_per_s",
        "device_trace")
    assert m["better"] == "lower"
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved["workloads"])
    # the layer is one the accepted benchmark names, letter for letter
    assert m["layer"] in {o["layer"] for o in BENCH["per_layer"]
                          if o["name"] == "train_recompute_ms"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_training_cell_reports_it(cell):
    names = {m["name"] for m in manifest.metrics_for(
        BENCH, manifest.cell_of(BENCH, cell), "per_layer")}
    assert {"loss_head_ms", "train_recompute_ms", "train_step_ms"} <= names


@pytest.mark.parametrize("family", FAMILIES,
                         ids=lambda f: f.__name__.rsplit(".", 1)[-1])
def test_the_reader_sums_every_phase_of_the_heads_scope(family):
    """Every family's ``MODULE_TAGS`` holds ``ds_loss_head``: forward,
    backward and the recomputation of a program that has one."""
    assert loss_head_ms.TAG in family.MODULE_TAGS
    record = _record(family, {"/device:TPU:0": HEAD + REST})
    assert loss_head_ms.read(record) == pytest.approx(50 + 60 + 4 + 30)
    rows = {(p, t): ms for p, t, _, ms in sr.busiest_chip(record)["rows"]}
    assert rows["forward", "ds_loss_head"] == pytest.approx(110.0)
    assert rows["backward", "ds_loss_head"] == pytest.approx(4.0)
    assert rows["recompute", "ds_loss_head"] == pytest.approx(30.0)
    once = _record(family, {"/device:TPU:0": HEAD[:3] + REST})
    assert loss_head_ms.read(once) == pytest.approx(114.0)
    assert sr.phase_ms(once, "recompute") == 0


def test_the_reader_reads_the_busiest_chip():
    """Four chips, each a quarter of the head: the chip with most busy
    time is read, as the phases are, so that they add up."""
    slow = [(n, o, 2 * ns) for n, o, ns in HEAD[:3] + REST]
    record = _record(gpt2, {"/device:TPU:0": HEAD[:3] + REST,
                            "/device:TPU:1": slow})
    assert sr.attribution(record)["chip"] == "/device:TPU:1"
    assert loss_head_ms.read(record) == pytest.approx(2 * 114.0)


def test_a_run_without_the_scope_or_without_a_trace_reads_nothing():
    """None, and nothing raised: a program none of whose instructions ran
    under ``ds_loss_head``, and a run that was not traced."""
    assert loss_head_ms.read(_record(olmoe, {"/device:TPU:0": REST})) is None
    untraced = harness.Record(
        cell={"name": "olmoe-train-1chip-s4096", "chips": 1}, config={},
        family=olmoe, rehearse=False, peaks=None)
    untraced.extra.update(tokens_per_step=16384, global_batch=4,
                          seq_len=4096)
    assert loss_head_ms.read(untraced) is None
