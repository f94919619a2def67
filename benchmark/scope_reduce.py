"""A phase and a kernel name for the train step's device time.

An ``XLA Ops`` event is named by its instruction's text WITHOUT metadata
(``trace_reduce``'s account of the plane), so the scopes the program traces
under (``telemetry.spans.annotate``'s list: the engine's ``ds_fwd_bwd`` and
``ds_optimizer``, and each model's and kernel's own) reach a device event
only through a JOIN: the event's instruction name (``%fusion.430``) is
looked up in the compiled step's text, whose ``metadata={op_name="..."}``
holds the whole path the instruction was traced under —

    jit(train_batch_fn)/ds_fwd_bwd/transpose(jvp(<Model>))/while/body/
        closed_call/h/h/checkpoint/rematted_computation/blk/<module>/mul

— phase (``ds_optimizer``), direction (``jvp(`` / ``transpose(jvp(``),
recomputation (``rematted_computation``) and the flax module or kernel. The
phases are the engine's and JAX's own path elements; which modules and which
kernel scopes a path can hold is the MODEL's, so the tags come from the
cell's family (``KERNEL_TAGS``, ``MODULE_TAGS``) and none is written here. The
text is ``record.compiled_text`` (the executable that ran, from the
executable cache). An instruction the text does not have, or has under
another opcode or shape, is ``unscoped``: a text that is not the program that
ran must not lend its names (PERF.md Findings 1 saw an AOT text's ``%copy.44``
be ``%copy.41`` on the chip).
"""

import collections
import re

from benchmark import readers, roofline, trace_reduce

PHASES = ("forward", "backward", "recompute", "optimizer", "unscoped")
SLOT = "scope_attribution"                  # where record.extra keeps it
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?((%[^\s=]+) = .*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_table(compiled_text):
    """{"%name": (op_name or "", label)} for every instruction line of every
    computation of the module (instruction names are unique in a module)."""
    table = {}
    for text, name in _INSTRUCTION.findall(compiled_text):
        op_name = _OP_NAME.search(text)
        table[name] = (op_name.group(1) if op_name else "",
                       trace_reduce.label(text))
    return table


def phase_of(op_name):
    if "/ds_optimizer" in op_name:
        return "optimizer"
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(jvp(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "unscoped"


def tag_of(op_name, family):
    """The kernel or module an ``op_name`` path runs through: one of the
    family's kernel scopes first (a path element that STARTS with it, so a
    kernel's variants share its tag), then the first of the family's module
    tags that is an element."""
    parts = op_name.split("/")
    for tag in family.KERNEL_TAGS:
        if any(p.startswith(tag) for p in parts):
            return tag
    return next((tag for tag in family.MODULE_TAGS if tag in parts), "-")


def place(table, event_name, family):
    """(phase, tag, op_name) of a device event; ``unscoped`` where the table
    does not hold the event's instruction under the same opcode and shape."""
    op_name, label = table.get(event_name.partition(" = ")[0], ("", None))
    if label != trace_reduce.label(event_name):
        return "unscoped", "-", ""
    return phase_of(op_name), tag_of(op_name, family), op_name


def kind_of(event_name):
    if trace_reduce.is_pallas(event_name):
        return "pallas"
    return "collective" if trace_reduce.is_collective(event_name) else "op"


def chip_attribution(events, table, steps, family):
    """One chip's slice, in ms a step: self time (a ``while`` counts only
    what its body does not cover) by (phase, tag, kind), by phase, the
    Pallas time of each of the family's kernel tags, and the heaviest single
    instructions that are unscoped or collectives."""
    rows = collections.Counter()
    unscoped, collectives = collections.Counter(), collections.Counter()
    for i, ns in trace_reduce.self_times(events).items():
        name = events[i].name
        phase, tag, op_name = place(table, name, family)
        kind = kind_of(name)
        rows[phase, tag, kind] += ns
        if phase == "unscoped":
            unscoped[trace_reduce.label(name),] += ns
        if kind == "collective":
            collectives[trace_reduce.label(name), phase, tag, op_name] += ns
    per_step = 1e6 * max(steps, 1)

    def ms(counter, n=None):
        return [[*key, ns / per_step] for key, ns in counter.most_common(n)]

    phases = {p: 0.0 for p in PHASES}
    kernels = {k: 0.0 for k in family.KERNEL_TAGS}
    for (phase, tag, kind), ns in rows.items():
        phases[phase] += ns / per_step
        if kind == "pallas" and tag in kernels:
            kernels[tag] += ns / per_step
    return {"steps": steps,
            "busy_ms": trace_reduce.union_ns(events) / per_step,
            "phase_ms": phases, "kernel_ms": kernels, "rows": ms(rows),
            "heaviest_unscoped": ms(unscoped, 10),
            "heaviest_collectives": ms(collectives, 10)}


def attribution(record):
    """{"chip": the plane with most busy time, "chips": {plane:
    chip_attribution}} of the traced slice, computed once and kept in
    ``record.extra`` so that ``harness.write_detail`` writes the table out;
    None without a device plane or the compiled text."""
    if SLOT in record.extra:
        return record.extra[SLOT]
    if not readers.traced(record) or not record.compiled_text:
        return None
    table = scope_table(record.compiled_text)
    t0, t1 = record.slice
    chips = {}
    for plane in record.planes():
        events = trace_reduce.clip(
            trace_reduce.ops(record.trace, plane), t0, t1)
        steps = len(trace_reduce.modules(record.trace, plane,
                                         record.extra["step_module"]))
        chips[plane] = chip_attribution(events, table, steps, record.family)
    busiest = max(chips, key=lambda p: chips[p]["busy_ms"])
    record.extra[SLOT] = {"chip": busiest, "chips": chips}
    return record.extra[SLOT]


def busiest_chip(record):
    """The chip all seven metrics are read from, so that they add up."""
    found = attribution(record)
    return found["chips"][found["chip"]] if found else None


def phase_ms(record, phase):
    chip = busiest_chip(record)
    return chip["phase_ms"][phase] if chip else None


def kernel_ms(record, tags):
    """{chip: Pallas ms a step under ``tags`` together}; None where no chip
    ran a kernel under any of them (a program without the scope, a family
    that does not list it, no trace)."""
    found = attribution(record)
    if not found:
        return None
    out = {plane: sum(chip["kernel_ms"].get(t, 0.0) for t in tags)
           for plane, chip in found["chips"].items()}
    return out if any(out.values()) else None


def kernel_roofline(record, tag, needed, peak_per_s):
    """A kernel's share of its roofline: ``needed`` operations (or bytes) a
    step on one chip, counted by the caller from ``roofline.py`` and its
    family, over ``peak_per_s`` (the row of ``record.peaks`` that bounds the
    kernel), over the Pallas time under ``tag`` on the busiest chip. None
    where no event carries the tag."""
    per_chip = kernel_ms(record, (tag,))
    if not per_chip:
        return None
    busiest = attribution(record)["chip"]
    return roofline.share(needed, peak_per_s, per_chip[busiest] / 1e3)
