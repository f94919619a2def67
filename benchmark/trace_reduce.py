"""From a profiler trace to numbers: the one reduction every PR uses.

Reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and nothing else.
How a v5e trace is laid out (jax 0.9.0 / libtpu 0.0.34, looked at by hand in
PR 22 with ``benchmark/tools/trace_look.py``):

* one plane ``/device:TPU:<n>`` per chip. Its line ``XLA Modules`` has one
  event per launched executable, named ``jit_<fn>(<fingerprint>)``. Its line
  ``XLA Ops`` is what the TensorCore executes: one event per HLO
  instruction, named by the instruction's text
  (``%fusion.430 = bf16[...] fusion(...), kind=...``), NESTED — a ``while``
  covers the events of its body. ``Async XLA Ops`` holds the in-flight
  spans of ``*-start``/``*-done`` pairs (DMA, collectives) that overlap the
  core; they are not core time. ``Steps`` groups modules.
* a Pallas kernel is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"``. Its instruction is named after
  the innermost scope it was traced under (``%attn.13``), NOT after the
  kernel function: the device plane cannot tell two kernels of one scope
  apart except by their operand shapes.
* the plane ``/host:CPU`` has one line per thread; ``python`` carries every
  ``TraceAnnotation`` (``telemetry/spans.span`` and the harness's own) and
  the Python frames. Device time runs about a millisecond ahead of the host
  clock; ``clock_skew_ns`` estimates it from launches that found the device
  idle, and gap attribution shifts by it.

All times are nanoseconds as the profiler gives them.
"""

import collections
import re
from typing import NamedTuple

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
CONTAINER_OPS = ("while", "conditional", "call")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"([a-z][a-z0-9\-]*)\(")


class Event(NamedTuple):
    name: str
    start: float
    end: float

    @property
    def dur(self):
        return self.end - self.start


class Trace(NamedTuple):
    devices: dict       # plane name -> {line name -> [Event] sorted by start}
    host: dict          # thread line name -> [Event] sorted by start


def load(xplane_path):
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(xplane_path))


def from_profile(profile):
    devices, host = {}, {}
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            target = devices.setdefault(plane.name, {})
        elif plane.name == "/host:CPU":
            target = host
        else:
            continue
        for line in plane.lines:
            evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            evs.sort(key=lambda e: (e.start, -e.end))
            target.setdefault(line.name, []).extend(evs)
    return Trace(devices, host)


# ------------------------------------------------------------- instructions

def opcode(name):
    """HLO opcode of an ``XLA Ops`` event name (``fusion``, ``custom-call``,
    ``all-gather-start`` ...); the name itself when it is not an
    instruction's text."""
    _, eq, rest = name.partition(" = ")
    m = _OPCODE.search(rest) if eq else None
    return m.group(1) if m else name


def label(name, width=96):
    """A short, stable label of an instruction: its name, opcode and first
    result shape (``%attn.13 custom-call bf16[160,1024,64]``)."""
    instr, eq, rest = name.partition(" = ")
    if not eq:
        return name[:width]
    shape = re.search(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return f"{instr} {opcode(name)} {shape.group(0) if shape else ''}"[:width]


def is_collective(name):
    """A collective instruction — synchronous, or the ``-start``/``-done``
    of an asynchronous one. The core's self time in these is the collective
    time NOT hidden behind compute: what overlaps runs on the ``Async XLA
    Ops`` line while the core executes other instructions."""
    op = opcode(name)
    return any(op == c or op == c + "-start" or op == c + "-done"
               for c in COLLECTIVE_OPS)


def is_pallas(name):
    return PALLAS_TARGET in name


# ---------------------------------------------------------------- intervals

def clip(events, t0, t1):
    return [Event(e.name, max(e.start, t0), min(e.end, t1))
            for e in events if e.end > t0 and e.start < t1]


def union_ns(events):
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start):
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events, t0, t1):
    """Idle intervals of [t0, t1) as Events named "" — where no event runs."""
    out, cur = [], t0
    for e in sorted(clip(events, t0, t1), key=lambda e: e.start):
        if e.start > cur:
            out.append(Event("", cur, e.start))
        cur = max(cur, e.end)
    if cur < t1:
        out.append(Event("", cur, t1))
    return out


def self_times(events):
    """{event index: self ns} for NESTED events of one line: an event's
    duration minus what the events nested directly inside it cover."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    selfs = {i: events[i].dur for i in order}
    stack = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= min(e.end, events[stack[-1]].end) - e.start
        stack.append(i)
    return selfs


def self_time_by(events, key):
    """Sum of self time grouped by ``key(event.name)`` (None drops it)."""
    out = collections.Counter()
    for i, ns in self_times(events).items():
        k = key(events[i].name)
        if k is not None:
            out[k] += ns
    return out


def time_where(events, pred):
    """Total self time of the events whose name satisfies ``pred``."""
    return sum(ns for i, ns in self_times(events).items()
               if pred(events[i].name))


# ------------------------------------------------------------------- device

def ops(trace, plane):
    return trace.devices[plane].get("XLA Ops", [])


def modules(trace, plane, prefix=None):
    evs = trace.devices[plane].get("XLA Modules", [])
    return [e for e in evs if prefix is None or e.name.startswith(prefix)]


def window_of(trace):
    """[t0, t1) spanned by the device modules of every chip."""
    evs = [e for p in trace.devices for e in modules(trace, p)]
    if not evs:
        return None
    return min(e.start for e in evs), max(e.end for e in evs)


def busy_ns(trace, plane, t0, t1):
    """ns of [t0, t1) in which an operation ran on this chip's core."""
    return union_ns(clip(ops(trace, plane), t0, t1))


def step_starts_ms(trace, plane, prefix):
    """Device ms from each ``prefix`` module's first op to the next one's."""
    starts = [e.start for e in modules(trace, plane, prefix)]
    return [(b - a) / 1e6 for a, b in zip(starts, starts[1:])]


def top_ops(trace, plane, t0, t1, n=10):
    """The n instructions with most self time in [t0, t1), as
    [label, seconds], containers (while/conditional/call) left out."""
    by = self_time_by(
        clip(ops(trace, plane), t0, t1),
        lambda nm: None if opcode(nm) in CONTAINER_OPS else label(nm))
    return [[k, ns / 1e9] for k, ns in by.most_common(n)]


# --------------------------------------------------------------------- host

def clock_skew_ns(trace, plane):
    """How far the device's clock runs ahead of the host's: a module cannot
    start before the host issued it, so the most negative
    (module start - k-th ``tpu::System::Execute`` start) is the skew. 0 when
    launches and modules cannot be paired one to one."""
    issued = [e for evs in trace.host.values() for e in evs
              if e.name == "tpu::System::Execute"]
    issued.sort(key=lambda e: e.start)
    mods = modules(trace, plane)
    if not mods or len(issued) != len(mods):
        return 0.0
    return max(0.0, -min(m.start - h.start for m, h in zip(mods, issued)))


def annotations(trace, prefixes):
    """Host events on any thread whose name starts with one of
    ``prefixes`` (the harness's and the program's TraceAnnotations)."""
    return sorted((e for evs in trace.host.values() for e in evs
                   if e.name.startswith(tuple(prefixes))),
                  key=lambda e: e.start)


def attribute_gaps(idle, spans, skew_ns=0.0, n=10):
    """The idle gaps named by what the host was doing: each gap goes to the
    innermost host span that covers at least half of it (else to the span
    covering most of it, else to "(no span)"); gaps are merged by name and
    the n names with most idle time returned as [name, seconds]."""
    by = collections.Counter()
    for g in sorted(idle, key=lambda g: -g.dur)[:2000]:
        s, e = g.start + skew_ns, g.end + skew_ns
        inner, most = None, None
        for sp in spans:
            if sp.start >= e:
                break
            cover = min(e, sp.end) - max(s, sp.start)
            if cover <= 0:
                continue
            if 2 * cover >= g.dur and (inner is None or sp.dur < inner.dur):
                inner = sp
            if most is None or cover > most[0]:
                most = (cover, sp)
        chosen = inner or (most[1] if most else None)
        by[chosen.name if chosen else "(no span)"] += g.dur
    return [[k, ns / 1e9] for k, ns in by.most_common(n)]
