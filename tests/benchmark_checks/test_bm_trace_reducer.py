"""The trace reducer on a small trace recorded on a v5e (PR 22).

``fixtures/tiny_v5e.xplane.pb`` is 16 KB: four launches of one jitted
``tanh(x @ x).sum()`` on a 2048 x 2048 bf16 matrix, three back to back
under ``bench/dispatch`` spans, then 20 ms of ``time.sleep`` under
``bench/host_sleep``, then the fourth, all inside ``bench/outer``. The
numbers below were read off the trace by hand (trace_look.py).
"""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PLANE = "/device:TPU:0"


@pytest.fixture(scope="module")
def trace():
    return tr.load(os.path.join(HERE, "fixtures", "tiny_v5e.xplane.pb"))


def test_planes_lines_and_modules(trace):
    assert sorted(trace.devices) == [PLANE]
    assert {"XLA Modules", "XLA Ops", "Async XLA Ops"} <= set(
        trace.devices[PLANE])
    mods = tr.modules(trace, PLANE, "jit__lambda")
    assert len(mods) == 4 and all(m.dur == 90218.0 for m in mods)
    assert tr.modules(trace, PLANE, "jit_train") == []


def test_busy_time_is_the_union_of_op_intervals(trace):
    t0, t1 = tr.window_of(trace)
    assert (t0, t1) == (42742883.0, 66050226.0)
    # 4 launches x (13 ns copy-start + 3 ns copy-done + 90196 ns fusion)
    assert tr.busy_ns(trace, PLANE, t0, t1) == 360848.0
    # half of the first launch only
    assert tr.busy_ns(trace, PLANE, t0, t0 + 45000) == pytest.approx(
        45000 - 5, abs=1)


def test_step_time_is_start_to_start(trace):
    assert tr.step_starts_ms(trace, PLANE, "jit__lambda") == pytest.approx(
        [0.914869, 0.645392, 21.656864])


def test_opcode_label_and_self_time_by_opcode(trace):
    by = tr.self_time_by(tr.ops(trace, PLANE), tr.opcode)
    assert dict(by) == {"fusion": 360785.0, "copy-start": 53.0,
                        "copy-done": 10.0}
    top = tr.top_ops(trace, PLANE, *tr.window_of(trace), n=1)
    assert top == [["%fusion fusion bf16[]", pytest.approx(360785e-9)]]


def test_the_long_idle_gap_goes_to_the_host_span_that_slept(trace):
    t0, t1 = tr.window_of(trace)
    idle = tr.gaps(tr.ops(trace, PLANE), t0, t1)
    longest = max(idle, key=lambda g: g.dur)
    assert longest.dur == 21566649.0          # third launch's end -> fourth
    assert sum(g.dur for g in idle) == (t1 - t0) - 360848.0
    spans = tr.annotations(trace, ("bench/",))
    assert [s.name for s in spans] == [
        "bench/outer", "bench/dispatch", "bench/dispatch", "bench/dispatch",
        "bench/host_sleep"]
    skew = tr.clock_skew_ns(trace, PLANE)
    assert skew == 1121660.0                  # device clock ~1.1 ms ahead
    named = dict(map(tuple, tr.attribute_gaps(idle, spans, skew)))
    assert named["bench/host_sleep"] == pytest.approx(21566649e-9)
    # the two short gaps between the back-to-back launches fall to the span
    # around them; nothing is left without a name
    assert named["bench/outer"] == pytest.approx((824654 + 555178 + 3) * 1e-9,
                                                 rel=1e-3)
    assert "(no span)" not in named


# ----------------------------------------- arithmetic on hand-made events

def ev(name, a, b):
    return tr.Event(name, float(a), float(b))


def test_self_time_takes_nested_events_out_of_their_container():
    evs = [ev("%while.1 = (s32[]) while(s32[] %x), body=%b", 0, 100),
           ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 10, 40),
           ev("%all-gather-start.2 = (f32[8]) all-gather-start(f32[2] %a)",
              40, 45),
           ev("%all-gather-done.2 = f32[8] all-gather-done((f32[8]) %s)",
              60, 90),
           ev("%attn.3 = bf16[4] custom-call(bf16[4] %q), "
              'custom_call_target="tpu_custom_call"', 100, 130)]
    by = tr.self_time_by(evs, tr.opcode)
    assert by == {"while": 35.0, "fusion": 30.0, "all-gather-start": 5.0,
                  "all-gather-done": 30.0, "custom-call": 30.0}
    assert tr.time_where(evs, tr.is_collective) == 35.0   # exposed: core waits
    assert tr.time_where(evs, tr.is_pallas) == 30.0
    assert tr.union_ns(evs) == 130.0
    assert [(g.start, g.end) for g in tr.gaps(evs[1:], 0, 140)] == [
        (0, 10), (45, 60), (90, 100), (130, 140)]


def test_gap_without_a_covering_span_is_named_so():
    idle = [ev("", 0, 10), ev("", 50, 60)]
    spans = [ev("bench/eng_step", 48, 70)]
    assert dict(map(tuple, tr.attribute_gaps(idle, spans))) == {
        "(no span)": pytest.approx(10e-9),
        "bench/eng_step": pytest.approx(10e-9)}
