"""What the closed loop counts as attempted, finished and failed.

Driven with a stand-in engine, so that requests can end in ways a healthy
engine never ends them: aborted, at an end-of-sequence token, with too few
or too many tokens, or lost.
"""

import json
import os
import types

import pytest

from benchmark import manifest, serve_loop
from benchmark.kinds import serve_closed_loop


class Engine:
    """The part of ``ServingEngine`` the feeder and the tracker touch."""

    def __init__(self, slots):
        self.spec = types.SimpleNamespace(slots=slots)
        self.slots = [types.SimpleNamespace(request=None)
                      for _ in range(slots)]
        self.queue = []

    def submit(self, request):
        free = next(s for s in self.slots if s.request is None)
        free.request = request

    def end(self, client, reason, tokens):
        slot = next(s for s in self.slots if s.request is not None
                    and s.request.rid[1] == client)
        request, slot.request = slot.request, None
        request.generated = [1] * tokens
        request.finish_reason = reason
        return request


@pytest.fixture
def loop():
    with open(os.path.join(manifest.HERE, "workloads",
                           "gpt2l-serve-decode-sat.json")) as f:
        traffic = json.load(f)
    ctx = types.SimpleNamespace(traffic=traffic, seed=5)
    eng, tracker = Engine(4), serve_loop.Tracker()
    feeder = serve_closed_loop.Feeder(
        ctx, eng, tracker, {"vocab_size": 50304, "seq_scale": 1.0})
    feeder.start(0.0)
    return eng, tracker, feeder


def _step(eng, tracker, feeder, finished, t):
    tracker.stamp(eng, finished, t)
    feeder.after_step(finished, t)


def test_one_client_per_slot_and_each_sends_on_when_its_request_ends(loop):
    eng, tracker, feeder = loop
    assert [s.request.rid for s in eng.slots] == [("c", c, 0)
                                                  for c in range(4)]
    want = tracker.reqs[("c", 2, 0)].want
    _step(eng, tracker, feeder, [eng.end(2, "length", want)], 1.0)
    assert tracker.reqs[("c", 2, 0)].ok
    assert ("c", 2, 1) in tracker.reqs and len(tracker.reqs) == 5
    assert tracker.deliveries == [(1.0, want)]


@pytest.mark.parametrize("reason,delta", [
    ("aborted", -1),        # the engine dropped it with tokens missing
    ("eos", -3),            # ended early at an end-of-sequence token
    ("length", -1),         # says length, delivered one token too few
    ("length", 1),          # one too many
    ("aborted", 0),         # every token there, but not ended by length
])
def test_a_request_that_ends_other_than_asked_is_failed(loop, reason, delta):
    eng, tracker, feeder = loop
    rec = tracker.reqs[("c", 1, 0)]
    _step(eng, tracker, feeder, [eng.end(1, reason, rec.want + delta)], 2.0)
    assert rec.ended_t == 2.0 and rec.reason == reason and not rec.ok
    # its client has its reply, such as it is, and sends its next request
    assert ("c", 1, 1) in tracker.reqs
    assert tracker.lost(eng) == []


def test_a_request_the_engine_no_longer_holds_and_never_ended_is_lost(loop):
    eng, tracker, feeder = loop
    eng.slots[3].request = None
    assert [r.rid for r in tracker.lost(eng)] == [("c", 3, 0)]
    eng.queue.append(types.SimpleNamespace(rid=("c", 3, 0)))
    assert tracker.lost(eng) == []          # queued again: still held
