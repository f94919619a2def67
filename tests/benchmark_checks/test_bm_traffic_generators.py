"""The one traffic generator: same seed, same inputs; another seed, others."""

import itertools
import json
import os

import numpy as np
import pytest

from benchmark import manifest, traffic

CELLS = sorted(f[:-5] for f in os.listdir(
    os.path.join(manifest.HERE, "workloads")))


def _load(cell):
    with open(os.path.join(manifest.HERE, "workloads", cell + ".json")) as f:
        return json.load(f)


def _draw(p, seed):
    """Everything the harness would draw for this cell, as flat arrays."""
    if p["kind"] == "train_steps":
        return [b.ravel() for b in traffic.train_batches(p, seed, 50304)]
    stream = traffic.closed_loop_client(p, seed, 3, 50304)
    reqs = list(itertools.islice(stream, 5))
    return [np.concatenate([ids for ids, _ in reqs]),
            np.array([o for _, o in reqs])]


@pytest.mark.parametrize("cell", CELLS)
def test_generator_repeats_from_a_seed_and_differs_across_seeds(cell):
    p = _load(cell)
    a, b, c = _draw(p, 7), _draw(p, 7), _draw(p, 8)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert any(x.shape != y.shape or not np.array_equal(x, y)
               for x, y in zip(a, c))


@pytest.mark.parametrize("cell", [c for c in CELLS if "serve" in c])
def test_requests_stay_inside_the_cells_limits(cell):
    p = _load(cell)
    # after a client's first request, which is met mid-way
    reqs = list(itertools.islice(
        traffic.closed_loop_client(p, 1, 0, 50304), 1, 400))
    assert len(reqs) > 100
    for ids, out in reqs:
        assert p["prompt_tokens"]["min"] <= len(ids) <= p["prompt_tokens"]["max"]
        assert p["output_tokens"]["min"] <= out <= p["output_tokens"]["max"]
        assert len(ids) + out <= p["max_total_tokens"]
        assert ids.dtype == np.int32 and ids.max() < p["token_below"]


CHAT_PROMPTS = {"dist": "lognormal", "median": 192, "sigma": 0.8,
                "min": 16, "max": 768}    # PERF.md's chat mix, kept for later


def test_lognormal_lengths_have_the_median_they_were_given():
    x = traffic.lengths(CHAT_PROMPTS, np.random.default_rng(0), 20000)
    assert abs(np.median(x) - CHAT_PROMPTS["median"]) < 6
    assert x.min() == CHAT_PROMPTS["min"] and x.max() == CHAT_PROMPTS["max"]


def test_fixed_lengths_and_an_unknown_distribution():
    x = traffic.lengths({"dist": "fixed", "value": 40},
                        np.random.default_rng(0), 5, scale=0.5)
    assert x.tolist() == [20] * 5
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "zipf"}, np.random.default_rng(0), 1)


def test_first_closed_loop_request_is_met_midway_and_not_cut_short():
    """Clients start together: each one's first request has a share of its
    output already generated and folded into its prompt, so prompt + output
    is a whole request's and the window opens on steady-state contexts."""
    p = _load("gpt2l-serve-decode-sat")
    lo_p, hi_p = p["prompt_tokens"]["min"], p["prompt_tokens"]["max"]
    lo_o, hi_o = p["output_tokens"]["min"], p["output_tokens"]["max"]
    firsts = [next(traffic.closed_loop_client(p, 0, c, 50304))
              for c in range(256)]
    totals = [len(ids) + out for ids, out in firsts]
    assert lo_p + lo_o <= min(totals) and max(totals) <= hi_p + hi_o
    assert all(out >= 2 and len(ids) >= lo_p for ids, out in firsts)
    # phases are spread: some clients have most of their output behind them
    assert min(out for _, out in firsts) < lo_o / 4
    assert max(len(ids) for ids, _ in firsts) > hi_p + lo_o / 2
    # mean context at the start is about prompt + half an output
    mean_ctx = np.mean([len(ids) for ids, _ in firsts])
    assert abs(mean_ctx - ((lo_p + hi_p) / 2 + (lo_o + hi_o) / 4)) < 25
    # the largest first prompt decides the prefill buckets the cell warms
    pages = -(-(hi_p + hi_o - 2) // 128)
    assert max(p["prefill_page_buckets"]) >= pages


def test_rehearsal_scale_shrinks_every_length():
    p = _load("gpt2l-serve-decode-sat")
    for ids, out in itertools.islice(
            traffic.closed_loop_client(p, 0, 0, 512, 0.125), 50):
        assert len(ids) + out <= 128 and ids.max() < 512 and out >= 2
    (b,) = traffic.train_batches(dict(_load("gpt2l-train-1chip"),
                                      batch_pool=1), 0, 512, 0.125)
    assert b.shape == (8, 128) and b.max() < 512
