"""The one traffic generator: a cell's parameters + a seed -> its inputs.

A traffic mix is the data file ``benchmark/workloads/<cell>.json``; this
module turns it into token batches or per-client request streams, and
nothing here knows a cell by name. The same seed gives the same inputs,
bit for bit; another seed gives others. Token ids are uniform below
``token_below`` (the published vocabulary: the padded rows are never fed).
``scale`` shrinks every length for the CPU rehearsal and is 1 on the chip.
"""

import numpy as np


def _rng(seed, *stream):
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def lengths(spec, rng, n, scale=1.0):
    """n integer lengths from ``spec``: {"dist": "lognormal", "median",
    "sigma", "min", "max"} | {"dist": "uniform", "min", "max"} |
    {"dist": "fixed", "value"}; clipped to [min, max], then scaled."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    elif dist == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, size=n).astype(float)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    if "min" in spec:
        x = np.clip(x, spec["min"], spec["max"])
    return np.maximum(1, np.rint(x * scale)).astype(np.int64)


def token_ids(rng, n, below):
    return rng.integers(0, below, size=n, dtype=np.int32)


def train_batches(p, seed, vocab, scale=1.0):
    """The pool of ``batch_pool`` host batches [global_batch, seq_len] the
    training loop cycles through."""
    rng = _rng(seed, 1)
    seq = max(8, int(round(p["seq_len"] * scale)))
    below = min(p["token_below"], vocab)
    return [rng.integers(0, below, size=(p["global_batch"], seq),
                         dtype=np.int32) for _ in range(p["batch_pool"])]


def _request_sizes(p, rng, n, scale):
    """(prompt lengths, output lengths), prompt + output <= the budget."""
    budget = max(4, int(round(p["max_total_tokens"] * scale)))
    prompts = np.minimum(lengths(p["prompt_tokens"], rng, n, scale),
                         budget - 2)
    outputs = np.maximum(
        2, np.minimum(lengths(p["output_tokens"], rng, n, scale),
                      budget - prompts))
    return prompts, outputs


def closed_loop_client(p, seed, client, vocab, scale=1.0):
    """Endless stream of (prompt ids, output tokens) of one client.

    Clients start together, so the first request of each is met MID-WAY: a
    share of its output, drawn uniformly, counts as already generated and
    is folded into the prompt (random ids like the rest), and the request
    asks for the remainder. The window then opens on the contexts, page
    fill and phases the mix has in its steady state, and no request is cut
    short: prompt + output of a first request is that of a whole one."""
    rng = _rng(seed, 3, client)
    below = min(p["token_below"], vocab)
    first = True
    while True:
        prompts, outputs = _request_sizes(p, rng, 1, scale)
        prompt, out = int(prompts[0]), int(outputs[0])
        if first:
            done = int(rng.integers(0, out - 1))      # leaves >= 2 to make
            prompt, out, first = prompt + done, out - done, False
        yield token_ids(rng, prompt, below), out


def sample_prompts(p, seed, n, vocab, reserve, scale=1.0):
    """n prompts of the mix for the correctness check, each leaving
    ``reserve`` positions of the budget free."""
    rng = _rng(seed, 4)
    budget = max(4, int(round(p["max_total_tokens"] * scale)))
    sizes = np.minimum(lengths(p["prompt_tokens"], rng, n, scale),
                       budget - reserve)
    below = min(p["token_below"], vocab)
    return [token_ids(rng, int(max(1, s)), below) for s in sizes]
