"""The one general traffic generator: a mix is a data file of parameters,
and these functions turn a mix and a seed into requests or batches.

Serving mixes (``closed_loop``, ``open_loop``): the multiset of prompt
lengths and, for an open loop, the arrival offsets are drawn once from the
mix's own ``trace_seed`` — a recorded trace, replayed in every run. ``--seed``
decides the token ids and which request carries which length, so every seed
gives the system the same amount of work in another order. A closed loop
sends only as much of its list as the system gets through, so its mix names a
``shuffle_block``: the lengths keep the trace's order and the seed moves them
inside blocks of that many requests, and any stretch the system gets through
holds the same lengths for every seed.
"""

from __future__ import annotations

import numpy as np


def prompt_lengths(mix: dict, n: int) -> np.ndarray:
    """The mix's multiset of ``n`` prompt lengths, the same for every seed."""
    rng = np.random.default_rng(mix["trace_seed"])
    spec = mix["prompt_tokens"]
    if spec["dist"] == "uniform":
        out = rng.integers(spec["min"], spec["max"] + 1, size=n)
    elif spec["dist"] == "lognormal":
        out = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"],
                                size=n))
        out = np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)
    else:
        raise KeyError(f"unknown length distribution {spec['dist']!r}")
    return np.sort(out)


def arrival_offsets(mix: dict, seconds: float) -> np.ndarray:
    """Poisson arrivals at ``rate_per_s`` over ``seconds``: the same offsets
    for every seed. A longer window extends the same trace."""
    rng = np.random.default_rng(mix["trace_seed"] + 1)
    rate = float(mix["rate_per_s"])
    n = int(rate * seconds * 1.5) + 16
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return t[t < seconds]


def requests(mix: dict, cfg: dict, seed: int, seconds: float) -> list:
    """The requests of one run, in sending order: ``{"due": offset or None,
    "prompt": [ids], "max_new_tokens": n}``. An open loop sends each when it
    is due; a closed loop's clients take them in turn (the list is long
    enough for any rate the system could reach)."""
    rng = np.random.default_rng(seed)
    if mix["kind"] == "open_loop":
        due = arrival_offsets(mix, seconds)
        n = len(due)
    else:
        n = int(mix["max_requests_per_s"] * seconds)
        due = [None] * n
    lengths = prompt_lengths(mix, n)
    block = int(mix.get("shuffle_block", 0))
    if block:
        lengths = lengths[np.random.default_rng(
            mix["trace_seed"] + 2).permutation(n)]
        lengths = lengths[np.concatenate(
            [s + rng.permutation(min(block, n - s))
             for s in range(0, n, block)])]
    else:
        lengths = lengths[rng.permutation(n)]
    vocab = cfg["vocab_size"]
    return [{"due": None if d is None else float(d),
             "prompt": rng.integers(1, vocab, size=int(k)).tolist(),
             "max_new_tokens": int(mix["max_new_tokens"])}
            for d, k in zip(due, lengths)]


def sample_for_check(done: list, seed: int, n: int) -> list:
    """A sample, drawn from the seed, of the finished requests, with the
    longest in it."""
    if not done:
        return []
    rng = np.random.default_rng(seed + 7)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i]["prompt"]) + len(done[i]["tokens"]))
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + [rest[i] for i in
                        rng.permutation(len(rest))[:max(0, n - 1)]]
    return [done[i] for i in pick]
