"""The one traffic generator: turns a mix's parameter file
(``traffic/<mix>.json``) and a seed into requests.

Every seed gets the same multiset of sizes and of inter-arrival gaps, in
another order, so that two seeds differ in what is served where and not in
how much work there is. A closed loop's requests come in rounds, one per
request each client sends, and every round holds the same multiset. Lengths are quantiles of a clipped lognormal at
``(i + 0.5) / n``; open-loop gaps are quantiles of a gamma distribution
with the mix's coefficient of variation, scaled to the mix's rate. Token
ids, the order of sizes and gaps, and which shared prefix each request
carries come from the seed.

A mix file holds:

- ``loop``: ``"open"`` (requests fall due on a schedule) or ``"closed"``
  (``clients`` callers, each sending its next request when the last one
  finishes);
- ``rate_per_s`` and ``interarrival`` (``{"cv": ...}``) for an open loop;
- ``prompt`` and ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal in tokens (with shared prefixes, ``prompt`` is the unshared
  suffix);
- optionally ``shared_prefix``: ``{"count", "tokens", "zipf_s"}``, system
  prompts that each request starts with, chosen by a Zipf law; the
  harness puts them into the prefix trie during set-up.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np
from scipy import stats

# Requests an open loop draws beyond what its rate makes due in the window,
# as a share, so a burst never runs out of requests.
OPEN_LOOP_MARGIN = 1.5
# Requests each closed-loop client has queued.
CLOSED_LOOP_DEPTH = 16


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new: int
    due_s: float = 0.0          # open loop: seconds after the window opens
    client: int = -1            # closed loop: the caller that sends it
    prefix: int = -1            # index of the shared prefix it starts with


@dataclasses.dataclass
class Trace:
    loop: str
    requests: list[Request]
    shared_prefixes: list[list[int]]
    clients: int = 0


def lognormal_sizes(p: dict, n: int) -> np.ndarray:
    """``n`` lengths: the clipped lognormal's quantiles at (i + 0.5) / n,
    ascending."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(p["median"]) + p["sigma"] * z)
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


def gamma_gaps(rate: float, cv: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps in seconds with mean ``1 / rate`` and
    coefficient of variation ``cv``: gamma quantiles at (i + 0.5) / n,
    rescaled so that their mean is exact."""
    shape = 1.0 / (cv * cv)
    q = stats.gamma.ppf((np.arange(n) + 0.5) / n, shape)
    return q / q.mean() / rate


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests carry each of ``count`` prefixes under a
    Zipf(s) law, by largest remainder."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - out), kind="stable")[:n - out.sum()]:
        out[i] += 1
    return out


def open_loop_count(spec: dict, seconds: float) -> int:
    return int(math.ceil(spec["rate_per_s"] * seconds * OPEN_LOOP_MARGIN)) + 8


def make_trace(spec: dict, seed: int, vocab: int, seconds: float) -> Trace:
    """The requests of one run of the mix ``spec``."""
    rng = np.random.default_rng(seed)
    loop = spec["loop"]
    if loop == "open":
        n = open_loop_count(spec, seconds)
        prompt = rng.permutation(lognormal_sizes(spec["prompt"], n))
        output = rng.permutation(lognormal_sizes(spec["output"], n))
    elif loop == "closed":
        # one round per request a client sends, each round the same
        # multiset of lengths over the clients, in another order
        c = spec["clients"]
        n = c * CLOSED_LOOP_DEPTH
        prompt = np.concatenate([rng.permutation(lognormal_sizes(
            spec["prompt"], c)) for _ in range(CLOSED_LOOP_DEPTH)])
        output = np.concatenate([rng.permutation(lognormal_sizes(
            spec["output"], c)) for _ in range(CLOSED_LOOP_DEPTH)])
    else:
        raise ValueError(f"unknown loop {loop!r}")

    prefixes: list[list[int]] = []
    which = np.full(n, -1)
    sp = spec.get("shared_prefix")
    if sp:
        prefixes = [rng.integers(1, vocab, sp["tokens"]).tolist()
                    for _ in range(sp["count"])]
        which = rng.permutation(np.repeat(
            np.arange(sp["count"]), zipf_counts(sp["count"], sp["zipf_s"], n)))

    reqs = []
    for i in range(n):
        toks = rng.integers(1, vocab, int(prompt[i])).tolist()
        if which[i] >= 0:
            toks = prefixes[which[i]] + toks
        reqs.append(Request(toks, int(output[i]), prefix=int(which[i])))
    if loop == "open":
        gaps = rng.permutation(gamma_gaps(
            spec["rate_per_s"], spec["interarrival"]["cv"], n))
        for r, t in zip(reqs, np.cumsum(gaps)):
            r.due_s = float(t)
        return Trace(loop, reqs, prefixes)
    clients = spec["clients"]
    for i, r in enumerate(reqs):
        r.client = i % clients
    return Trace(loop, reqs, prefixes, clients)
