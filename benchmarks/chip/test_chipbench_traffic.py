"""The traffic generator: a seed reproduces its trace exactly, two seeds
differ in order and ids but not in their sizes, and the drawn lengths
keep the mixes' medians and clips: the committed mixes, and an open-loop
mix with shared prefixes (``chipbench_testkit.MIXES``)."""

import json
import statistics

import numpy as np
import pytest

from chipbench import traffic
from chipbench_testkit import BENCH_DIR, MIXES as KIT_MIXES

COMMITTED = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))
MIXES = COMMITTED + ["kit-agent"]
SECONDS = 51.0
VOCAB = 23136


def mix(name):
    if name == "kit-agent":
        return KIT_MIXES["agent"]
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def trace(name, seed):
    return traffic.make_trace(mix(name), seed, VOCAB, SECONDS)


def flat(t):
    return [(r.prompt, r.max_new, r.due_s, r.client, r.prefix)
            for r in t.requests], t.shared_prefixes


@pytest.mark.parametrize("name", MIXES)
def test_one_seed_reproduces_its_trace(name):
    assert flat(trace(name, 2**33 + 17)) == flat(trace(name, 2**33 + 17))


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_differ_in_order_not_in_sizes(name):
    a, b = trace(name, 1), trace(name, 2**32 + 1)
    assert flat(a) != flat(b)
    assert [r.prompt[:8] for r in a.requests] != \
        [r.prompt[:8] for r in b.requests]
    size = lambda t: [(len(r.prompt), r.max_new) for r in t.requests]
    assert sorted(len(r.prompt) for r in a.requests) == \
        sorted(len(r.prompt) for r in b.requests)
    assert sorted(r.max_new for r in a.requests) == \
        sorted(r.max_new for r in b.requests)
    assert size(a) != size(b)          # paired differently
    if a.loop == "open":
        gaps = lambda t: sorted(np.diff([0.0] + [r.due_s for r in t.requests]))
        assert np.allclose(gaps(a), gaps(b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_keep_the_mix_median_and_clips(name):
    m, t = mix(name), trace(name, 99)
    prefix = m.get("shared_prefix", {}).get("tokens", 0)
    for key, got in (("prompt", [len(r.prompt) - (prefix if r.prefix >= 0
                                                  else 0)
                                 for r in t.requests]),
                     ("output", [r.max_new for r in t.requests])):
        p = m[key]
        assert min(got) >= p["min"] and max(got) <= p["max"]
        assert abs(statistics.median(got) - p["median"]) <= 1
        if t.loop == "open":
            assert sorted(got) == list(traffic.lognormal_sizes(p, len(got)))
        else:       # each round: one request per client, the same multiset
            c = t.clients
            for k in range(0, len(got), c):
                assert sorted(got[k:k + c]) == \
                    list(traffic.lognormal_sizes(p, c))


def test_open_loop_rate_burstiness_and_prefix_law():
    m, t = mix("kit-agent"), trace("kit-agent", 5)
    due = np.asarray([r.due_s for r in t.requests])
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert gaps.mean() == pytest.approx(1 / m["rate_per_s"])
    assert gaps.std() / gaps.mean() == pytest.approx(
        m["interarrival"]["cv"], rel=0.25)
    sp = m["shared_prefix"]
    assert len(t.shared_prefixes) == sp["count"]
    assert all(len(p) == sp["tokens"] for p in t.shared_prefixes)
    counts = np.bincount([r.prefix for r in t.requests])
    assert list(counts) == list(traffic.zipf_counts(
        sp["count"], sp["zipf_s"], len(t.requests)))
    assert counts[0] > counts[1]
    for r in t.requests:
        assert r.prompt[:sp["tokens"]] == t.shared_prefixes[r.prefix]
    assert all(1 <= tok < VOCAB for r in t.requests for tok in r.prompt)


def test_closed_loop_clients():
    m, t = mix("batch-decode"), trace("batch-decode", 5)
    assert t.clients == m["clients"]
    per = np.bincount([r.client for r in t.requests])
    assert list(per) == [traffic.CLOSED_LOOP_DEPTH] * m["clients"]
    assert not t.shared_prefixes
