"""The traffic generator: deterministic in the seed, the same sizes and
arrival gaps for every seed in an order the seed draws, stratified into
runs, and within each mix's stated ranges."""

import numpy as np
import pytest

from chipbench import cell, weights
from chipbench.traffic import (BLOCK, generate, n_requests, stratified,
                               stratified_runs)

BIG = 2**31 + 977     # seeds may exceed 32 signed bits
MIXES = ["chat", "repo", "offline"]


# a mix with sessions (code completion over repository context), as a
# later cell would send it
REPO = {"arrivals": {"kind": "poisson", "rate_per_s": 1.6},
        "prompt": {"median": 384, "sigma": 0.6, "min": 64, "max": 1024},
        "output": {"median": 32, "sigma": 0.6, "min": 8, "max": 128},
        "sessions": {"count": 16, "zipf": 1.1,
                     "prefix": {"median": 1536, "sigma": 0.4, "min": 512,
                                "max": 2560, "align": 256}}}


def mix(name):
    if name == "offline":
        # the offline arrival law, on the chat mix's lengths
        return dict(mix("chat"), arrivals={"kind": "offline",
                                           "requests_per_s": 4})
    if name == "repo":
        return REPO
    return cell.load_json(f"{cell.HERE}/traffic/{name}.json")


def key(s):
    return [(i.due, i.max_new, i.session, i.prompt.tolist()) for i in s.items]


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_in_the_seed(name):
    a = generate(mix(name), BIG, 20.0, 49152)
    b = generate(mix(name), BIG, 20.0, 49152)
    c = generate(mix(name), BIG + 1, 20.0, 49152)
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    a = generate(mix(name), 1, 51.0, 49152)
    b = generate(mix(name), BIG, 51.0, 49152)

    def gaps(s):
        # the last gap runs from the last arrival to the window's end
        due = [i.due for i in s.items] + [51.0]
        return sorted(np.round(np.diff(due), 9).tolist())
    assert gaps(a) == gaps(b)
    def own(s):
        return sorted(i.prompt.size - (s.prefixes[i.session].size
                                       if s.prefixes else 0) for i in s.items)
    assert own(a) == own(b)
    assert sorted(i.max_new for i in a.items) == \
        sorted(i.max_new for i in b.items)
    assert sorted(i.session or 0 for i in a.items) == \
        sorted(i.session or 0 for i in b.items)
    assert [p.size for p in a.prefixes] == [p.size for p in b.prefixes]
    # ... in another order
    assert [i.max_new for i in a.items] != [i.max_new for i in b.items]
    assert [i.prompt[:8].tolist() for i in a.items] != \
        [i.prompt[:8].tolist() for i in b.items]


@pytest.mark.parametrize("n", [1, 5, 8, 16, 51, 100])
def test_runs_hold_one_item_of_each_stratum(n):
    rng = np.random.default_rng(n)
    values = rng.permutation(n)
    runs = stratified_runs(values, 8, rng)
    assert sorted(np.concatenate(runs).tolist()) == list(range(n))
    sizes = [len(r) for r in runs]
    assert max(sizes) - min(sizes) <= 1 and max(sizes) <= 8
    stratum = np.zeros(n, int)
    for j, s in enumerate(np.array_split(np.arange(n), 8)):
        stratum[s] = j
    for r in runs:
        assert len(set(stratum[r].tolist())) == len(r)


def test_a_seed_bunches_arrivals_only_within_a_run():
    m = mix("chat")
    n = n_requests(m, 51.0)
    spans = []
    for seed in (1, 2, BIG):
        due = np.array([i.due for i in generate(m, seed, 51.0, 1000).items]
                       + [51.0])
        # the first run starts at 0; each run spans about block / rate
        starts = np.cumsum([0] + [len(r) for r in stratified_runs(
            np.arange(n), BLOCK, np.random.default_rng(0))])
        spans += np.diff(due[starts]).tolist()
    assert max(spans) < 2.2 * min(spans)
    assert not np.array_equal(stratified(np.arange(51), 8,
                                         np.random.default_rng(1)),
                              stratified(np.arange(51), 8,
                                         np.random.default_rng(2)))


@pytest.mark.parametrize("name", MIXES)
def test_ranges(name):
    m = mix(name)
    s = generate(m, 5, 30.0, 152064)
    assert len(s.items) == n_requests(m, 30.0)
    pre = m.get("sessions", {}).get("prefix")
    for it in s.items:
        own = it.prompt.size - (s.prefixes[it.session].size if pre else 0)
        assert m["prompt"]["min"] <= own <= m["prompt"]["max"]
        assert m["output"]["min"] <= it.max_new <= m["output"]["max"]
        assert 0 <= it.due < 30.0
        assert it.prompt.dtype == np.int32
        assert 0 <= it.prompt.min() and it.prompt.max() < 152064
    if m["arrivals"]["kind"] == "offline":
        assert all(it.due == 0.0 for it in s.items)
    if pre:
        for p in s.prefixes:
            assert pre["min"] <= p.size <= pre["max"]
            assert p.size % pre["align"] == 0


def test_sessions_share_prefixes_and_a_few_send_most():
    s = generate(mix("repo"), BIG, 60.0, 49152)
    for it in s.items:
        pre = s.prefixes[it.session]
        assert (it.prompt[:pre.size] == pre).all()
    # a few sessions send most of the traffic
    counts = np.bincount([it.session for it in s.items], minlength=16)
    assert counts.max() > 4 * counts.min() > 0


def test_seed_key_takes_any_whole_number():
    for s in (0, 1, BIG, 2**40 + 3):
        k = weights.seed_key(s)
        assert k.shape == ()
    assert not (weights.seed_key(BIG) == weights.seed_key(BIG + 1))
