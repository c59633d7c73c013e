"""The one traffic generator: a mix's data file in, a request schedule out.

A mix (``traffic/<mix>.json``) states its arrivals and length laws:

- ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop: the
  window holds ``round(r * seconds)`` requests) or ``{"kind": "offline",
  "requests_per_s": r}`` (``ceil(r * seconds)`` requests, all due at 0);
- ``prompt`` and ``output``: lognormal ``median``/``sigma`` clipped to
  ``min``..``max`` tokens (with ``sessions``, ``prompt`` is each request's
  own suffix after its session's prefix);
- ``sessions`` (optional): ``count`` sessions picked Zipf(``zipf``), each
  with a prefix whose length is lognormal ``prefix`` rounded down to a
  multiple of ``prefix.align``.

Every seed gets the same set of sizes and arrival gaps: lengths are the
law's quantiles at ``(i + 0.5) / n``, arrival gaps the exponential's,
session picks the Zipf law's.  The seed draws their order, and every token
id.  The order is stratified: each run of ``BLOCK`` consecutive requests
(or one fewer) takes one arrival gap, one prompt length and one output
length from each of ``BLOCK`` equal strata of the sorted values (at most
one of each, in a run one short), so every run spans about
the same time and carries about the same work, and a seed can bunch
arrivals only within a run.  (Fully shuffled, the order alone moved a
51-request window's p90 TTFT threefold: one seed's bunched arrivals
queued twice over, and read the same on both of its runs.)  Which session
gets which prefix length is one fixed draw, the same for every seed.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

BLOCK = 8      # requests in a run of the stratified order


@dataclasses.dataclass
class Item:
    rid: int
    due: float                  # seconds after the window opens
    prompt: np.ndarray          # int32 token ids, session prefix included
    max_new: int
    session: int | None = None


@dataclasses.dataclass
class Schedule:
    items: list[Item]
    prefixes: list[np.ndarray]  # one per session, filled before the window


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(law: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.round(law["median"] * np.exp(law["sigma"] * z))
    x = np.clip(x, law["min"], law["max"])
    if "align" in law:
        x = np.maximum(law["min"], x // law["align"] * law["align"])
    return x.astype(np.int64)


def zipf_picks(count: int, s: float, n: int) -> np.ndarray:
    cdf = np.cumsum(np.arange(1, count + 1, dtype=np.float64) ** -s)
    return np.searchsorted(cdf / cdf[-1], _quantiles(n))


def stratified_runs(values: np.ndarray, block: int, rng) -> list:
    """``values`` dealt into runs of ``block`` items or one fewer, in an
    order drawn from ``rng``: each run holds one item of each of ``block``
    strata of the sorted values.  Strata differ in size by at most one; a
    short stratum leaves out one run, each run in turn."""
    v = np.asarray(values)
    strata = np.array_split(np.sort(v), block)
    m = len(strata[0])
    runs: list[list] = [[] for _ in range(m)]
    hole = int(rng.integers(m)) if m else 0
    for s in strata:
        targets = list(range(m))
        if len(s) < m:
            targets.remove(hole % m)
            hole += 1
        for r, x in zip(rng.permutation(targets), s):
            runs[r].append(x)
    return [rng.permutation(np.array(r, dtype=v.dtype)) for r in runs]


def stratified(values: np.ndarray, block: int, rng) -> np.ndarray:
    return np.concatenate(stratified_runs(values, block, rng))


def n_requests(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        return max(1, round(arr["rate_per_s"] * seconds))
    return math.ceil(arr["requests_per_s"] * seconds)


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    # separate streams: the session prefixes do not depend on the window
    tokens, pre, order = (np.random.default_rng(s) for s in
                          np.random.SeedSequence(int(seed)).spawn(3))
    n = n_requests(mix, seconds)
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        gaps = stratified(-np.log1p(-_quantiles(n)), BLOCK, order)
        cum = np.concatenate([[0.0], np.cumsum(gaps)])
        due = seconds * cum[:-1] / cum[-1]
    elif arr["kind"] == "offline":
        due = np.zeros(n)
    else:
        raise ValueError(f"arrivals kind {arr['kind']!r}")
    lens = stratified(lognormal_lengths(mix["prompt"], n), BLOCK, order)
    outs = stratified(lognormal_lengths(mix["output"], n), BLOCK, order)
    sess = mix.get("sessions")
    prefixes: list[np.ndarray] = []
    picks = [None] * n
    if sess:
        plen = lognormal_lengths(sess["prefix"], sess["count"])
        plen = np.random.default_rng(0).permutation(plen)
        prefixes = [pre.integers(0, vocab, int(k), dtype=np.int32)
                    for k in plen]
        picks = stratified(zipf_picks(sess["count"], sess["zipf"], n),
                           BLOCK, order)
    items = []
    for i in range(n):
        own = tokens.integers(0, vocab, int(lens[i]), dtype=np.int32)
        s = None if picks[i] is None else int(picks[i])
        prompt = own if s is None else np.concatenate([prefixes[s], own])
        items.append(Item(rid=i, due=float(due[i]), prompt=prompt,
                          max_new=int(outs[i]), session=s))
    return Schedule(items=items, prefixes=prefixes)
