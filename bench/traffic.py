"""One seeded generator for every traffic mix in ``bench/traffic/*.json``.

A mix is data.  Its fields:

``loop``
    ``"open"``: requests are due on a schedule whatever the server does;
    ``"closed"``: ``clients`` callers each send their next request when the
    previous one has finished.
``arrival``
    open loop only: ``{"process": "poisson", "rate_per_s": r}``.
``clients``
    closed loop only: the number of callers.
``preroll_s``
    seconds of the same traffic served before the window opens, so the
    window starts from a loaded server; counted as set-up.
``prompt`` / ``output``
    length distributions, ``{"dist": "lognormal", "median", "sigma",
    "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.  With a
    shared prefix, ``prompt`` is the unique part that follows it.
``prefix``
    ``null``, or ``{"count": k, "tokens": n, "zipf_s": s}``: k shared
    prefixes of n tokens, chosen with Zipf(s) popularity.

The seed changes the token ids, never the work: the lengths, prefix
choices and arrival gaps of a block of requests are the distribution's
quantiles, put in an order that a fixed seed draws.  So every seed offers the
same requests at the same times.  (Shuffling them by the seed was tried:
on the chat cell, whose window holds ~40 requests of heavy-tailed length,
the order alone moved the output tokens in the window by 16% between
seeds, against 0-4% between two runs of one seed.)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

CLOSED_BLOCK = 64          # closed-loop requests are stratified in blocks
_ORDER_SEED = 61_120_420


@dataclass
class Req:
    """One request of a schedule.  ``due`` is seconds from the window's
    opening (negative during the pre-roll); a closed loop sets it when the
    request is sent."""
    idx: int
    prompt_len: int            # unique part, after any shared prefix
    output_len: int
    prefix: int = -1           # shared prefix id, -1 for none
    due: Optional[float] = None


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *stream])


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths that stand for ``dist``: its quantiles at
    ``(i + 0.5) / n``, rounded and clipped to ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        v = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def gaps(arrival: dict, n: int, seconds: float) -> np.ndarray:
    """``n`` inter-arrival gaps of the process, scaled to sum to
    ``seconds`` (the block then spans exactly its time)."""
    if arrival["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (seconds / g.sum())


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests use each of ``count`` prefixes under
    Zipf(s) popularity, by largest remainder."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    c = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - c), kind="stable")[: n - c.sum()]:
        c[i] += 1
    return c


def _block(mix: dict, n: int, rng: np.random.Generator, idx0: int) -> List[Req]:
    """``n`` requests whose sizes and prefixes are the mix's fixed multiset,
    in the order ``rng`` puts them in."""
    p = lengths(mix["prompt"], n)
    o = lengths(mix["output"], n)
    pre = mix.get("prefix")
    if pre:
        ids = np.repeat(np.arange(pre["count"]),
                        zipf_counts(pre["count"], float(pre["zipf_s"]), n))
    else:
        ids = np.full(n, -1)
    # lengths and prefixes are shuffled on their own: which prefix a
    # request uses does not follow from its length
    p, o, ids = rng.permutation(p), rng.permutation(o), rng.permutation(ids)
    return [Req(idx0 + i, int(p[i]), int(o[i]), int(ids[i])) for i in range(n)]


def open_schedule(mix: dict, seconds: float) -> List[Req]:
    """Every request of an open loop due in ``[-preroll_s, seconds)``: a
    pre-roll block and a window block, each of ``rate × length`` requests,
    sorted by due time."""
    if mix["loop"] != "open":
        raise ValueError("open_schedule needs an open-loop mix")
    rate = float(mix["arrival"]["rate_per_s"])
    out: List[Req] = []
    for part, (start, length) in enumerate(((-float(mix["preroll_s"]),
                                             float(mix["preroll_s"])),
                                            (0.0, float(seconds)))):
        n = int(round(rate * length))
        if n == 0:
            continue
        rng = _rng(_ORDER_SEED, 1, part)
        reqs = _block(mix, n, rng, len(out))
        g = rng.permutation(gaps(mix["arrival"], n, length))
        # the first request is due at the block's start and each later one
        # a gap after the one before: the block stays within its length
        due = start + np.cumsum(g) - g
        for r, t in zip(reqs, due):
            r.due = float(t)
        out.extend(reqs)
    return out


def closed_pool(mix: dict, n: int) -> List[Req]:
    """The first ``n`` requests of a closed loop's queue, in blocks of
    ``CLOSED_BLOCK`` that each hold the mix's fixed multiset."""
    if mix["loop"] != "closed":
        raise ValueError("closed_pool needs a closed-loop mix")
    out: List[Req] = []
    for b in range(math.ceil(n / CLOSED_BLOCK)):
        out.extend(_block(mix, CLOSED_BLOCK, _rng(_ORDER_SEED, 2, b),
                          len(out)))
    return out[:n]


def prefix_tokens(mix: dict, seed: int, vocab: int) -> List[np.ndarray]:
    pre = mix.get("prefix")
    if not pre:
        return []
    return [_rng(seed, 3, k).integers(1, vocab, int(pre["tokens"]),
                                      dtype=np.int64)
            for k in range(int(pre["count"]))]


def prompt(req: Req, seed: int, vocab: int, prefixes: List[np.ndarray]
           ) -> List[int]:
    """The token ids of ``req``'s prompt: its shared prefix, if any, then
    its unique part (ids in ``[1, vocab)``, drawn from the seed)."""
    tail = _rng(seed, 4, req.idx).integers(1, vocab, req.prompt_len,
                                           dtype=np.int64)
    if req.prefix >= 0:
        tail = np.concatenate([prefixes[req.prefix], tail])
    return tail.tolist()
