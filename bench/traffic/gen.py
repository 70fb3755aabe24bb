"""The one traffic generator: reads a mix's parameters, makes requests.

A mix (``bench/traffic/<mix>.json``) holds only data:

* ``loop``: ``"open"`` (requests due on a Poisson schedule at
  ``rate_rps``) or ``"closed"`` (``clients`` callers, each sending its
  next request when its last one finished);
* ``preroll_s``: seconds the load runs before the measured window opens
  (a closed loop's first requests get budgets spread over their draw,
  so the callers start at different points of a reply);
* ``prompt`` / ``output``: length distributions in tokens, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
  ``{"dist": "loguniform", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``; ``prompt`` is the private part
  and ``output`` the greedy token budget;
* ``shared_prefix`` (optional): ``{"count", "tokens", "zipf_s"}``, a
  set of system prompts put before the private part, chosen with Zipf
  popularity.

Every seed gets the same work in another order. Requests come in blocks
(of ``BLOCK``, or as many as the caller asks: the harness makes an open
loop's first block exactly the requests due before its window closes);
within a block of n the lengths are the distribution's quantiles at
``(i + 0.5) / n``, the gaps between arrivals the exponential
distribution's quantiles, and the system prompts are shared out by
largest remainder of their Zipf weights; the seed orders each of these
in each block and draws the token ids. So two seeds differ in order and
content, never in how many tokens they ask for. The order is drawn
stratified (:func:`spread`): every run of ``STRATA`` requests from a
block's start holds one value of each of its ``STRATA`` quantile
bands, so any stretch of the load, such as the part of it that a
window sees, asks for about the same work on every seed.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist

import numpy as np

BLOCK = 64
STRATA = 8


@dataclasses.dataclass
class Request:
    index: int
    due_s: float            # offset from the start of the load (open loop)
    prompt: np.ndarray      # int32 token ids, shared prefix included
    max_new: int
    prefix_id: int          # system prompt index, -1 when unshared
    prefix_len: int


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop {mix['loop']!r}")
    return mix


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, as whole tokens
    within ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "loguniform":
        v = lo * (hi / lo) ** u
    elif spec["dist"] == "uniform":
        v = lo + (hi - lo) * u
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def spread(rng, values) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which each run of
    ``STRATA`` entries from the start holds one value from each band of
    ``len(values) / STRATA`` consecutive ranks (where ``len(values)`` is
    no multiple of ``STRATA``, some runs hold one fewer): which value of
    a band goes to which run, the order inside a run and the order of
    the runs are the draw."""
    v = np.sort(np.asarray(values))
    runs = -(-len(v) // STRATA)
    rank = np.arange(STRATA * runs).reshape(STRATA, runs)
    rank = np.stack([rng.permutation(row) for row in rank])  # band -> run
    out = []
    for r in rng.permutation(runs):
        members = rank[:, r][rank[:, r] < len(v)]
        out.append(v[rng.permutation(members)])
    return np.concatenate(out)


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests each of ``count`` prefixes gets."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    got = np.floor(exact).astype(np.int64)
    got[np.argsort(-(exact - got), kind="stable")[:n - got.sum()]] += 1
    return got


def generate(mix: dict, seed: int, horizon_s: float, vocab: int,
             n_min: int = 0, first_block: int = BLOCK) -> list[Request]:
    """Requests for ``horizon_s`` seconds of load (open loop: due before
    the horizon; closed loop: ``n_min`` or more, in the order clients
    take them). The first block holds ``first_block`` requests, the
    others ``BLOCK``. Token ids are drawn from ``[1, vocab)``."""
    rng = np.random.default_rng(seed)
    shared = mix.get("shared_prefix")
    prefixes = ([rng.integers(1, vocab, shared["tokens"], dtype=np.int32)
                 for _ in range(shared["count"])] if shared else [])
    out: list[Request] = []
    t = 0.0
    n = first_block
    while True:
        p_len = spread(rng, quantiles(mix["prompt"], n))
        o_len = spread(rng, quantiles(mix["output"], n))
        gap = spread(rng, -np.log1p(-(np.arange(n) + 0.5) / n))
        pid = (spread(rng, np.repeat(
            np.arange(shared["count"]),
            zipf_counts(shared["count"], shared["zipf_s"], n)))
            if shared else np.full(n, -1))
        for i in range(n):
            if mix["loop"] == "open":
                t += gap[i] / mix["rate_rps"]
                if t >= horizon_s:
                    return out
            own = rng.integers(1, vocab, int(p_len[i]), dtype=np.int32)
            pre = prefixes[pid[i]] if pid[i] >= 0 else own[:0]
            out.append(Request(len(out), t if mix["loop"] == "open" else 0.0,
                               np.concatenate([pre, own]), int(o_len[i]),
                               int(pid[i]), len(pre)))
        n = BLOCK
        if mix["loop"] == "closed" and len(out) >= n_min:
            # the clients' first requests stand for replies already under
            # way: budgets spread over (0, 1] of their draw, so the loop
            # starts near its steady mix of progress
            for i in range(mix["clients"]):
                out[i].max_new = max(1, math.ceil(
                    out[i].max_new * (i + 0.5) / mix["clients"]))
            return out


def longest(mix: dict) -> tuple[int, int]:
    """(longest prompt, longest output) the mix can make."""
    pre = mix["shared_prefix"]["tokens"] if mix.get("shared_prefix") else 0
    return pre + mix["prompt"]["max"], mix["output"]["max"]


def first_block(mix: dict, seconds: float) -> int:
    """An open loop's first block: the requests due before the window
    closes, so that every seed puts the same work into the window."""
    if mix["loop"] != "open":
        return BLOCK
    return max(8, round(mix["rate_rps"] * (mix["preroll_s"] + seconds)))
