"""The generator: same seed, same requests; every seed the same work."""
import collections
import json
import os

import numpy as np
import pytest

from bench.traffic import gen

MIXES = os.path.join(os.path.dirname(__file__), "..", "traffic")
NAMES = sorted(f[:-5] for f in os.listdir(MIXES) if f.endswith(".json"))


def _mix(name):
    return gen.load(os.path.join(MIXES, name + ".json"))


def _first(mix, seed, n):
    """The first ``n`` requests of a mix (whole blocks when ``n`` is a
    multiple of the block)."""
    if mix["loop"] == "open":
        reqs = gen.generate(mix, seed, 2 * n / mix["rate_rps"], 1000)
    else:
        reqs = gen.generate(mix, seed, 0, 1000, n_min=n)
    assert len(reqs) >= n
    return reqs[:n]


@pytest.mark.parametrize("name", NAMES)
def test_same_seed_same_requests(name):
    a = _first(_mix(name), 2**31 + 77, 128)
    b = _first(_mix(name), 2**31 + 77, 128)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.due_s, x.max_new, x.prefix_id) == (y.due_s, y.max_new,
                                                    y.prefix_id)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", NAMES)
def test_seeds_differ_in_order_not_in_work(name):
    mix = _mix(name)
    n = 4 * gen.BLOCK
    runs = []
    for seed in (1, 2):
        reqs = _first(mix, seed, n)
        if mix["loop"] == "closed":
            reqs = reqs[gen.BLOCK:]        # first budgets are spread
        runs.append(reqs)
    a, b = runs
    assert [r.max_new for r in a] != [r.max_new for r in b]
    blk = lambda rs, f: sorted(f(r) for r in rs)  # noqa: E731
    for f in (lambda r: len(r.prompt), lambda r: r.max_new,
              lambda r: r.prefix_id):
        assert blk(a, f) == blk(b, f)


@pytest.mark.parametrize("name", NAMES)
def test_lengths_follow_the_file(name):
    mix = _mix(name)
    reqs = _first(mix, 5, 2 * gen.BLOCK)[gen.BLOCK:]
    pre = mix["shared_prefix"]["tokens"] if mix.get("shared_prefix") else 0
    own = np.array([len(r.prompt) - pre for r in reqs])
    out = np.array([r.max_new for r in reqs])
    for got, spec in ((own, mix["prompt"]), (out, mix["output"])):
        assert got.min() >= spec["min"] and got.max() <= spec["max"]
        if spec["dist"] == "lognormal":
            assert abs(np.median(got) - spec["median"]) <= 0.05 * spec[
                "median"]
        else:
            mid = (np.sqrt(spec["min"] * spec["max"])
                   if spec["dist"] == "loguniform"
                   else (spec["min"] + spec["max"]) / 2)
            assert abs(np.median(got) - mid) <= 0.05 * mid


def test_zipf_popularity_and_shared_content():
    mix = _mix("shared-chat")
    reqs = _first(mix, 9, gen.BLOCK)
    counts = collections.Counter(r.prefix_id for r in reqs)
    sp = mix["shared_prefix"]
    assert [counts[i] for i in range(sp["count"])] == list(
        gen.zipf_counts(sp["count"], sp["zipf_s"], gen.BLOCK))
    assert counts[0] > counts[1] > counts[sp["count"] - 1]
    heads = {}
    for r in reqs:
        heads.setdefault(r.prefix_id, r.prompt[:sp["tokens"]])
        np.testing.assert_array_equal(r.prompt[:sp["tokens"]],
                                      heads[r.prefix_id])


def test_open_loop_arrivals_at_the_rate():
    mix = dict(_mix("shared-chat"), rate_rps=4.0)
    reqs = gen.generate(mix, 3, 1000.0, 1000)
    assert abs(len(reqs) / 1000.0 - 4.0) < 0.1
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues)
    # every block of 64 spans the same time: the gaps are the quantiles
    spans = [dues[i + gen.BLOCK - 1] - dues[i - 1]
             for i in range(gen.BLOCK, len(dues) - gen.BLOCK, gen.BLOCK)]
    assert max(spans) - min(spans) < 1e-6


def test_closed_loop_first_budgets_are_spread():
    mix = _mix("long-decode")
    reqs = gen.generate(mix, 4, 0, 1000, n_min=gen.BLOCK)
    first = [r.max_new for r in reqs[:mix["clients"]]]
    assert min(first) < mix["output"]["min"] and len(set(first)) > 1
    assert all(r.max_new >= mix["output"]["min"]
               for r in reqs[mix["clients"]:])
    assert json.dumps(mix)  # data only


def test_an_open_loop_window_gets_the_same_work_from_every_seed():
    mix = _mix("shared-chat")
    seconds = 30.0
    n = gen.first_block(mix, seconds)
    close = mix["preroll_s"] + seconds
    sets = []
    for seed in (2**31 + 1, 2**31 + 2):
        reqs = gen.generate(mix, seed, close + 60, 1000, first_block=n)
        due = [r for r in reqs if r.due_s < close]
        assert abs(len(due) - n) <= 1
        sets.append((sorted(len(r.prompt) for r in reqs[:n]),
                     sorted(r.max_new for r in reqs[:n])))
        assert abs(reqs[n - 1].due_s - n / mix["rate_rps"]) < 1.0
    assert sets[0] == sets[1]


def test_every_run_of_strata_holds_each_band_once():
    rng = np.random.default_rng(2**31 + 9)
    n = gen.STRATA * 8
    out = gen.spread(rng, np.arange(n))
    assert sorted(out) == list(range(n))
    band = n // gen.STRATA
    for r in range(0, n, gen.STRATA):
        assert sorted(x // band for x in out[r:r + gen.STRATA]) == list(
            range(gen.STRATA))
    assert not np.array_equal(out, gen.spread(np.random.default_rng(1),
                                              np.arange(n)))
