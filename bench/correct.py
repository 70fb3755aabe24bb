"""How ``correct`` is decided for a served model.

After the window, a sample of the finished requests, drawn from the seed
and always holding the one that served the most tokens, is run through
the plain reference (:mod:`bench.reference.dense`) over its prompt and
the tokens the engine served. At every served position the reference's
best logit minus its logit for the served token is that token's gap;
greedy serving in exact arithmetic gives 0 everywhere. Two numbers are
read from the gaps of the whole sample:

* ``mean_gap``: their mean. Rounding flips a token only where the
  reference's two best logits lie closer than the rounding's error, and
  then by less than that error, so the mean grows about as the square
  of the error: it parts a precision step below the served one from the
  served one by far more than the widest gap does;
* ``served_gap``: the widest.

The run is correct when every number that the cell's limits
(``bench/limits/<cell>.json``) name is within its limit, every sampled
request served its whole token budget, and the sample holds
``sample_tokens`` served tokens.

The control ``ref-int8`` puts the reference itself, one precision step
below the configuration's (int8 weights and keys and values, bfloat16
products), in the program's place: at each position of the same prompts
and served tokens, the token judged is the one the lower precision puts
first. It needs no decoding of its own, so one run reads both the
program's numbers and the control's.
"""
from __future__ import annotations

import numpy as np

from bench import weights
from bench.reference import dense

NUMBERS = ("mean_gap", "served_gap")


def sample(logs, seed: int, want_tokens: int) -> list:
    """Indices of finished requests to check: the longest, then others
    in an order drawn from the seed, until ``want_tokens`` served tokens
    are covered."""
    done = [i for i, r in enumerate(logs) if r.finished and r.tokens]
    if not done:
        return []
    first = max(done, key=lambda i: (len(logs[i].tokens), -i))
    rest = [i for i in done if i != first]
    order = np.random.default_rng([seed, 0xC0DE]).permutation(len(rest))
    picked, n = [first], len(logs[first].tokens)
    for j in order:
        if n >= want_tokens:
            break
        picked.append(rest[j])
        n += len(logs[rest[j]].tokens)
    return picked


def gaps(model: dict, seed: int, cases: list, pad_to: int, rows: int,
         control: bool = False) -> tuple[np.ndarray, np.ndarray | None]:
    """Over all cases (prompt, served tokens): the gap at every served
    position, and with ``control`` the gap at every position of the
    token the lower-precision reference puts first there."""
    w = weights.draw(model, seed)
    served, lower = [], []
    for prompt, toks in cases:
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        first = len(prompt) - 1
        lg = dense.logits(model, w, seq, first, pad_to, rows)
        best = lg.max(axis=1)
        at = np.arange(len(toks))
        served.append(best - lg[at, np.asarray(toks)])
        if control:
            pick = dense.logits(model, w, seq, first, pad_to, rows,
                                lower=True).argmax(axis=1)
            lower.append(best - lg[at, pick])
    cat = (lambda g: np.concatenate(g) if g else np.full(1, np.inf))
    return cat(served), (cat(lower) if control else None)


def numbers(gap: np.ndarray) -> dict:
    return {"mean_gap": float(gap.mean()), "served_gap": float(gap.max())}


def judge(model: dict, seed: int, logs, prompts: dict, limits: dict,
          max_seq: int, control: bool = False, log=None
          ) -> tuple[bool, dict]:
    """(correct, checks) where ``checks`` maps each number compared to
    ``{"value", "limit"}``. ``prompts`` maps a request index to its
    prompt tokens; ``max_seq`` is the engine's longest sequence. With
    ``control`` the numbers judged are the ``ref-int8`` control's, and
    the program's own go to ``log``."""
    picked = sample(logs, seed, limits["sample_tokens"])
    short = sum(1 for i in picked
                if len(logs[i].tokens) != logs[i].max_new)
    rows = max([len(logs[i].tokens) for i in picked], default=1)
    served, lower = gaps(model, seed, [(prompts[i], logs[i].tokens)
                                       for i in picked], max_seq,
                         -(-rows // 256) * 256, control)
    got = numbers(served)
    if log:
        log("gaps of the served tokens: " + ", ".join(
            f"{k} {v!r}" for k, v in got.items()))
    if control:
        got = numbers(lower)
        if log:
            log("gaps of the ref-int8 control: " + ", ".join(
                f"{k} {v!r}" for k, v in got.items()))
    checks = {k: {"value": got[k], "limit": limits[k]}
              for k in NUMBERS if k in limits}
    checks["short_finishes"] = {"value": short, "limit": 0}
    checks["sampled_tokens"] = {"value": sum(len(logs[i].tokens)
                                             for i in picked),
                                "limit": limits["sample_tokens"]}
    ok = (bool(picked) and short == 0
          and all(checks[k]["value"] <= checks[k]["limit"]
                  for k in NUMBERS if k in checks)
          and checks["sampled_tokens"]["value"] >= limits["sample_tokens"])
    return ok, checks
