"""The arithmetic of a run: percentiles, rates and shares over the
records the window leaves. Pure Python, so the tests can feed it
hand-made logs."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class ReqLog:
    """One request as the load generator saw it. Times are host-clock
    seconds (``time.perf_counter``)."""

    due: float                       # when the schedule sent it
    prompt_len: int
    prefix_id: int
    prefix_len: int
    max_new: int
    submit: float = math.nan         # when it reached Engine.submit
    stamps: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finished: bool = False
    failed: bool = False             # refused at submit


@dataclasses.dataclass
class StepLog:
    """One ``Engine.step()``: when it ran and what it produced."""

    t0: float
    t1: float
    decode: list                     # [(request index, context length)]
    prefilled: list                  # [request index] first tokens
    saved: int                       # prompt tokens the prefix index
    #                                  skipped in this step


@dataclasses.dataclass
class Record:
    """What a measured window leaves for the metric readers."""

    slots: int
    open: float
    close: float
    requests: list                   # [ReqLog]
    steps: list                      # [StepLog]
    counters: dict                   # engine counters: {"open": .., "close": ..}
    setup_s: float
    dims: dict                       # model widths (bench.weights.dims)
    peaks: dict                      # the chip's row of peaks.json
    compiles_in_window: int = 0

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def in_window(self, t: float) -> bool:
        return self.open <= t <= self.close

    def window_steps(self) -> list:
        return [s for s in self.steps
                if s.t0 >= self.open and s.t1 <= self.close]


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``
    percent of the sample at or below it. ``inf`` stands for a request
    that never got its answer."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(math.ceil(p / 100 * len(v)) - 1, 0)]


def ttfts(rec: Record) -> list:
    """Due time to first token, seconds, for every request due in the
    window; a refused request or one with no token reads ``inf``."""
    out = []
    for r in rec.requests:
        if rec.open <= r.due < rec.close:
            out.append(r.stamps[0] - r.due if r.stamps and not r.failed
                       else math.inf)
    return out


def gaps(rec: Record) -> list:
    """Every gap between two consecutive tokens of one request with both
    tokens inside the window, seconds."""
    out = []
    for r in rec.requests:
        s = [t for t in r.stamps if rec.in_window(t)]
        out += [b - a for a, b in zip(s, s[1:])]
    return out


def tokens_in_window(rec: Record) -> int:
    return sum(1 for r in rec.requests for t in r.stamps
               if rec.in_window(t))


def occupancy(rec: Record) -> float:
    """Mean share of the slots that decoded in a step, each step weighted
    by its wall time; NaN when no step ran in the window."""
    steps = rec.window_steps()
    wall = sum(s.t1 - s.t0 for s in steps)
    if wall <= 0:
        return math.nan
    return sum(len(s.decode) / rec.slots * (s.t1 - s.t0)
               for s in steps) / wall


def lags(rec: Record) -> list:
    """Submit time minus due time for requests submitted in the window."""
    return [r.submit - r.due for r in rec.requests
            if rec.in_window(r.submit)]


def prefill_work(rec: Record, step: StepLog) -> int:
    """Prompt tokens a step had to compute: the prompts it prefilled less
    what the prefix index let it skip."""
    return max(sum(rec.requests[i].prompt_len for i in step.prefilled)
               - step.saved, 0)
