"""Percentiles, rates and shares on hand-made event logs."""
import math

from bench import stats


def _req(due, stamps, prompt=100, **kw):
    r = stats.ReqLog(due=due, prompt_len=prompt, prefix_id=-1, prefix_len=0,
                     max_new=len(stamps), submit=due, **kw)
    r.stamps = list(stamps)
    r.tokens = [1] * len(stamps)
    r.finished = True
    return r


def _rec(requests, steps=(), open_=10.0, close=20.0, slots=4):
    return stats.Record(slots=slots, open=open_, close=close,
                        requests=list(requests), steps=list(steps),
                        counters={}, setup_s=1.0, dims={}, peaks={})


def test_nearest_rank_percentile():
    v = list(range(1, 11))
    assert stats.percentile(v, 50) == 5
    assert stats.percentile(v, 90) == 9
    assert stats.percentile(v, 95) == 10
    assert stats.percentile([3.0], 99) == 3.0
    assert math.isnan(stats.percentile([], 90))
    assert stats.percentile([1, 2, math.inf], 90) == math.inf


def test_ttft_counts_requests_due_in_the_window_from_their_due_time():
    rec = _rec([_req(9.0, [9.5]),              # due before the window
                _req(11.0, [11.2, 11.4]),
                _req(12.0, []),                # never answered: missing
                _req(13.0, [13.5], failed=True),
                _req(19.9, [21.0])])           # answered after close
    t = sorted(stats.ttfts(rec))
    assert t[:2] == [0.19999999999999929, 1.1000000000000014]
    assert t[2:] == [math.inf, math.inf]


def test_gaps_only_inside_the_window_and_a_stall_moves_the_tail():
    steady = [_req(9.0, [9.0 + 0.1 * i for i in range(120)])]
    base = stats.percentile(stats.gaps(_rec(steady)), 95)
    assert abs(base - 0.1) < 1e-9
    stamps = [9.0 + 0.1 * i for i in range(120)]
    stalled = [t + (2.0 if t > 15.0 else 0.0) for t in stamps]
    tail = stats.percentile(stats.gaps(_rec([_req(9.0, stalled)])), 100)
    assert abs(tail - 2.1) < 1e-9
    # 7 stalled gaps of 20 in another request push p95 up
    many = [_req(9.0, [10.0 + i * (2.0 if i % 3 == 0 else 0.1)
                       for i in range(20)])]
    assert stats.percentile(stats.gaps(_rec(many)), 95) >= 1.9
    assert len(stats.gaps(_rec(steady))) == 100    # 10.0 .. 20.0 inclusive


def test_rate_counts_tokens_inside_the_window():
    rec = _rec([_req(0.0, [5.0, 10.0, 15.0, 20.0, 25.0])])
    assert stats.tokens_in_window(rec) == 3
    assert stats.tokens_in_window(rec) / rec.seconds == 0.3


def test_occupancy_is_weighted_by_step_time():
    steps = [stats.StepLog(10.0, 11.0, [(0, 5)] * 4, [], 0),   # full, 1 s
             stats.StepLog(11.0, 14.0, [(0, 5)], [], 0),       # 1/4, 3 s
             stats.StepLog(19.0, 21.0, [(0, 5)] * 4, [], 0)]   # past close
    rec = _rec([], steps)
    assert rec.window_steps() == steps[:2]
    assert abs(stats.occupancy(rec) - (1.0 * 1 + 0.25 * 3) / 4) < 1e-12


def test_generator_lag_and_prefill_work():
    r = _req(11.0, [12.0], prompt=1100)
    r.submit = 11.25
    rec = _rec([r, _req(12.0, [12.5], prompt=300)])
    assert stats.lags(rec) == [0.25, 0.0]
    step = stats.StepLog(12.0, 12.5, [], [0, 1], 1024)
    assert stats.prefill_work(rec, step) == 1100 + 300 - 1024
