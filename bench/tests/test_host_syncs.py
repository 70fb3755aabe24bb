"""``host_syncs_per_step`` on hand-made records: the engine counter's
difference between the window's two snapshots over the steps run
between them, and nothing for a program without the counter."""
from bench import harness, stats


def _rec(counters, steps):
    return stats.Record(slots=4, open=10.0, close=20.0, requests=[],
                        steps=[stats.StepLog(t0, t1, [], [], 0)
                               for t0, t1 in steps],
                        counters=counters, setup_s=1.0, dims={}, peaks={})


READ = harness.reader("host_syncs_per_step")


def test_reads_per_step_in_the_window():
    rec = _rec({"open": {"host_syncs": 1000}, "close": {"host_syncs": 1096}},
               [(9.0, 9.9), (10.0, 12.0), (12.0, 15.0), (15.0, 19.5),
                (19.5, 20.0)])
    # the step that ended before the window opened is not counted
    assert READ(rec, None) == 96 / 4


def test_nothing_to_read_without_the_counter_or_a_step():
    old = {"open": {"preemptions": 0}, "close": {"preemptions": 0}}
    assert READ(_rec(old, [(10.0, 11.0)]), None) is None
    assert READ(_rec({}, [(10.0, 11.0)]), None) is None
    syncs = {"open": {"host_syncs": 5}, "close": {"host_syncs": 5}}
    assert READ(_rec(syncs, []), None) is None
