"""The span-aware reduction (``bench/spans.py``) on a small trace recorded
on a v5e (``data/record_spans.py``): a 2-layer qwen2-0.5b engine serving
three prefix-sharing requests through two slots, every ``Engine.step``
inside an ``engine.step`` span, so the engine's own phase spans nest
inside it, and a 20 ms ``bench.wait_due`` sleep after each step."""
import os

import jax
import pytest

from bench import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "trace-spans.xplane.pb")
NAMES = os.path.join(os.path.dirname(__file__), "..", "trace_names.json")
QWEN = dict(hq=14, hk=2, dh=64, d=896, f=4864, vocab=151936)
W = trace.widths(QWEN, 2, 64, 64)


@pytest.fixture(scope="module")
def old():
    return trace.reduce(TRACE, NAMES, W)


@pytest.fixture(scope="module")
def new():
    return spans.reduce(TRACE, NAMES, W)


def test_the_engine_spans_nest_inside_engine_step():
    host = []
    for plane in jax.profiler.ProfileData.from_file(TRACE).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name in spans.SPANS]
    steps = [(s, e) for n, s, e in host if n == "engine.step"]
    inner = [(n, s, e) for n, s, e in host if n in spans.PROGRAM_SPANS]
    assert {n for n, _, _ in inner} == set(spans.PROGRAM_SPANS)
    for _, s, e in inner:
        assert any(a <= s and e <= b for a, b in steps)


def test_device_numbers_are_the_old_reductions(old, new):
    for key in ("window_s", "busy_s", "class_s", "device_ops", "n_ops"):
        assert new[key] == old[key], key
    assert new["class_s"]["attn_decode"] > 0 and new["class_s"]["gemm"] > 0


def test_idle_goes_to_the_innermost_program_span(old, new):
    idle = new["idle_s"]
    before = dict(old["idle_gaps"])
    assert "engine.sample" not in before and idle["engine.sample"] > 0
    # what the old reduction put in engine.step is now split between the
    # residue and the program's spans; the benchmark's spans keep theirs
    program = sum(idle.get(n, 0.0) for n in spans.PROGRAM_SPANS)
    assert idle.get("engine.step", 0.0) + program == pytest.approx(
        before["engine.step"], abs=1e-9)
    assert idle["bench.wait_due"] == pytest.approx(before["bench.wait_due"])
    assert idle["bench.wait_due"] >= 5 * 0.02 * 0.9
    assert sum(idle.values()) + new["busy_s"] == pytest.approx(
        new["window_s"], abs=1e-9)


def test_idle_gaps_are_the_ten_largest_spans(new):
    top = sorted(new["idle_s"].items(), key=lambda kv: -kv[1])[:10]
    assert [tuple(g) for g in new["idle_gaps"]] == top
