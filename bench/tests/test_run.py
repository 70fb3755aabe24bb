"""The runner end to end on the CPU: it refuses to measure without a
known chip, its dry run serves the smoke configuration through the
whole path, and the correctness check refuses broken serving.

The smoke limits (``data/smoke-limits.json``) sit above what sound smoke
runs read on 14 seeds (``mean_gap`` at most 0.00088, ``served_gap`` at
most 0.0235) and far below what each fault reads on 3 seeds (``mean_gap``
0.147 and more, ``served_gap`` 1.68 and more). The controls are checked
at the cells' own size on the chip, in ``test_control_chip.py``: at
d_model 128 int8 rounding is about as small as bfloat16's."""
import json
import math
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import harness

ROOT = harness.ROOT
RUN = os.path.join(ROOT, "bench", "run.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(*args, cwd=ROOT):
    return subprocess.run([sys.executable, os.path.join(cwd, "bench",
                                                        "run.py"), *args],
                          cwd=cwd, env=ENV, capture_output=True, text=True,
                          timeout=900)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_refuses_without_a_chip():
    _no_result(_run("--workload", "qwen2-0.5b.shared-chat", "--seed", "1",
                    "--seconds", "1", "--trace", "0"))


def test_refuses_an_unknown_chip(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(harness.NoChip):
        harness.chip(False)


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    _no_result(_run("--workload", "qwen2-0.5b.shared-chat", "--seed", "1",
                    "--seconds", "1", "--trace", "0", cwd=str(tmp_path)))


def test_dry_run_serves_the_smoke_cell_and_prints_no_result():
    p = _run("--dry-run", "--seed", str(2**31 + 3), "--seconds", "2")
    assert p.returncode == 0, p.stderr[-3000:]
    _no_result(types.SimpleNamespace(returncode=1, stdout=p.stdout))
    assert "check served_gap" in p.stderr
    assert "compiles in window 0" in p.stderr


def _dry(seed=5, **kw):
    return harness.run(harness.dry_cell(False), seed, 2.0, False, 0.0,
                       dry=True, log=lambda *_: None, **kw)


def _patch_decode(monkeypatch, wrap):
    from repro.models import transformer
    real = transformer.decode_step
    monkeypatch.setattr(transformer, "decode_step",
                        lambda *a, **k: wrap(real, *a, **k))


def test_fault_token_altered_where_it_is_produced(monkeypatch):
    from repro.serving import engine
    real = engine.sample
    monkeypatch.setattr(engine, "sample",
                        lambda *a, **k: (real(*a, **k) + 1) % 500)
    assert _dry()["correct"] is False


def test_fault_step_returns_its_state_unchanged(monkeypatch):
    def stale(real, ctx, params, tokens, cache, *a, **k):
        logits, _ = real(ctx, params, tokens, cache, *a, **k)
        return logits, cache
    _patch_decode(monkeypatch, stale)
    assert _dry()["correct"] is False


def test_fault_half_the_batch_left_out(monkeypatch):
    def half(real, *a, **k):
        # the decode step serves each row from the other half of its
        # pair: every row's own logits are left out
        logits, cache = real(*a, **k)
        b = logits.shape[0] // 2 * 2
        pairs = logits[:b].reshape(b // 2, 2, -1)[:, ::-1]
        return logits.at[:b].set(pairs.reshape(b, -1)), cache
    _patch_decode(monkeypatch, half)
    assert _dry()["correct"] is False


def test_sound_runs_are_correct():
    for seed in (11, 2**31 + 12):
        res = _dry(seed)
        assert res["correct"] is True, res["checks"]
        assert np.isfinite(res["metrics"]["output_tok_s"]["value"])


@pytest.mark.parametrize("trace", [False, True])
def test_a_metric_that_reads_infinity_refuses_the_run(monkeypatch, trace):
    # a tail of requests that never got a first token reads infinite:
    # the run must fail, not print a line without that metric
    monkeypatch.setattr(harness, "reader",
                        lambda name, root=ROOT: lambda rec, tr: math.inf)
    with pytest.raises(harness.Stalled):
        harness.run(harness.dry_cell(trace), 5, 2.0, trace, 0.0, dry=True,
                    log=lambda *_: None)
