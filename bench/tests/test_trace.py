"""The trace reduction on a small trace recorded on a v5e
(``data/record_trace.py``): one paged decode-attention kernel and one
(8, 896) x (896, 4864) product per step, five steps, each followed by
a 50 ms wait in which the device idles."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
NAMES = os.path.join(os.path.dirname(__file__), "..", "trace_names.json")
QWEN = dict(hq=14, hk=2, dh=64, d=896, f=4864, vocab=151936)


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(os.path.join(DATA, "trace.xplane.pb"), NAMES,
                        trace.widths(QWEN, 2, 64, 64))


def test_window_busy_and_idle(summary):
    assert 0.25 < summary["window_s"] < 2.0
    assert 0 < summary["busy_s"] < summary["window_s"]
    idle = dict(summary["idle_gaps"])
    # five 50 ms sleeps, each an idle gap put down to the wait
    assert idle["bench.wait_due"] >= 5 * 0.05 * 0.9
    assert abs(sum(idle.values()) + summary["busy_s"]
               - summary["window_s"]) < 1e-6


def test_kernel_classes(summary):
    cls = summary["class_s"]
    assert cls["attn_decode"] > 0 and cls["gemm"] > 0
    assert cls["attn_decode"] + cls["gemm"] <= summary["busy_s"] + 1e-9
    assert len(summary["device_ops"]) <= 10


def test_shape_patterns_tell_decode_from_prefill_attention():
    classes, containers, _ = trace.load_names(
        NAMES, trace.widths(QWEN, 32, 64, 64))
    pool = "bf16[1024,64,2,64]{3,2,1,0:T(2,128)(2,1)} %p"
    decode = ("%c.1 = (bf16[32,2,7,64]{3,2,1,0}, f32[32,2,128]{2,1,0}) "
              "custom-call(" + pool + "), custom_call_target="
              "\"tpu_custom_call\"")
    chunk = decode.replace("[32,2,7,64]", "[32,2,448,64]")
    down = "%f.2 = bf16[2048,896]{1,0} fusion(bf16[2048,4864]{1,0} %a, " \
           "bf16[24,4864,896]{2,1,0} %w)"
    copy = "%copy.1 = bf16[1,1024,64,2,64]{1,4,3,2,0} copy(" + pool + ")"
    loop = "%while.2 = (s32[], bf16[24,896,4864]{2,1,0}) while(%t)"
    assert trace.classify(decode, classes) == "attn_decode"
    assert trace.classify(chunk, classes) is None
    assert trace.classify(down, classes) == "gemm"
    assert trace.classify(copy, classes) is None
    assert any(p.search(loop) for p in containers)
    assert not any(p.search(decode) for p in containers)
