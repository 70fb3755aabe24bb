"""The matrix products' share of their roofline: the least time of the
window's products (per step, each layer's and the output head's product
over the rows that step decoded, and again over the prompt tokens it
computed, weights read once each) over the time the trace shows in the
GEMM kernels and fused GEMM seams."""
from bench import stats
from bench.metrics import ops


def read(rec, trace):
    if not trace or not trace["class_s"].get("gemm"):
        return None
    least = 0.0
    for s in rec.window_steps():
        least += ops.gemm_least_time(rec.dims, len(s.decode), len(s.decode),
                                     rec.peaks)
        least += ops.gemm_least_time(rec.dims, stats.prefill_work(rec, s),
                                     len(s.prefilled), rec.peaks)
    return 100.0 * least / trace["class_s"]["gemm"]
