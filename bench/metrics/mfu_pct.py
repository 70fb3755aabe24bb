"""The whole model step's share of the chip's bf16 peak: FLOPs of the
tokens computed in the window (prompt tokens less prefix hits, plus
decoded tokens; 2 x matmul weights per token plus attention over each
token's context) over window seconds x peak."""
from bench import stats
from bench.metrics import ops


def read(rec, trace):
    if not rec.peaks:
        return None
    flops = 0.0
    for s in rec.window_steps():
        flops += ops.model_flops(rec.dims, len(s.decode),
                                 sum(c for _, c in s.decode))
        # the skipped prefix sits at the front of the prompts that have
        # one: charge each computed prompt token its own position
        skip = s.saved
        for i in s.prefilled:
            r = rec.requests[i]
            start = min(r.prefix_len if r.prefix_id >= 0 else 0, skip,
                        r.prompt_len - 1)
            skip -= start
            n = r.prompt_len - start
            flops += ops.model_flops(
                rec.dims, n, (start + 1 + r.prompt_len) * n // 2)
    return 100.0 * flops / (rec.seconds * rec.peaks["bf16_flops"])
