"""Paged and grouped decode attention's share of its roofline: the least
time the window's decode attention needs (per step, the slower of its
FLOPs at peak and the distinct keys and values it must read at HBM
bandwidth, a shared prefix counted once per step) over the time the
trace shows in the decode-attention kernels."""
from bench.metrics import ops


def read(rec, trace):
    if not trace or not trace["class_s"].get("attn_decode"):
        return None
    least = 0.0
    for s in rec.window_steps():
        rows = [(c, rec.requests[i].prefix_id, rec.requests[i].prefix_len)
                for i, c in s.decode]
        if rows:
            least += ops.least_time(*ops.decode_attention(rec.dims, rows),
                                    rec.peaks)
    return 100.0 * least / trace["class_s"]["attn_decode"]
