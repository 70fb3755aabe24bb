"""Operations and bytes of the work a step needs, from shapes alone.

These are the yardstick's own counts, not the program's cost models:
what the algorithm has to compute and move, in bfloat16, whatever the
kernels do. ``d`` is a dims dict of :func:`bench.weights.dims`.
"""
from __future__ import annotations

BF16 = 2


def layer_gemms(d: dict) -> list:
    """(K, N) of each matrix product in one decoder layer."""
    q, kv = d["hq"] * d["dh"], d["hk"] * d["dh"]
    return [(d["d"], q + 2 * kv),          # q, k, v projections
            (q, d["d"]),                   # output projection
            (d["d"], 2 * d["f"]),          # gate and up
            (d["f"], d["d"])]              # down


def matmul_params(d: dict) -> int:
    """Weights every token multiplies through: all layers and the head."""
    return (d["layers"] * sum(k * n for k, n in layer_gemms(d))
            + d["d"] * d["vocab"])


def gemm(m: int, k: int, n: int) -> tuple:
    """(FLOPs, bytes) of an (m, k) x (k, n) product: weights, input and
    output each moved once."""
    return 2 * m * k * n, BF16 * (k * n + m * k + m * n)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's bound: the slower of compute and memory."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])


def gemm_least_time(d: dict, m_layers: int, m_head: int,
                    peaks: dict) -> float:
    """Least time of one pass through the model's products with
    ``m_layers`` rows through every layer and ``m_head`` through the
    output head; the weights are read once."""
    if m_layers <= 0:
        return 0.0
    t = d["layers"] * sum(least_time(*gemm(m_layers, k, n), peaks)
                          for k, n in layer_gemms(d))
    if m_head > 0:
        t += least_time(*gemm(m_head, d["d"], d["vocab"]), peaks)
    return t


def decode_attention(d: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of one decode step's attention over all layers.

    ``rows`` holds ``(context, prefix_id, prefix_len)`` per decoded
    sequence. Bytes are the distinct keys and values the batch reads:
    a prefix that several rows share counts once in the step. Queries
    and outputs are moved once each.
    """
    kv_tok = 2 * d["hk"] * d["dh"] * BF16          # K and V per position
    distinct = 0
    seen = set()
    flops = 0
    for ctx, pid, plen in rows:
        flops += 4 * d["hq"] * d["dh"] * ctx
        if pid >= 0 and plen:
            distinct += ctx - plen
            if pid not in seen:
                seen.add(pid)
                distinct += plen
        else:
            distinct += ctx
    qo = len(rows) * 2 * d["hq"] * d["dh"] * BF16
    return d["layers"] * flops, d["layers"] * (distinct * kv_tok + qo)


def model_flops(d: dict, new_tokens: int, ctx_sum: int) -> float:
    """FLOPs of ``new_tokens`` tokens through the model, with
    ``ctx_sum`` the positions their attention covers in total."""
    return (2 * matmul_params(d) * new_tokens
            + 4 * d["layers"] * d["hq"] * d["dh"] * ctx_sum)
