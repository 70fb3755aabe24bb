"""The device's idle time in the traced window, put down to the
innermost host span around each gap: the benchmark's own spans
(``bench.trace.HOST_SPANS``) and the spans the engine opens inside
``Engine.step`` (``PROGRAM_SPANS``).

:func:`reduce` returns :func:`bench.trace.reduce`'s summary unchanged
except that ``idle_gaps`` is taken over both sets of spans, and adds
``idle_s``, the idle seconds of every span (``host.other`` for a gap
inside no span). Device busy time and the kernel classes are read by
``bench.trace`` alone, so every metric read from them is the same under
either reduction.
"""
from __future__ import annotations

import collections

from bench import trace

PROGRAM_SPANS = ("engine.admit", "engine.prefill", "engine.grow",
                 "engine.decode", "engine.sample")
SPANS = trace.HOST_SPANS + PROGRAM_SPANS


def idle_s(xspace_path: str, ops_line: str) -> dict | None:
    """{span: idle seconds} over the ``bench.window`` span, each gap
    between device operations (on the line ``ops_line``) put down to the
    innermost of :data:`SPANS` around its middle; None without a window
    or a device operation in it."""
    import jax
    pd = jax.profiler.ProfileData.from_file(xspace_path)
    host, device = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name == trace.WINDOW or e.name in SPANS]
        elif plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == ops_line:
                    device += [(e.start_ns, e.end_ns) for e in line.events]
    win = [(s, e) for n, s, e in host if n == trace.WINDOW]
    if not win:
        return None
    w0, w1 = win[0]
    busy = trace._union([(max(s, w0), min(e, w1)) for s, e in device
                         if e > w0 and s < w1])
    if not busy:
        return None
    around = [(n, s, e) for n, s, e in host if n in SPANS]
    idle = collections.Counter()
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        inner = [(e - s, n) for n, s, e in around if s <= mid <= e]
        idle[min(inner)[1] if inner else "host.other"] += b - a
    return {n: v / 1e9 for n, v in idle.items()}


def reduce(xspace_path: str, names_path: str, w: dict) -> dict | None:
    """:func:`bench.trace.reduce`'s summary with ``idle_gaps`` over
    :data:`SPANS` and the full ``idle_s``."""
    summary = trace.reduce(xspace_path, names_path, w)
    if summary is None:
        return None
    _, _, ops_line = trace.load_names(names_path, w)
    idle = idle_s(xspace_path, ops_line)
    summary["idle_s"] = idle
    summary["idle_gaps"] = [[n, v] for n, v in sorted(
        idle.items(), key=lambda kv: -kv[1])[:10]]
    return summary
