"""Reduce a profiler trace of the measured window to what the metric
readers need: device busy time, time per kernel class, the heaviest
device operations, and the device's idle time by what the host was doing.

The window is the host span ``bench.window`` that the harness writes
into the trace; device operations are clipped to it. Operations are
put into classes by the shape patterns in ``bench/trace_names.json``,
filled in with the cell's widths; one operation belongs to the first
class it matches.
"""
from __future__ import annotations

import collections
import json
import os
import re

WINDOW = "bench.window"
HOST_SPANS = ("bench.wait_due", "bench.submit", "engine.step")


def load_names(path: str, widths: dict):
    """(classes, containers, ops line) with the patterns compiled for
    ``widths`` (the placeholders ``{B}``, ``{D}``, ... of the table)."""
    with open(path) as f:
        table = json.load(f)
    mult = table["vocab_multiple"]
    w = dict(widths, VP=-(-widths["V"] // mult) * mult)

    def comp(pats):
        return [re.compile(re.sub(r"\{([A-Z0-9]+)\}",
                                  lambda m: str(w[m.group(1)]), p))
                for p in pats]

    classes = {c: {k: comp(v) for k, v in spec.items()}
               for c, spec in table["classes"].items()}
    return classes, comp(table["containers"]), table["device_ops_line"]


def widths(dims: dict, slots: int, page_size: int, chunk: int) -> dict:
    """The placeholder values of the name table for one cell."""
    g = dims["hq"] // dims["hk"]
    q, kv = dims["hq"] * dims["dh"], dims["hk"] * dims["dh"]
    return dict(B=slots, PS=page_size, HK=dims["hk"], G=g, D=dims["dh"],
                GC=g * chunk, DM=dims["d"], Q=q, KV=kv, QKV=q + 2 * kv,
                F=dims["f"], F2=2 * dims["f"], V=dims["vocab"])


def classify(name: str, classes: dict):
    for cls, spec in classes.items():
        if (all(p.search(name) for p in spec["all"])
                and any(p.search(name) for p in spec["any"])
                and not any(p.search(name) for p in spec["none"])):
            return cls
    return None


def short(name: str) -> str:
    """An HLO operation's text without layouts and operands."""
    name = re.sub(r"\{[^{}]*\}", "", name)
    cut = name.find("(", name.find("=") + 3)
    return name[:cut if cut > 0 else 160][:160]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(xspace_path: str, names_path: str, w: dict) -> dict | None:
    """The window's device summary, or None when the trace holds no
    window or no device operation in it. ``w`` is :func:`widths`."""
    import jax
    classes, containers, ops_line = load_names(names_path, w)
    pd = jax.profiler.ProfileData.from_file(xspace_path)
    host, device = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name == WINDOW or e.name in HOST_SPANS]
        elif plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == ops_line:
                    device += [(e.name, e.start_ns, e.end_ns)
                               for e in line.events]
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win or not device:
        return None
    w0, w1 = win[0]
    clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in device
               if e > w0 and s < w1]
    if not clipped:
        return None
    busy = _union([(s, e) for _, s, e in clipped])
    per_op = collections.Counter()
    per_class = collections.Counter()
    for n, s, e in clipped:
        if any(p.search(n) for p in containers):
            continue
        per_op[short(n)] += e - s
        cls = classify(n, classes)
        if cls:
            per_class[cls] += e - s
    # idle gaps, each put down to the innermost host span around its middle
    spans = [(n, s, e) for n, s, e in host if n in HOST_SPANS]
    idle = collections.Counter()
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        around = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        idle[min(around)[1] if around else "host.other"] += b - a
    busy_ns = sum(e - s for s, e in busy)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "class_s": {c: v / 1e9 for c, v in per_class.items()},
        "device_ops": [[n, v / 1e9] for n, v in per_op.most_common(10)],
        "idle_gaps": [[n, v / 1e9] for n, v in idle.most_common(10)],
        "n_ops": len(clipped),
    }


def find_xspace(trace_dir: str) -> str | None:
    for root, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    return None
