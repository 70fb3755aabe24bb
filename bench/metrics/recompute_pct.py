"""Share of the positions prefilled in the window that preempted
requests computed again at re-admission: the differences of the
engine's ``recomputed_tokens`` and ``prefill_tokens`` counters between
the window's two snapshots. None for a program without the counters,
or a window with no prefill."""


def read(rec, trace):
    delta = {}
    for name in ("recomputed_tokens", "prefill_tokens"):
        before = rec.counters.get("open", {}).get(name)
        after = rec.counters.get("close", {}).get(name)
        if before is None or after is None:
            return None
        delta[name] = after - before
    if not delta["prefill_tokens"]:
        return None
    return 100.0 * delta["recomputed_tokens"] / delta["prefill_tokens"]
