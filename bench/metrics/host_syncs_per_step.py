"""Device-to-host reads the engine made per step in the window: the
difference of its ``host_syncs`` counter (one read per sampled token,
one per cache leaf of a tier gather) over the steps run between the
window's two counter snapshots. None for a program without the counter."""


def read(rec, trace):
    before = rec.counters.get("open", {}).get("host_syncs")
    after = rec.counters.get("close", {}).get("host_syncs")
    steps = rec.window_steps()
    if before is None or after is None or not steps:
        return None
    return (after - before) / len(steps)
