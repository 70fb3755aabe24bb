"""Mean share of the engine's slots that decoded in a step, each step
weighted by its wall time."""
from bench import stats


def read(rec, trace):
    return 100.0 * stats.occupancy(rec)
