"""95th percentile of every gap between consecutive tokens of one
request, both inside the window, over all requests."""
from bench import stats


def read(rec, trace):
    return stats.percentile(stats.gaps(rec), 95) * 1e3
