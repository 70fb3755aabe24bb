"""90th percentile, over every request due in the window, of the time
from its due time to its first token; a refused or unanswered request
counts as missing (infinite)."""
from bench import stats


def read(rec, trace):
    return stats.percentile(stats.ttfts(rec), 90) * 1e3
