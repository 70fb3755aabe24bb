"""How late the load generator ran: 95th percentile of submit time minus
due time over requests submitted in the window. Requests wait for the
step in flight, so this is the engine tick as the generator sees it."""
from bench import stats


def read(rec, trace):
    return stats.percentile(stats.lags(rec), 95) * 1e3
