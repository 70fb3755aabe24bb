"""Output tokens of every request stamped inside the window, over the
window's seconds."""
from bench import stats


def read(rec, trace):
    return stats.tokens_in_window(rec) / rec.seconds
