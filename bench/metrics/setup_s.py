"""Process start to the opening of the window: loading, weights,
compiling or loading programs, warm-up, and the load's pre-roll."""


def read(rec, trace):
    return rec.setup_s
