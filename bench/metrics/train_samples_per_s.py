"""Training samples completed in the window over the window's length."""


def read(run):
    c = run.counters
    return c["samples"] / c["window_s"] if "samples" in c else None
