"""Loop-thread time in the program span ``runner.fetch`` (copying a chunk's
per-step metrics to the host) per training step of the traced window.
Formula: ``bench.program_trace.readings``."""

from bench import program_trace


def read(run):
    return program_trace.read(run, "train_fetch_us_per_step")
