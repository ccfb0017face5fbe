"""Loop-thread self time of the program span ``runner.dispatch`` (launching
a chunk on the device) per training step of the traced window.
Formula: ``bench.program_trace.readings``."""

from bench import program_trace


def read(run):
    return program_trace.read(run, "train_dispatch_us_per_step")
