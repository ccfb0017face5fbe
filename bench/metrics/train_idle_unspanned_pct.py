"""Share of the traced window's device-idle time under no program span on
the window's thread: host time the program's spans do not account for.
Formula: ``bench.program_trace.readings``."""

from bench import program_trace


def read(run):
    return program_trace.read(run, "train_idle_unspanned_pct")
