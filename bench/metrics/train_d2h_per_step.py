"""Device-to-host fetches (the program's counter ``d2h``: one per chunk's
metrics, one per checkpoint's small leaves, one per large shard) per
training step of the window.
Formula: ``bench.program_trace.readings``."""

from bench import program_trace


def read(run):
    return program_trace.read(run, "train_d2h_per_step")
