"""Device time under the program's name scope ``stage`` (the fused chunk's
join and the kernel layout's padding) per training step of the traced
window.
Formula: ``bench.program_trace.readings``."""

from bench import program_trace


def read(run):
    return program_trace.read(run, "train_stage_us_per_step")
