"""Device time under the program's name scope ``simulate`` (batch synthesis,
``data/pipeline.batch_at``) per training step of the traced window.
Formula: ``bench.program_trace.readings``."""

from bench import program_trace


def read(run):
    return program_trace.read(run, "train_sim_us_per_step")
