"""Loop-thread time in the program spans ``ckpt.snapshot``, ``ckpt.wait``
and ``ckpt.restore`` per training step of the traced window (the flush
runs on its worker and is not counted).
Formula: ``bench.program_trace.readings``."""

from bench import program_trace


def read(run):
    return program_trace.read(run, "train_ckpt_us_per_step")
