"""Whole training step's share of the chip's bf16 peak: forward and
backward operations per sample at real widths, times samples per second
of the (traced) window."""

from bench import work


def read(run):
    c = run.counters
    if not c.get("samples"):
        return None
    rate = c["samples"] / c["window_s"]
    return (100.0 * rate * work.train_ops_per_row(run.sizes)
            / run.peaks["bf16_flops_per_s"])
