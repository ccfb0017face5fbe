"""Whole training step's share of the chip's bf16 peak: forward and
backward operations per sample at real widths (the cell's kind gives
them), times samples per second of the (traced) window."""


def read(run):
    c = run.counters
    if not c.get("samples"):
        return None
    rate = c["samples"] / c["window_s"]
    return (100.0 * rate * run.kind.ops_per_sample()
            / run.peaks["bf16_flops_per_s"])
