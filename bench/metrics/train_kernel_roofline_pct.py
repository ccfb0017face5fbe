"""Fused training kernel's share of its roofline: the least time the chip
needs for the kernel's work at the configuration's real widths (forward
and backward operations against the bf16 peak, since no f32 peak is
published, or its HBM bytes against the bandwidth, whichever is longer;
the cell's kind counts both) over the kernel's device time."""

from bench import work

KERNEL = "fused_train"


def read(run):
    t, c = run.trace, run.counters
    if t is None or not c.get("samples"):
        return None
    secs = t.kernel_seconds(KERNEL)
    if secs <= 0:
        return None
    counted = run.kind.kernel_work(KERNEL, c["samples"],
                                   t.kernel_launches(KERNEL))
    if counted is None:
        return None
    share, _ = work.roofline_share(*counted, secs,
                                   run.peaks["bf16_flops_per_s"],
                                   run.peaks["hbm_bytes_per_s"])
    return share
