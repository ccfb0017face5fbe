"""Device busy time outside the fused training kernel per training step:
batch synthesis (the EPG simulator), staging and checkpoint copies.  The
trace names operations by HLO name only, so the simulator's share cannot
be told apart from the rest by name."""

KERNEL = "fused_train"


def read(run):
    t, steps = run.trace, run.counters.get("steps")
    if t is None or not steps:
        return None
    return (t.busy_s * t.n_devices - t.kernel_seconds(KERNEL)) / steps * 1e6
