"""Device time of the fused training kernel per training step."""

KERNEL = "fused_train"   # fused_train_call (SGD), fused_train_adam_call


def read(run):
    t, steps = run.trace, run.counters.get("steps")
    if t is None or not steps:
        return None
    s = t.kernel_seconds(KERNEL)
    return s / steps * 1e6 if s > 0 else None
