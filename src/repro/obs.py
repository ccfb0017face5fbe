"""Stable names for the program's host spans and counters.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs it records a host span on the profiler's host plane, on
the same clock as the device's ops, kept in memory until the trace stops;
otherwise it costs one object's construction.  ``count(name, n)`` adds to
``COUNTS``, an in-memory counter that is always on; counters are
incremented at the boundaries the spans mark, so ratios (host fetches per
step, bytes per checkpoint) are counted where the work happens.
"""

from __future__ import annotations

import collections

import jax

SPANS = (
    "runner.dispatch",   # launching a chunk (or one step) on the device
    "runner.fetch",      # copying a chunk's per-step metrics to the host
    "runner.retire",     # a chunk's fetch, straggler update and callbacks
    "runner.sync",       # waiting for the device at the loop's exit
    "ckpt.restore",      # reading the latest checkpoint back
    "ckpt.wait",         # waiting for the previous checkpoint's flush
    "ckpt.snapshot",     # device->host copy of the state and its manifest
    "ckpt.flush",        # file writes, rename and clean-up of old steps
)
_SPAN_SET = frozenset(SPANS)

# runner.chunks, runner.steps, d2h (device->host fetches: a checkpoint's
# small leaves make one), ckpt.saves, ckpt.bytes (host bytes snapshotted),
# ckpt.packed_leaves (small leaves written into a checkpoint's one pack file)
COUNTS: collections.Counter = collections.Counter()


def span(name: str, **args):
    """Context manager: a host span ``name`` (one of ``SPANS``), with
    ``args`` recorded beside it."""
    if name not in _SPAN_SET:
        raise ValueError(f"unknown span {name!r}; add it to obs.SPANS")
    return jax.profiler.TraceAnnotation(name, **args)


def count(name: str, n: int = 1) -> None:
    COUNTS[name] += n
