"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The window is the host span named ``window`` that the harness opens around
its measured loop.  Within it:

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
  over the devices;
* per-operation device time, keyed by the HLO operation's name without its
  ``%`` and numeric suffix (``%fused_train_call.1`` -> ``fused_train_call``;
  a Pallas kernel keeps the name of the function that calls it);
* program launches: events on the ``XLA Modules`` line;
* idle gaps: the stretches of the window in which no device operation ran,
  each put down to the harness span (``train``, ``enqueue``, ``poll``,
  ``drain``) that overlaps it most, or to ``host`` where none does
  (``idle_by_span``);
* the program's own record (``bench/program_trace.py``), read from a trace
  file by :func:`reduce_xplane`: device time under each name scope, the
  window thread's program spans, and the idle time put down to the
  innermost program span open, the harness span where none is
  (``idle_by_cause``, which ``breakdown.idle_gaps`` reports).

Device and host events share the profiler's clock; they can disagree by
some hundreds of microseconds, which moves a gap's attribution, not its
length.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

HARNESS_SPANS = ("train", "enqueue", "poll", "drain")
_OP_NAME = re.compile(r"%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)*(?:\s|$|\.clone)")


def op_name(event_name: str) -> str:
    """``%fused_train_call.1 = (...) custom-call(...)`` -> ``fused_train_call``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _top(d: dict, n: int) -> list:
    return sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:n]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                   # averaged over devices
    n_devices: int
    op_seconds: dict                # op name -> device seconds (all devices)
    op_count: dict                  # op name -> events
    launches: int                   # XLA module executions (all devices)
    idle_by_span: dict              # harness span -> idle seconds
    # the program's own record, from a trace file (empty from bare planes):
    # name scope -> device seconds (all devices)
    scope_seconds: dict = dataclasses.field(default_factory=dict)
    # window-thread program span -> seconds in the window, and innermost
    span_seconds: dict = dataclasses.field(default_factory=dict)
    self_seconds: dict = dataclasses.field(default_factory=dict)
    # innermost window-thread program span (or ``unspanned``) -> idle s
    idle_by_program_span: dict = dataclasses.field(default_factory=dict)
    # the same, with the unspanned part put down to the harness spans
    idle_by_cause: dict = dataclasses.field(default_factory=dict)
    dispatch_lags_s: list | None = None   # chunk program start - dispatch

    def kernel_seconds(self, prefix: str) -> float:
        return sum(v for k, v in self.op_seconds.items()
                   if k.startswith(prefix))

    def kernel_launches(self, prefix: str) -> int:
        return sum(v for k, v in self.op_count.items()
                   if k.startswith(prefix))

    def top_ops(self, n: int = 10) -> list:
        return _top(self.op_seconds, n)

    def top_idle(self, n: int = 10) -> list:
        return _top(self.idle_by_cause or self.idle_by_span, n)


def reduce_planes(planes, window: str = "window",
                  spans=HARNESS_SPANS) -> Reduced:
    """Reduce planes, given as ``[(plane_name, {line_name: [(name, start_ns,
    duration_ns), ...]})]``, to a :class:`Reduced` summary."""
    win = None
    host_spans = []
    devices = []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            devices.append(lines)
            continue
        for events in lines.values():
            for name, start, dur in events:
                if name == window and win is None:
                    win = (start, start + dur)
                elif name in spans:
                    host_spans.append((start, start + dur, name))
    if win is None:
        raise ValueError(f"trace has no host span named {window!r}")
    if not devices:
        raise ValueError("trace has no /device:TPU plane")
    w0, w1 = win
    op_ns = collections.Counter()
    op_n = collections.Counter()
    launches = 0
    busy_ns = 0.0
    idle = collections.Counter()
    host_spans.sort()
    starts = [s for s, _e, _n in host_spans]
    for lines in devices:
        intervals = []
        for name, start, dur in lines.get("XLA Ops", ()):
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            intervals.append((s, e))
            op_ns[op_name(name)] += e - s
            op_n[op_name(name)] += 1
        for _name, start, dur in lines.get("XLA Modules", ()):
            if w0 <= start < w1:
                launches += 1
        merged = _union(intervals)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                idle[_blame(starts, host_spans, g0, g1)] += (g1 - g0) * 1e-9
    n = len(devices)
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   n_devices=n,
                   op_seconds={k: v * 1e-9 for k, v in op_ns.items()},
                   op_count=dict(op_n), launches=launches,
                   idle_by_span={k: v / n for k, v in idle.items()})


def _blame(starts, host_spans, g0, g1) -> str:
    """The harness span overlapping [g0, g1) most.  The spans come from one
    thread, one after another, so only those just before ``g1`` can."""
    best, name = 0.0, "host"
    i = bisect.bisect_left(starts, g1) - 1
    while i >= 0:
        s, e, nm = host_spans[i]
        if e <= g0:
            break
        ov = min(e, g1) - max(s, g0)
        if ov > best:
            best, name = ov, nm
        i -= 1
    return name


def reduce_program_planes(planes, spans=(), window: str = "window"
                          ) -> Reduced:
    """Reduce planes as ``program_trace.load`` gives them: by HLO op name
    and harness span as :func:`reduce_planes` does, and by the program's
    name scopes and its spans named in ``spans``."""
    from bench import program_trace

    harness = frozenset((window,) + HARNESS_SPANS)
    bare = [(pname, {ln: [e[:3] for e in events
                          if pname.startswith("/device:") or e[0] in harness]
                     for ln, events in lines.items()})
            for pname, lines in planes]
    p = program_trace.reduce_program(planes, spans, window)
    return dataclasses.replace(
        reduce_planes(bare, window=window),
        scope_seconds=p.scope_seconds, span_seconds=p.span_seconds,
        self_seconds=p.self_seconds,
        idle_by_program_span=p.idle_by_program_span,
        idle_by_cause=p.idle_by_cause, dispatch_lags_s=p.dispatch_lags_s)


def reduce_xplane(path, window: str = "window", spans=()) -> Reduced:
    """Reduce an ``.xplane.pb`` by :func:`reduce_program_planes`; ``spans``
    are the program's span names (``repro.obs.SPANS``), given by the caller
    so that a span the program adds is read with no edit here."""
    from bench import program_trace

    spans = tuple(spans)
    planes = program_trace.load(path, spans + HARNESS_SPANS + (window,))
    return reduce_program_planes(planes, spans, window)
