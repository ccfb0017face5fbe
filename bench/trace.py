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
  ``drain``) that overlaps it most, or to ``host`` where none does.

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


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                   # averaged over devices
    n_devices: int
    op_seconds: dict                # op name -> device seconds (all devices)
    op_count: dict                  # op name -> events
    launches: int                   # XLA module executions (all devices)
    idle_by_span: dict              # harness span -> idle seconds

    def kernel_seconds(self, prefix: str) -> float:
        return sum(v for k, v in self.op_seconds.items()
                   if k.startswith(prefix))

    def kernel_launches(self, prefix: str) -> int:
        return sum(v for k, v in self.op_count.items()
                   if k.startswith(prefix))

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_seconds.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.idle_by_span.items()),
                      key=lambda kv: -kv[1])[:n]


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


def load_planes(path, host_names=("window",) + HARNESS_SPANS) -> list:
    """Read an ``.xplane.pb`` into the plain form :func:`reduce_planes`
    takes: the device planes' op and module lines, and the host events
    named in ``host_names``."""
    from jax.profiler import ProfileData

    host_names = frozenset(host_names)
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith("/device:")
        lines = {}
        for line in plane.lines:
            if device:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                        for e in line.events]
            else:
                kept = [(e.name, e.start_ns, e.duration_ns)
                        for e in line.events if e.name in host_names]
                if kept:
                    lines[line.name] = kept
        out.append((plane.name, lines))
    return out


def reduce_xplane(path, window: str = "window") -> Reduced:
    return reduce_planes(load_planes(path), window=window)
