"""The program's own spans and op scopes in a profiler trace of the
window, the readings taken from them, and a probe that prints them all.

``bench/trace.py`` reduces a trace by HLO op name and by the harness's own
spans, and through :func:`load` and :func:`reduce_program` by what the
program records itself, on every traced run:

* op scopes: every device op's ``tf_op`` stat, its name-scope path
  (``jit(chunk_step)/simulate/jit(sample_batch)/...``), kept in the event
  metadata of the device's plane, which ``jax.profiler.ProfileData`` does
  not expose; a reader of the ``XSpace`` protobuf's wire format below
  decodes it.  ``scope_seconds`` is the device time under each scope, a
  ``jit(f)`` component counted as ``f``;
* program spans: the host events named in ``repro.obs.SPANS``, by thread
  line.  Each device-idle instant of the window is put down to the
  innermost program span open at that instant on the window's own thread
  (``unspanned`` where none is); spans on other threads (the checkpoint
  flush's worker) are never blamed.

The counters (``repro.obs.COUNTS`` over the window) are the harness's, on
every run.  :func:`readings` holds the formulas of the metric readers in
``bench/metrics/`` that read spans, scopes and counters.

    PYTHONPATH=src python3 -m bench.program_trace --workload <cell> \\
        --seed <n> [--seconds <s>] [--keep <file.xplane.pb>]

runs one ``--trace 1`` run of the cell through ``bench.harness`` and prints
its result line with ``program``: the traced window's samples per second,
the idle split by program span, each span's loop-thread and self time, the
scope seconds, the lag from each chunk's dispatch to its program's start
on the device, the counters, and the cost of one span with no trace
running.
"""

from __future__ import annotations

import collections
import dataclasses
import statistics

from bench import trace

WINDOW = "window"
UNSPANNED = "unspanned"
CHUNK_PROGRAM = "jit_chunk_step"

# -- the XSpace wire format ---------------------------------------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map<int64,
# XEventMetadata>), stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata: name = 2, stats = 5; XStatMetadata: name = 2;
# XStat: metadata_id = 1, str_value = 5, ref_value = 7 (an interned string:
# the name of stat metadata ``ref_value``).


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field, skipped otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_entries(raw):
    """A map field's entries as {key: value bytes}."""
    out = {}
    for buf in raw:
        kv = dict(_fields(buf))
        out[kv.get(1, 0)] = kv.get(2, b"")
    return out


def op_scopes(path) -> dict:
    """{device plane name: {event name: tf_op}} of an ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(v)
            elif g == 5:
                stats.append(v)
        if not name.startswith("/device:"):
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                      for k, v in _map_entries(stats).items()}
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        scopes = {}
        for meta in _map_entries(events).values():
            ev_name, value = "", None
            for g, v in _fields(meta):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if st.get(1) == tf_op:
                        value = (bytes(st[5]).decode() if 5 in st
                                 else stat_names.get(st.get(7)))
            if value:
                scopes[ev_name] = value
        out[name] = scopes
    return out


def scope_path(tf_op: str) -> tuple:
    """``jit(chunk_step)/simulate/jit(sample_batch)/mul:`` -> ``("chunk_step",
    "simulate", "sample_batch")``: the scopes an op sits under, without the
    op itself."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    parts = path.split("/")[:-1]
    return tuple(p[4:-1] if p.startswith("jit(") and p.endswith(")") else p
                 for p in parts)


# -- planes --------------------------------------------------------------------


def load(path, spans) -> list:
    """An ``.xplane.pb`` in the plain form :func:`reduce_program` takes:
    ``[(plane name, {line name: [(name, start_ns, duration_ns, scopes)]})]``
    with the device planes' op and module lines (``scopes`` from each op's
    ``tf_op``) and, on host lines (``<index>:<thread name>``), the events
    named ``window`` or in ``spans`` (``scopes`` empty)."""
    from jax.profiler import ProfileData

    keep = frozenset(spans) | {WINDOW}
    tf_ops = op_scopes(path)
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        lines = {}
        if plane.name.startswith("/device:"):
            ops = tf_ops.get(plane.name, {})
            paths = {name: scope_path(tf_op) for name, tf_op in ops.items()}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        (e.name, e.start_ns, e.duration_ns,
                         paths.get(e.name, ()))
                        for e in line.events]
        else:
            # threads may share a name: a line is its place and its name
            for i, line in enumerate(plane.lines):
                kept = [(e.name, e.start_ns, e.duration_ns, ())
                        for e in line.events if e.name in keep]
                if kept:
                    lines[f"{i}:{line.name}"] = kept
        out.append((plane.name, lines))
    return out


@dataclasses.dataclass
class ProgramReduced:
    window_s: float
    busy_s: float                   # averaged over devices
    n_devices: int
    scope_seconds: dict             # scope -> device seconds (all devices)
    idle_by_program_span: dict      # innermost loop-thread span -> idle s
    idle_by_cause: dict             # the same, unspanned by harness span
    span_seconds: dict              # loop-thread span -> seconds in window
    self_seconds: dict              # loop-thread span -> seconds innermost
    dispatch_lags_s: list | None    # chunk program start - its dispatch


def innermost(spans, t0, t1) -> list:
    """``[(start, end, name)]`` tiling ``[t0, t1)``: at each instant the
    innermost of ``spans`` (``(start, end, name)`` of one thread) open then,
    the one opened last, or ``UNSPANNED``."""
    cuts = collections.defaultdict(list)
    for i, (s, e, _n) in enumerate(spans):
        s, e = max(s, t0), min(e, t1)
        if e > s:
            cuts[s].append((1, i))
            cuts[e].append((0, i))
    out, open_, at = [], {}, t0
    for x in sorted(cuts) + [t1]:
        if x > at:
            top = (max(open_, key=lambda i: (spans[i][0], -spans[i][1]))
                   if open_ else None)
            name = spans[top][2] if top is not None else UNSPANNED
            if out and out[-1][2] == name and out[-1][1] == at:
                out[-1] = (out[-1][0], x, name)
            else:
                out.append((at, x, name))
            at = x
        for opens, i in sorted(cuts.get(x, ())):
            if opens:
                open_[i] = True
            else:
                open_.pop(i, None)
    return out


def _pieces(segments, gaps):
    """``(name, start, end)`` of each part of ``segments`` that lies inside
    ``gaps`` (both sorted, each disjoint)."""
    j = 0
    for g0, g1 in gaps:
        while j < len(segments) and segments[j][1] <= g0:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < g1:
            s, e, name = segments[k]
            if min(e, g1) > max(s, g0):
                yield name, max(s, g0), min(e, g1)
            k += 1


def reduce_program(planes, spans, window: str = WINDOW) -> ProgramReduced:
    """Reduce ``planes`` (as :func:`load` gives them) to a
    :class:`ProgramReduced`: op scopes, and the idle gaps of the window
    split by the program spans (those named in ``spans``) of the window's
    own thread; by cause, the unspanned part of each gap is put down to
    the harness span that overlaps it most (``trace.HARNESS_SPANS``, or
    ``host``), as ``trace.reduce_planes`` puts down a whole gap."""
    spans = frozenset(spans)
    win, devices, by_line, harness = None, [], {}, []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            devices.append(lines)
            continue
        for lname, events in lines.items():
            for name, start, dur, _sc in events:
                if name == window and win is None:
                    win = (start, start + dur, (pname, lname))
                elif name in spans:
                    by_line.setdefault((pname, lname), []).append(
                        (start, start + dur, name))
                elif name in trace.HARNESS_SPANS:
                    harness.append((start, start + dur, name))
    if win is None:
        raise ValueError(f"trace has no host span named {window!r}")
    if not devices:
        raise ValueError("trace has no /device:TPU plane")
    w0, w1, loop_line = win
    loop = sorted(by_line.get(loop_line, ()))
    segments = innermost(loop, w0, w1)
    harness.sort()
    starts = [s for s, _e, _n in harness]
    n = len(devices)
    scope_ns = collections.Counter()
    busy_ns = 0.0
    idle, cause = collections.Counter(), collections.Counter()
    weight = 1.0 / n
    modules = []
    for lines in devices:
        intervals = []
        for _name, start, dur, scopes in lines.get("XLA Ops", ()):
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            intervals.append((s, e))
            for sc in set(scopes):
                scope_ns[sc] += e - s
        merged = trace._union(intervals)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        dev_idle, dev_cause = collections.Counter(), collections.Counter()
        for name, a, b in _pieces(segments, gaps):
            dev_idle[name] += (b - a) * 1e-9 * weight
            if name == UNSPANNED:
                name = trace._blame(starts, harness, a, b)
            dev_cause[name] += (b - a) * 1e-9 * weight
        idle.update(dev_idle)
        cause.update(dev_cause)
        modules += [start for name, start, _d, _s
                    in lines.get("XLA Modules", ())
                    if name.startswith(CHUNK_PROGRAM) and w0 <= start < w1]
    span_ns = collections.Counter()
    for s, e, name in loop:
        span_ns[name] += max(0, min(e, w1) - max(s, w0))
    self_ns = collections.Counter()
    for s, e, name in segments:
        self_ns[name] += e - s
    dispatches = [s for s, _e, name in loop
                  if name == "runner.dispatch" and w0 <= s < w1]
    lags = None
    if n == 1 and len(modules) == len(dispatches):
        lags = [(m - d) * 1e-9 for m, d in zip(sorted(modules), dispatches)]
    return ProgramReduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9 / n, n_devices=n,
        scope_seconds={k: v * 1e-9 for k, v in scope_ns.items()},
        idle_by_program_span=dict(idle), idle_by_cause=dict(cause),
        span_seconds={k: v * 1e-9 for k, v in span_ns.items()},
        self_seconds={k: v * 1e-9 for k, v in self_ns.items()
                      if k != UNSPANNED},
        dispatch_lags_s=lags)


def readings(p, counts: dict, steps: int) -> dict:
    """The per-step and share readings of one traced window (``p``, a
    :class:`ProgramReduced` or a ``trace.Reduced``): device time under
    ``simulate`` and ``stage``, ``runner.dispatch``'s self time, the loop
    thread's time in ``runner.fetch`` and in checkpoint restore, wait and
    snapshot, the share of device-idle time under no program span, and
    host fetches, each per step of the window (``steps``); ``counts`` are
    the counter deltas.  A reading whose scope, span or counter the trace
    lacks (a program without them) is left out."""
    if not steps:
        return {}
    per_step = 1e6 / steps
    out = {}
    for name, scope in (("train_sim_us_per_step", "simulate"),
                        ("train_stage_us_per_step", "stage")):
        if scope in p.scope_seconds:
            out[name] = p.scope_seconds[scope] * per_step
    if "runner.dispatch" in p.self_seconds:
        out["train_dispatch_us_per_step"] = (
            p.self_seconds["runner.dispatch"] * per_step)
    if "runner.fetch" in p.span_seconds:
        out["train_fetch_us_per_step"] = (
            p.span_seconds["runner.fetch"] * per_step)
    ckpt = [p.span_seconds[k] for k in ("ckpt.snapshot", "ckpt.wait",
                                        "ckpt.restore")
            if k in p.span_seconds]
    if ckpt:
        out["train_ckpt_us_per_step"] = sum(ckpt) * per_step
    idle = sum(p.idle_by_program_span.values())
    if p.span_seconds and idle > 0:
        out["train_idle_unspanned_pct"] = (
            100.0 * p.idle_by_program_span.get(UNSPANNED, 0.0) / idle)
    if "d2h" in counts:
        out["train_d2h_per_step"] = counts["d2h"] / steps
    return out


def read(run, name: str):
    """Reading ``name`` of :func:`readings` for a harness ``Run``: its trace
    and counters, per step of its window; None untraced, or where the run
    has nothing to read it from."""
    if run.trace is None:
        return None
    return readings(run.trace, run.counters,
                    run.counters.get("steps", 0)).get(name)


def _top(d: dict) -> list:
    return sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])


def span_cost_ns(reps: int = 200_000) -> dict:
    """Nanoseconds of one ``obs.span`` entered and left with no trace
    running, with and without arguments, less an empty loop's."""
    import time

    from repro import obs

    def clock(body):
        t0 = time.perf_counter_ns()
        body()
        return (time.perf_counter_ns() - t0) / reps

    def empty():
        for _ in range(reps):
            pass

    def bare():
        for _ in range(reps):
            with obs.span("runner.fetch"):
                pass

    def with_args():
        for i in range(reps):
            with obs.span("runner.dispatch", step=i, n=16):
                pass

    base = min(clock(empty) for _ in range(3))
    return {"span_ns": min(clock(bare) for _ in range(3)) - base,
            "span_args_ns": min(clock(with_args) for _ in range(3)) - base}


def traced_run(cell, seed: int, seconds: float, t_start: float,
               keep=None) -> dict:
    """One ``--trace 1`` run of ``cell`` through ``bench.harness``: its
    result line with ``program``, what the harness's ``Run`` holds of the
    program's spans, scopes and counters; the trace is copied to ``keep``
    if given."""
    from bench import harness

    run = harness.measure(cell, seed, seconds, True, t_start,
                          keep_trace=keep)
    result = harness.line(run)
    t, c = run.trace, run.counters
    lags = t.dispatch_lags_s
    result["program"] = {
        "steps": c["steps"],
        "samples_per_s": c["samples"] / c["window_s"],
        "idle_by_program_span": _top(t.idle_by_program_span),
        "span_seconds": _top(t.span_seconds),
        "self_seconds": _top(t.self_seconds),
        "scope_seconds": _top(t.scope_seconds),
        "counts": c,
        "lowered_in_window": run.lowered,
        "dispatch_lag_s": None if lags is None else {
            "n": len(lags), "min": min(lags, default=None),
            "median": statistics.median(lags) if lags else None,
            "negative": sum(lag < 0 for lag in lags)},
    }
    return result


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep", default=None,
                    help="copy the window's .xplane.pb here")
    args = ap.parse_args(argv)

    import jax

    from bench import harness, spec
    cell = spec.load(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("bench.program_trace: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_cache()
    result = traced_run(cell, args.seed, args.seconds, t_start, args.keep)
    result["program"]["span_cost"] = span_cost_ns()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
