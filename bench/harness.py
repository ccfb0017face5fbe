"""One run of one cell: set-up, the measured window, the trace reduction,
the correctness comparison and the result line.

The generator kind of a cell comes from its traffic file (``kind``) and is
the class ``CELL`` of ``bench/kinds/<kind>.py``; the metrics it reports
come from ``BENCHMARK.json`` and are read by the reader files in
``bench/metrics``.  Nothing here names a cell, a kind or a model family:
the work of a cell at its real widths is its kind object's to give
(``Run.kind``), and the program's counters and spans are read by the
names ``repro.obs`` lists.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import importlib.util
import math
import shutil
import tempfile
import time

import jax

from bench import peaks, spec, trace
from repro import obs

METRICS_DIR = spec.BENCH / "metrics"
# a program lowered inside the window is a compile the warm-up missed
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run, and what its result line is
    made of."""
    cell: spec.Cell
    # the cell's kind object, its set-up done; a reader of work at real
    # widths asks it (the training kind: ops_per_sample, kernel_work)
    kind: object
    peaks: dict
    setup_s: float
    # the kind's counters over the window, beside the program's counter
    # deltas (repro.obs.COUNTS) under their own names
    counters: dict
    trace: trace.Reduced | None
    device: dict = dataclasses.field(default_factory=dict)
    numbers: dict = dataclasses.field(default_factory=dict)
    lowered: int = 0             # programs lowered inside the window


def enable_cache() -> None:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set), holding every program,
    however small or quick to compile, so that only a checkout's first run
    of a cell compiles."""
    from repro.launch import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def kind(name: str):
    """The cell class of generator kind ``name``: ``CELL`` of
    ``bench/kinds/<name>.py``."""
    return importlib.import_module(f"bench.kinds.{name}").CELL


def reader(name: str):
    """The ``read(run)`` function of metric ``name``: from
    ``bench/metrics/<name>.py``, else from the file of the name's part
    before its first dot (one reader for ``x.train`` and ``x.serve``)."""
    for stem in (name, name.split(".", 1)[0]):
        path = METRICS_DIR / f"{stem}.py"
        if path.exists():
            s = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(s)
            s.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} in {METRICS_DIR}")


class _Lowerings:
    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, _duration, **_kw):
        if self.on and event == _LOWERING_EVENT:
            self.n += 1


def _finite(x):
    return x if math.isfinite(x) else str(x)


def measure(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            t_start: float, keep_trace=None) -> Run:
    """Set-up, the window (profiled if ``traced``, the trace reduced and
    copied to ``keep_trace`` if given), and the comparison with the
    reference.  ``t_start`` is when the process started, so that
    ``setup_s`` counts imports and JAX's start."""
    devices = jax.devices()
    dev = devices[0]
    table = peaks.peaks_for(dev.device_kind)
    if traced:
        seconds = min(seconds, float(cell.traffic.get("trace_seconds",
                                                      seconds)))
    c = kind(cell.traffic["kind"])(cell, seed)
    c.setup(seconds)
    lowerings = _Lowerings()
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    setup_s = time.perf_counter() - t_start
    try:
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        lowerings.on = True
        before = collections.Counter(obs.COUNTS)
        try:
            with jax.profiler.TraceAnnotation("window"):
                counters = c.window()
        finally:
            lowerings.on = False
            if traced:
                jax.profiler.stop_trace()
        deltas = {k: v - before[k] for k, v in obs.COUNTS.items()
                  if v != before[k]}
        counters = {**deltas, **counters}
        stats = dev.memory_stats() or {}
        reduced = None
        if traced:
            files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
            if keep_trace:
                shutil.copy(files[0], keep_trace)
            reduced = trace.reduce_xplane(files[0], spans=obs.SPANS)
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    # the kind computes every number it can; the cell's file says which
    # are compared, and their limits
    return Run(cell=cell, kind=c, peaks=table, setup_s=setup_s,
               counters=counters, trace=reduced, device=device,
               numbers=c.check(), lowered=lowerings.n)


def line(r: Run) -> dict:
    """The result line of run ``r``: its cell's end-to-end metrics, or
    traced its per-layer metrics, each from its reader, and last,
    ``checks``, each number compared beside its limit."""
    cell, counters, numbers = r.cell, r.counters, r.numbers
    checks = {k: {"value": _finite(numbers[k]), "limit": lim}
              for k, lim in cell.limits.items()}
    correct = (counters["failed"] == 0
               and all(math.isfinite(numbers[k]) and numbers[k] <= lim
                       for k, lim in cell.limits.items()))
    metrics = {}
    traced = r.trace is not None
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(r.device)
    result = {"correct": bool(correct), "attempted": int(counters["attempted"]),
              "failed": int(counters["failed"]), "metrics": metrics,
              "device": device}
    if traced:
        device["busy_s"] = r.trace.busy_s
        device["window_s"] = r.trace.window_s
        result["breakdown"] = {"device_ops": r.trace.top_ops(),
                               "idle_gaps": r.trace.top_idle()}
    result["checks"] = checks
    return result


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float) -> tuple:
    """(result line, programs lowered inside the window, every number the
    kind computed against the reference) of one run (:func:`measure`,
    :func:`line`)."""
    r = measure(cell, seed, seconds, traced, t_start)
    return line(r), r.lowered, r.numbers
