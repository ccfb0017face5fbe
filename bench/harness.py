"""One run of one cell: set-up, the measured window, the trace reduction,
the correctness comparison and the result line.

The generator kind of a cell comes from its traffic file (``kind``) and is
the class ``CELL`` of ``bench/kinds/<kind>.py``; the metrics it reports
come from ``BENCHMARK.json`` and are read by the reader files in
``bench/metrics``.  Nothing here names a cell or a kind.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import math
import shutil
import tempfile
import time

import jax

from bench import peaks, spec, trace, work

METRICS_DIR = spec.BENCH / "metrics"
# a program lowered inside the window is a compile the warm-up missed
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: spec.Cell
    sizes: tuple
    peaks: dict
    setup_s: float
    counters: dict
    trace: trace.Reduced | None


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


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float) -> tuple:
    """(result line, programs lowered inside the window, every number the
    kind computed against the reference) of one run.  The line's last key,
    ``checks``, holds each number compared beside its limit.  ``t_start`` is when the process started, so that ``setup_s``
    counts imports and JAX's start."""
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
        try:
            with jax.profiler.TraceAnnotation("window"):
                counters = c.window()
        finally:
            lowerings.on = False
            if traced:
                jax.profiler.stop_trace()
        stats = dev.memory_stats() or {}
        mem_peak = int(stats.get("peak_bytes_in_use", 0))
        reduced = None
        if traced:
            files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
            reduced = trace.reduce_xplane(files[0])
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    # the kind computes every number it can; the cell's file says which
    # are compared, and their limits
    numbers = c.check()
    checks = {k: {"value": _finite(numbers[k]), "limit": lim}
              for k, lim in cell.limits.items()}
    correct = (counters["failed"] == 0
               and all(math.isfinite(numbers[k]) and numbers[k] <= lim
                       for k, lim in cell.limits.items()))
    r = Run(cell=cell, sizes=work.layer_sizes(cell.config), peaks=table,
            setup_s=setup_s, counters=counters, trace=reduced)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": int(counters["attempted"]),
              "failed": int(counters["failed"]), "metrics": metrics,
              "device": device}
    if traced:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.top_idle()}
    result["checks"] = checks
    return result, lowerings.n, numbers
