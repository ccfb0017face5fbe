"""The reduction of the program's own spans and op scopes
(``bench/program_trace.py``) and the metric readers that read them: on
hand-made planes, and on the small trace recorded on a TPU v5e
(``test_bench_trace.py``), whose every reading from before the program's
spans were read is pinned here so that a change to the reduction shows."""

import json
import pathlib

import pytest

from bench import harness, peaks, program_trace as pt, spec, trace
from repro import obs

DATA = pathlib.Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "v5e_train_serve.xplane.pb"
PIN = DATA / "v5e_train_serve.pin.json"
# the readers of the program's spans, scopes and counters
READERS = ("train_sim_us_per_step", "train_stage_us_per_step",
           "train_dispatch_us_per_step", "train_fetch_us_per_step",
           "train_ckpt_us_per_step", "train_idle_unspanned_pct",
           "train_d2h_per_step")


def planes():
    """The loop's thread: a retire with its fetch nested, a snapshot and a
    wait side by side, a dispatch; the worker's thread: one flush over the
    whole window.  The device runs three ops under named scopes."""
    loop = [("window", 0, 1000, ()), ("train", 0, 1000, ()),
            ("runner.retire", 100, 300, ()), ("runner.fetch", 150, 100, ()),
            ("ckpt.snapshot", 500, 100, ()), ("ckpt.wait", 600, 100, ()),
            ("runner.dispatch", 800, 50, ())]
    worker = [("ckpt.flush", 0, 1000, ())]
    dev = {"XLA Ops": [
        ("%fusion.1", 260, 40, ("chunk_step", "simulate", "sample_batch")),
        ("%concatenate.2", 860, 40, ("chunk_step", "stage")),
        ("%fused_train_call", 900, 50, ("chunk_step", "fused_train_call"))],
        "XLA Modules": [("jit_chunk_step(7)", 855, 100, ())]}
    return [("/host:CPU", {"0:python": loop, "1:python": worker}),
            ("/device:TPU:0", dev)]


def test_innermost_span_wins():
    spans = [(100, 400, "outer"), (100, 200, "inner"), (300, 350, "late")]
    assert pt.innermost(spans, 0, 500) == [
        (0, 100, pt.UNSPANNED), (100, 200, "inner"), (200, 300, "outer"),
        (300, 350, "late"), (350, 400, "outer"), (400, 500, pt.UNSPANNED)]
    assert pt.innermost([], 0, 10) == [(0, 10, pt.UNSPANNED)]


def test_reduce_hand_made_planes():
    r = pt.reduce_program(planes(), obs.SPANS)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(130e-9)
    # gaps [0,260), [300,860), [950,1000): the nested fetch wins over its
    # retire, the snapshot and the wait split their gap, the worker's flush
    # is never blamed, and the rest lies under no program span
    assert r.idle_by_program_span == pytest.approx({
        "runner.retire": 160e-9, "runner.fetch": 100e-9,
        "ckpt.snapshot": 100e-9, "ckpt.wait": 100e-9,
        "runner.dispatch": 50e-9, pt.UNSPANNED: 360e-9})
    assert sum(r.idle_by_program_span.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert r.span_seconds == pytest.approx({
        "runner.retire": 300e-9, "runner.fetch": 100e-9,
        "ckpt.snapshot": 100e-9, "ckpt.wait": 100e-9,
        "runner.dispatch": 50e-9})
    assert r.self_seconds == pytest.approx({
        "runner.retire": 200e-9, "runner.fetch": 100e-9,
        "ckpt.snapshot": 100e-9, "ckpt.wait": 100e-9,
        "runner.dispatch": 50e-9})
    assert r.scope_seconds == pytest.approx({
        "chunk_step": 130e-9, "simulate": 40e-9, "sample_batch": 40e-9,
        "stage": 40e-9, "fused_train_call": 50e-9})
    assert r.dispatch_lags_s == pytest.approx([55e-9])
    # by cause, the unspanned part goes to the harness span around it
    assert r.idle_by_cause == pytest.approx({
        **{k: v for k, v in r.idle_by_program_span.items()
           if k != pt.UNSPANNED}, "train": 360e-9})


def test_readings_per_step():
    r = pt.reduce_program(planes(), obs.SPANS)
    got = pt.readings(r, {"d2h": 25, "runner.steps": 10}, steps=10)
    assert got == pytest.approx({
        "train_sim_us_per_step": 40e-9 * 1e5,
        "train_stage_us_per_step": 40e-9 * 1e5,
        "train_dispatch_us_per_step": 50e-9 * 1e5,
        "train_fetch_us_per_step": 100e-9 * 1e5,
        "train_ckpt_us_per_step": 200e-9 * 1e5,
        "train_idle_unspanned_pct": 100.0 * 360 / 870,
        "train_d2h_per_step": 2.5})
    assert pt.readings(r, {}, steps=0) == {}
    # a program without spans, scopes or counters: nothing to read
    bare = pt.reduce_program(pt.load(FIXTURE, ()), ())
    assert pt.readings(bare, {}, steps=10) == {}


def test_reduce_needs_window_and_device():
    with pytest.raises(ValueError):
        pt.reduce_program(planes(), obs.SPANS, window="nope")
    with pytest.raises(ValueError):
        pt.reduce_program(planes()[:1], obs.SPANS)


@pytest.mark.parametrize("tf_op,path", [
    ("jit(chunk_step)/simulate/jit(sample_batch)/mul:",
     ("chunk_step", "simulate", "sample_batch")),
    ("jit(chunk_step)/reduce_sum:", ("chunk_step",)),
    ("jit(fwd)/dot_general", ("fwd",)),
])
def test_scope_path(tf_op, path):
    assert pt.scope_path(tf_op) == path


def test_recorded_v5e_trace_scopes():
    loaded = pt.load(FIXTURE, obs.SPANS)
    r = pt.reduce_program(loaded, obs.SPANS)
    base = trace.reduce_xplane(FIXTURE)
    assert (r.window_s, r.busy_s) == (base.window_s, base.busy_s)
    assert r.scope_seconds["sample_batch"] > 0
    assert r.scope_seconds["fused_train_call"] == pytest.approx(
        base.kernel_seconds("fused_train_call"))
    top = sum(r.scope_seconds.get(k, 0.0)
              for k in ("chunk_step", "fwd", "dynamic_slice"))
    assert 0 < top <= r.busy_s
    # the trace predates the program's spans: all idle time is unspanned
    assert r.idle_by_program_span == pytest.approx(
        {pt.UNSPANNED: r.window_s - r.busy_s})
    # the harness's reduction carries the program's
    assert (base.scope_seconds, base.idle_by_program_span) == (
        pt.reduce_program(pt.load(FIXTURE, ()), ()).scope_seconds,
        r.idle_by_program_span)


def _run(reduced, counters, setup_s=1.0):
    cell = spec.load("fpga-train-sgd")
    return harness.Run(cell=cell, kind=harness.kind("train")(cell, 1),
                       peaks=peaks.PEAKS["TPU v5 lite"], setup_s=setup_s,
                       counters=counters, trace=reduced)


def test_existing_readings_pinned_on_recorded_trace():
    """Every field of ``trace.Reduced`` and every metric reader of the
    benchmark that predates the reading of the program's spans reads on
    the recorded trace what it read when the program's spans were added;
    the readers added since find nothing to read in a trace that has no
    program spans and a window without counters."""
    pin = json.loads(PIN.read_text())
    r = trace.reduce_xplane(FIXTURE, spans=obs.SPANS)
    assert {k: getattr(r, k) for k in pin["reduced"]} == pin["reduced"]
    assert r.top_ops() == pin["top_ops"]
    assert r.top_idle() == pin["top_idle"]
    run = _run(r, pin["counters"], pin["setup_s"])
    cell = run.cell
    got = {m["name"]: harness.reader(m["name"])(run)
           for m in cell.per_layer + cell.end_to_end}
    assert {k: got.pop(k) for k in pin["metrics"]} == pin["metrics"]
    assert set(got) == set(READERS)
    assert all(v is None for v in got.values()), got


@pytest.mark.parametrize("source", ["recorded", "hand_made"])
@pytest.mark.parametrize("name", READERS)
def test_program_readers_equal_readings(name, source):
    """Each reader of ``bench/metrics`` gives what ``program_trace.readings``
    gives for the same trace and counters, per step of the window."""
    counters = {"steps": 10, "samples": 2560, "window_s": 1e-6,
                "attempted": 10, "failed": 0, "d2h": 25, "runner.chunks": 1}
    if source == "recorded":
        r = trace.reduce_xplane(FIXTURE, spans=obs.SPANS)
    else:
        r = trace.reduce_program_planes(planes(), obs.SPANS)
    want = pt.readings(r, counters, counters["steps"]).get(name)
    assert harness.reader(name)(_run(r, counters)) == want
    if source == "hand_made":
        assert want is not None
    assert harness.reader(name)(_run(None, counters)) is None


def test_idle_gaps_name_program_spans():
    """``breakdown.idle_gaps`` puts idle time down to the innermost program
    span, and to the harness's span only where none is open."""
    r = trace.reduce_program_planes(planes(), obs.SPANS)
    names = [k for k, _v in r.top_idle()]
    assert names[0] == "train" and "runner.fetch" in names
    assert set(names) == {"train", "runner.retire", "runner.fetch",
                          "ckpt.snapshot", "ckpt.wait", "runner.dispatch"}
    assert sum(v for _k, v in r.top_idle()) == pytest.approx(
        r.window_s - r.busy_s)
