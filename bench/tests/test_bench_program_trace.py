"""The reduction of the program's own spans and op scopes
(``bench/program_trace.py``): on hand-made planes, and on the small trace
recorded on a TPU v5e (``test_bench_trace.py``), whose every existing
reading is pinned here so that a change to the reduction shows."""

import dataclasses
import json
import pathlib

import pytest

from bench import harness, peaks, program_trace as pt, spec, trace, work
from repro import obs

DATA = pathlib.Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "v5e_train_serve.xplane.pb"
PIN = DATA / "v5e_train_serve.pin.json"


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


def test_readings_per_step():
    r = pt.reduce_program(planes(), obs.SPANS)
    got = pt.readings(r, {"d2h": 25, "runner.steps": 10}, steps=10)
    assert got == pytest.approx({
        "train_sim_us_per_step": 40e-9 * 1e5,
        "train_stage_us_per_step": 40e-9 * 1e5,
        "train_dispatch_us_per_step": 50e-9 * 1e5,
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
    # both readers see the same device events
    old = dict(trace.load_planes(FIXTURE))["/device:TPU:0"]
    new = dict(loaded)["/device:TPU:0"]
    for line in ("XLA Ops", "XLA Modules"):
        assert [e[:3] for e in new[line]] == old[line]


def test_existing_readings_pinned_on_recorded_trace():
    """Every field of ``trace.Reduced`` and every metric reader of the
    benchmark reads on the recorded trace what it read when the program's
    spans were added."""
    pin = json.loads(PIN.read_text())
    r = trace.reduce_xplane(FIXTURE)
    assert dataclasses.asdict(r) == pin["reduced"]
    assert r.top_ops() == pin["top_ops"]
    assert r.top_idle() == pin["top_idle"]
    cell = spec.load("fpga-train-sgd")
    run = harness.Run(cell=cell, sizes=work.layer_sizes(cell.config),
                      peaks=peaks.PEAKS["TPU v5 lite"],
                      setup_s=pin["setup_s"], counters=pin["counters"],
                      trace=r)
    got = {m["name"]: harness.reader(m["name"])(run)
           for m in cell.per_layer + cell.end_to_end}
    assert got == pin["metrics"]
