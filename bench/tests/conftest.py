"""Shared helpers for the benchmark's CPU tests: each cell cut to a size
the CPU runs in seconds, with Pallas interpreted and peaks for the CPU."""

import dataclasses

import pytest

from bench import peaks, spec

SMALL = {
    "fpga-train-sgd": dict(batch=16, tile_batch=8, chunk_steps=2,
                           ckpt_every=4),
}


def small_cell(name):
    cell = spec.load(name)
    return dataclasses.replace(cell, traffic={**cell.traffic, **SMALL[name]})


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The harness looks the device up in the peak table; the CPU has no
    row, so the tests lend it the v5e's."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
