"""A cell of another model family joins the benchmark as new files only: a
planted benchmark whose configuration has none of the MLP's widths, a
non-empty ``reduced`` and a precision for training alone, run through the
harness with a kind of its own.  The program's counters reach the readers
without the kind counting them."""

import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, spec
from repro import obs

CELL = "planted-lm-train"
CONFIG = {
    "name": "planted-lm", "source": "https://example.org/planted-lm",
    "hidden_size": 64, "num_hidden_layers": 2, "vocab_size": 128,
    "reduced": ["num_hidden_layers"],
    "assumed": {"vocab_size": "a slice of the vocabulary"},
    "precision": {"train": "highest"},
}
BENCH = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"],
    "run_seconds": 10,
    "configs": [{"name": "planted-lm", "source": CONFIG["source"],
                 "file": "bench/configs/planted-lm.json",
                 "reduced": ["num_hidden_layers"], "why": "planted"}],
    "workloads": [{"name": CELL, "config": "planted-lm",
                   "traffic": "planted-train", "chips": 1, "why": "planted"}],
    "end_to_end": [
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"},
        {"name": "train_samples_per_s", "unit": "samples/s",
         "better": "higher", "bound": 0.15, "source": "host_clock",
         "workloads": [CELL]}],
    "per_layer": [
        {"name": "train_mfu_pct", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "training step",
         "moves": "train_samples_per_s", "workloads": [CELL]}],
}
STEPS = 3


class PlantedCell:
    """A training kind of its own: steps of one matmul at the
    configuration's width, counting host fetches as the program does."""

    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.width = int(cell.config["hidden_size"])
        self.rows = int(cell.traffic["batch"])

    def setup(self, seconds):
        # counted before the window: not the window's
        obs.count("ckpt.saves")
        self.step = jax.jit(lambda w, x: jnp.tanh(x @ w))
        self.w = jnp.eye(self.width)
        self.x = jax.random.normal(jax.random.PRNGKey(self.seed % 2**31),
                                   (self.rows, self.width))
        jax.block_until_ready(self.step(self.w, self.x))

    def window(self):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            self.x = self.step(self.w, self.x)
            jax.device_get(self.x)
            obs.count("d2h")
            obs.count("runner.steps")
        dt = time.perf_counter() - t0
        return {"window_s": dt, "steps": STEPS, "samples": STEPS * self.rows,
                "attempted": STEPS, "failed": 0}

    def check(self):
        return {"gap": self.cell.traffic["gap"]}

    def ops_per_sample(self):
        return 6 * self.width * self.width


def planted(tmp_path, gap=0.0):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "planted-lm.json").write_text(json.dumps(CONFIG))
    (bench / "traffic" / "planted-train.json").write_text(json.dumps(
        {"kind": "planted", "batch": 8, "gap": gap}))
    (bench / "cells" / f"{CELL}.json").write_text(json.dumps(
        {"limits": {"gap": 1e-6}}))
    return spec.load(CELL, root=tmp_path, bench=BENCH)


@pytest.fixture
def planted_kind(monkeypatch):
    monkeypatch.setattr(harness, "kind",
                        lambda name: {"planted": PlantedCell}[name])


def test_config_of_another_family_loads(tmp_path):
    cell = planted(tmp_path)
    assert not {"n_frames", "hidden", "n_outputs"} & set(cell.config)
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert set(cell.config["precision"]) == {"train"}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                     "train_samples_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["train_mfu_pct"]


@pytest.mark.parametrize("gap,correct", [(0.0, True), (1.0, False)])
def test_planted_cell_runs_through_the_harness(tmp_path, planted_kind,
                                               cpu_peaks, gap, correct):
    cell = planted(tmp_path, gap)
    run = harness.measure(cell, 2**31 + 7, 0.1, False, time.perf_counter())
    assert run.trace is None and run.lowered == 0
    # the program's counters over the window, under their own names, and
    # the kind's beside them; what set-up counted is not the window's
    assert run.counters["d2h"] == STEPS
    assert run.counters["runner.steps"] == STEPS
    assert "ckpt.saves" not in run.counters
    assert run.counters["samples"] == STEPS * 8
    line = harness.line(run)
    assert line["correct"] is correct
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s"}
    assert line["metrics"]["setup_s"]["value"] == run.setup_s > 0
    rate = run.counters["samples"] / run.counters["window_s"]
    assert line["metrics"]["train_samples_per_s"]["value"] == rate
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"gap": {"value": gap, "limit": 1e-6}}
    # the whole step's share of the peak takes the work from the kind
    mfu = harness.reader("train_mfu_pct")(run)
    assert mfu == (100.0 * rate * 6 * 64 * 64
                   / run.peaks["bf16_flops_per_s"])
