"""The program's spans and counters (``repro.obs``): every span of the
training path appears in a profiler trace of a short chunked run, nested and
on the threads it belongs to; the counters count what the loop must do; the
name scopes reach a compiled chunk's op metadata."""

import collections
import functools
import glob
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from bench.kinds.train import chunk_lengths
from repro import obs
from repro.ft.runner import RunnerConfig, run

jax.config.update("jax_platform_name", "cpu")

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
TOTAL, CHUNK, EVERY = 20, 4, 8


def _state():
    return {"w": jnp.zeros((4, 4), jnp.float32),
            "b": jnp.ones((4,), jnp.float32)}


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _chunk(state, start, n):
    return ({"w": state["w"] + n, "b": state["b"] * 2.0},
            {"loss": jnp.arange(n, dtype=jnp.float32) + start})


def _host_events(trace_dir):
    """{thread line: [(name, start_ns, end_ns)]} of the program's spans."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):  # threads may share a name
            for e in line.events:
                if e.name in obs.SPANS:
                    out[(plane.name, i)].append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def _traced_run(tmp_path, **kw):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    cfg = RunnerConfig(total_steps=TOTAL, ckpt_dir=str(tmp_path / "ckpt"),
                       ckpt_every=EVERY)
    before = collections.Counter(obs.COUNTS)
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        state, step = run(**kw, init_state=_state(), cfg=cfg)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    assert step == TOTAL
    delta = {k: v - before[k] for k, v in obs.COUNTS.items()
             if v != before[k]}
    return delta, _host_events(tmp_path / "trace")


def test_chunked_run_spans_and_counts(tmp_path):
    counts, lines = _traced_run(tmp_path, train_step=None, batches=None,
                                chunk_fn=_chunk, chunk_steps=CHUNK)
    chunks = chunk_lengths(0, TOTAL, CHUNK, EVERY)
    # step 0 before the loop, then each period's boundary short of the end
    saves = 1 + (TOTAL - 1) // EVERY
    leaves = jax.tree.leaves(_state())
    assert counts == {
        "runner.chunks": len(chunks), "runner.steps": TOTAL,
        "ckpt.saves": saves,
        "ckpt.bytes": saves * sum(a.nbytes for a in leaves),
        # a save's small leaves cross to the host in one fetch
        "ckpt.packed_leaves": len(leaves) * saves,
        "d2h": len(chunks) + saves}

    seen = {name for events in lines.values() for name, _s, _e in events}
    assert seen == set(obs.SPANS)
    loop, = [k for k, ev in lines.items()
             if any(n == "runner.dispatch" for n, _s, _e in ev)]
    by_name = collections.defaultdict(list)
    for name, s, e in lines[loop]:
        by_name[name].append((s, e))
    assert len(by_name["runner.dispatch"]) == len(chunks)
    assert len(by_name["runner.fetch"]) == len(chunks)
    for fs, fe in by_name["runner.fetch"]:
        assert any(rs <= fs and fe <= re_ for rs, re_ in by_name["runner.retire"])
    # the periodic saves flush on the checkpoint worker, not the loop
    worker_flushes = [e for k, ev in lines.items() if k != loop
                      for e in ev if e[0] == "ckpt.flush"]
    assert len(worker_flushes) == saves - 1
    assert all(n != "runner.dispatch" for k, ev in lines.items()
               if k != loop for n, _s, _e in ev)


def test_stepwise_run_counts(tmp_path):
    step = jax.jit(lambda s, b: ({"w": s["w"] + b, "b": s["b"]},
                                 {"loss": jnp.float32(0)}))
    counts, lines = _traced_run(tmp_path, train_step=step,
                                batches=lambda i: jnp.float32(i))
    assert counts["runner.chunks"] == counts["runner.steps"] == TOTAL
    assert counts["ckpt.saves"] == 1 + (TOTAL - 1) // EVERY
    names = {n for ev in lines.values() for n, _s, _e in ev}
    assert {"runner.dispatch", "runner.sync", "ckpt.snapshot"} <= names


def test_span_names_are_stable():
    with pytest.raises(ValueError):
        obs.span("runner.nope")
    used = set()
    for path in SRC.rglob("*.py"):
        used |= set(re.findall(r'obs\.span\("([^"]+)"', path.read_text()))
    assert used == set(obs.SPANS)


def test_fused_chunk_carries_name_scopes():
    from repro.configs import get_config
    from repro.models import registry
    from repro.train import engine

    mcfg = get_config("mrf-fpga")
    ecfg = engine.EngineConfig(backend="fused-pallas", lr=1e-2,
                               optimizer="sgd", tile_batch=8, chunk_steps=2)
    chunk_fn, init = engine.build_chunked(
        registry.build(mcfg), ecfg, engine.default_stream(mcfg, 16),
        jax.random.PRNGKey(1))
    text = chunk_fn.lower(init(jax.random.PRNGKey(0)), 0, 2).as_text(
        debug_info=True)
    scopes = set(re.findall(r'"jit\(chunk_step\)/(\w+)/', text))
    assert {"simulate", "stage"} <= scopes
