"""Fault-tolerant training loop: periodic async checkpoints, resume-from-
latest, straggler watchdog, and crash-retry — the loop a real multi-pod job
runs under a cluster scheduler.

Dispatch modes
--------------
Stepwise (``chunk_steps=1``): one ``train_step(state, batches(step))`` call
per step.  The loop hard-syncs on the step's metrics only when an
``on_metrics`` callback is registered (the callback's ``dt`` is then true
per-step wall time); without one, steps are dispatched asynchronously and
the host syncs only at checkpoint boundaries and loop exit — the straggler
monitor then sees *dispatch* time, not compute time.

Chunked (``chunk_steps>1`` + a ``chunk_fn``): ``chunk_fn(state, start, n)``
runs ``n`` steps in one jitted ``lax.scan`` dispatch (batches synthesized
on-device — see train/engine.build_chunked) and returns per-step metrics
stacked ``(n, ...)``.  The loop dispatches chunk N+1 *before* syncing chunk
N's metrics, so between checkpoints the host fetch overlaps the next chunk's
compute; metrics cross to the host once per chunk.  At a checkpoint
boundary the loop retires the in-flight chunk and then snapshots the state
with nothing queued, so the device idles for the fetch, the wait on the
previous checkpoint's flush and the snapshot, until the next dispatch.
Chunk ends are clipped to checkpoint boundaries,
``total_steps`` (the final ragged chunk runs at its own static length), and
the fault-injection step, so checkpoints land exactly where the stepwise
loop would put them and a resume starts from any chunk boundary.  The
straggler monitor is fed once per chunk with the chunk's wall time
(dispatch-to-metrics-retired, clamped against overlap) divided by the
chunk length — per-step units, so mixed chunk lengths and stepwise runs
share one EWMA scale.

Fault injection (``inject_fault_at``) lets tests exercise the recovery path
deterministically on CPU: the loop "crashes" at a chosen step, then the
restart resumes from the latest checkpoint and must reach the same final
state as an uninterrupted run (tests/test_fault_tolerance.py,
tests/test_chunked_training.py).

Spans and counters (``repro.obs``): ``runner.dispatch`` around each launch
(counting ``runner.chunks`` and ``runner.steps``), ``runner.retire`` around
retiring a chunk with ``runner.fetch`` nested around its metrics' host
fetch (counting ``d2h``), and ``runner.sync`` around the waits for the
device; the checkpoint manager spans its own restore, wait and save.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax

from repro import obs
from repro.ft.checkpoint import CheckpointManager
from repro.ft.straggler import StragglerMonitor


class InjectedFault(RuntimeError):
    pass


@dataclasses.dataclass
class RunnerConfig:
    total_steps: int
    ckpt_dir: str
    ckpt_every: int = 50
    keep: int = 3
    max_restarts: int = 3
    inject_fault_at: int | None = None


def _next_boundary(step: int, every: int) -> int:
    return (step // every + 1) * every


def run(train_step: Callable, init_state, batches: Callable[[int], Any],
        cfg: RunnerConfig, *, shardings=None, on_metrics=None,
        chunk_fn: Callable | None = None, chunk_steps: int = 1):
    """Run to cfg.total_steps with checkpoint/restart.

    Returns ``(state, step)``: the final state and the step count reached.

    ``batches`` is a *seekable* factory — ``batches(step) -> batch`` must
    return the same batch for the same step on every call, so a restart
    replays the data stream deterministically from the resume step.  With
    ``chunk_steps > 1`` a ``chunk_fn(state, start, n)`` is required and
    ``batches`` is not consulted (the chunk synthesizes its own batches from
    the step index); the two modes are bit-identical by construction.
    """
    if chunk_steps > 1 and chunk_fn is None:
        raise ValueError("chunk_steps > 1 requires a chunk_fn "
                         "(see train/engine.build_chunked)")
    mgr = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep, every=cfg.ckpt_every)
    monitor = StragglerMonitor()
    restarts = 0
    faults_remaining = 1 if cfg.inject_fault_at is not None else 0

    # step-0 checkpoint: the train step donates its state buffers, so a crash
    # before the first periodic checkpoint must restore from step 0 rather
    # than reuse (already-donated) init_state.
    from repro.ft.checkpoint import latest_step, save_state
    if latest_step(cfg.ckpt_dir) is None:
        save_state(init_state, cfg.ckpt_dir, 0,  # jaxlint: disable=HOSTSYNC -- step-0 checkpoint runs before the loop starts; syncing here is the point
                   async_io=False)

    while True:
        restored, start = mgr.restore_latest(init_state, shardings)
        state = restored if restored is not None else init_state
        step = start
        try:
            if chunk_steps > 1:
                state, step = _chunked_loop(
                    chunk_fn, state, step, cfg, mgr, monitor,
                    on_metrics=on_metrics, chunk_steps=chunk_steps,
                    fault_live=faults_remaining > 0)
            else:
                state, step = _stepwise_loop(
                    train_step, state, step, batches, cfg, mgr, monitor,
                    on_metrics=on_metrics, fault_live=faults_remaining > 0)
            if step is None:  # fault fired inside the loop
                faults_remaining -= 1
                raise InjectedFault(f"injected at step {cfg.inject_fault_at}")
            mgr.wait()
            return state, step
        except InjectedFault:
            restarts += 1
            if restarts > cfg.max_restarts:
                raise
            mgr.wait()  # flush any pending async save, then "restart"
            continue


def _stepwise_loop(train_step, state, step, batches, cfg, mgr, monitor, *,
                   on_metrics, fault_live):
    """One step per dispatch.  Returns (state, step), or (state, None) when
    the injected fault fires (the caller raises — keeping the raise outside
    lets both loops share the restart bookkeeping)."""
    sync_each_step = on_metrics is not None
    while step < cfg.total_steps:
        batch = batches(step)
        t0 = time.perf_counter()
        if fault_live and step == cfg.inject_fault_at:
            return state, None
        with obs.span("runner.dispatch", step=step, n=1):
            state, metrics = train_step(state, batch)
        obs.count("runner.chunks")
        obs.count("runner.steps")
        if sync_each_step:
            with obs.span("runner.sync"):
                jax.block_until_ready(metrics["loss"])  # jaxlint: disable=HOSTSYNC -- opt-in sync_each_step mode exists to measure true per-step latency
        # without a callback, dt is dispatch time only (async steps); the
        # straggler EWMA then watches dispatch latency, documented above
        dt = time.perf_counter() - t0
        action = monitor.update(dt)
        if action == "checkpoint_and_evict":
            mgr.maybe_save(state, step + 1, force=True)  # snapshot pre-evict
        step += 1
        mgr.maybe_save(state, step)  # device->host snapshot = a sync point
        if on_metrics:
            on_metrics(step, metrics, dt)
    with obs.span("runner.sync"):
        jax.block_until_ready(state)  # jaxlint: disable=HOSTSYNC -- loop exit: the promised final sync, once per run
    return state, step


def _chunked_loop(chunk_fn, state, step, cfg, mgr, monitor, *, on_metrics,
                  chunk_steps, fault_live):
    """Whole chunks per dispatch, metrics retired one chunk behind.
    Returns (state, step) or (state, None) when the injected fault fires."""
    inflight = None  # (chunk start step, n, stacked metrics, dispatch t0)
    retired_at = float("-inf")  # when the device last went idle (host clock)

    def retire(chunk):
        """Block on a chunk's stacked metrics, fan them out per step."""
        nonlocal retired_at
        c_start, n, metrics, t0 = chunk
        with obs.span("runner.retire", step=c_start, n=n):
            with obs.span("runner.fetch"):
                host = jax.device_get(metrics)  # ONE host fetch for n steps
            obs.count("d2h")
            now = time.perf_counter()
            # a chunk dispatched while its predecessor was still computing
            # only *started* when the predecessor retired — clamp so
            # overlapped wall time isn't double-counted in dt / the
            # straggler EWMA
            dt = now - max(t0, retired_at)
            retired_at = now
            # per-step normalized: boundary-clipped chunks vary in length,
            # and the EWMA must compare like with like (and with stepwise
            # runs)
            action = monitor.update(dt / n)
            if on_metrics:
                for i in range(n):
                    on_metrics(c_start + i + 1,
                               jax.tree.map(lambda m: m[i], host), dt / n)
        return action

    while step < cfg.total_steps:
        if fault_live and step == cfg.inject_fault_at:
            if inflight is not None:  # deliver completed steps' metrics
                retire(inflight)
            return state, None
        n = min(chunk_steps, cfg.total_steps - step,
                _next_boundary(step, cfg.ckpt_every) - step)
        if fault_live and step < cfg.inject_fault_at:
            n = min(n, cfg.inject_fault_at - step)
        t0 = time.perf_counter()
        with obs.span("runner.dispatch", step=step, n=n):
            new_state, metrics = chunk_fn(state, step, n)  # async dispatch
        obs.count("runner.chunks")
        obs.count("runner.steps", n)
        prev, inflight = inflight, (step, n, metrics, t0)
        state, step = new_state, step + n
        if prev is not None:  # overlap: chunk N computes while N-1 retires
            if retire(prev) == "checkpoint_and_evict":
                mgr.maybe_save(state, step, force=True)  # snapshot pre-evict
        if step % cfg.ckpt_every == 0 and step < cfg.total_steps:
            # retire before saving: the snapshot is a sync point anyway, and
            # the next dispatch donates these state buffers
            retire(inflight)
            inflight = None
            mgr.maybe_save(state, step)
    if inflight is not None:
        retire(inflight)
    with obs.span("runner.sync"):
        jax.block_until_ready(state)  # jaxlint: disable=HOSTSYNC -- chunked-loop exit: one final sync after the last chunk retires
    mgr.maybe_save(state, step)
    return state, step
