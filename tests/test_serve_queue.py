"""Queue/executor tests for the pipelined serving stack: wave-formation
properties (every voxel served exactly once, voxel cap, deadline from
enqueue, priority order), pipelined == sync bit-exactness for both
backends, the no-per-tile-host-sync contract of the pipelined executor,
latency-from-enqueue semantics, and failed-lifecycle admission."""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from _serve_helpers import (N_FRAMES, calibrated_net as _calibrated_net,
                            features as _features, jitted_float_maps)

from repro.serve.executor import InflightWave, WaveExecutor, plan_tiles
from repro.serve.queue import RequestQueue, RequestState
from repro.serve.recon import ReconEngine, ReconRequest

jax.config.update("jax_platform_name", "cpu")


def _stub(n_voxels, rid=""):
    # the queue is duck-typed: it only reads n_voxels / request_id
    return types.SimpleNamespace(n_voxels=n_voxels, request_id=rid)


# --------------------------------------------------------------------------
# wave formation properties (admission layer alone, no jax)
# --------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_wave_formation_schedules_every_request_exactly_once(seed):
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 3000, size=int(rng.integers(1, 25))).tolist()
    prios = rng.integers(0, 3, size=len(counts)).tolist()
    cap = int(rng.integers(64, 4096))
    q = RequestQueue(max_wave_voxels=cap)
    tickets = [q.submit(_stub(n, str(i)), priority=p)
               for i, (n, p) in enumerate(zip(counts, prios))]
    assert q.pending_voxels() == sum(counts)

    waves = []
    while q.n_pending:  # flush, exactly as the engine's drain loop does
        waves.append(q.form_wave(flush=True))
    assert q.n_pending == 0
    flat = [t for w in waves for t in w]
    # every enqueued request scheduled exactly once
    assert sorted(id(t) for t in flat) == sorted(id(t) for t in tickets)
    assert all(t.state == RequestState.SCHEDULED for t in flat)
    # voxel cap respected; only a single oversized request may exceed it
    for w in waves:
        vox = sum(t.request.n_voxels for t in w)
        assert vox <= cap or len(w) == 1
    # priority order with FIFO tiebreak, never skipping within a class
    assert flat == sorted(tickets, key=lambda t: (-t.priority, t.seq))


def test_deadline_is_measured_from_enqueue():
    now = [0.0]
    q = RequestQueue(max_wave_voxels=10 ** 9, max_wait_ms=10.0,
                     clock=lambda: now[0])
    tk = q.submit(_stub(100))
    assert not q.wave_due() and q.form_wave() == []
    now[0] = 0.009
    assert not q.wave_due()  # 9 ms < 10 ms deadline
    now[0] = 0.011
    assert q.wave_due()      # oldest pending ticket hit its deadline
    assert q.form_wave() == [tk]
    assert q.form_wave() == [] and not q.wave_due()  # queue emptied


def test_deadline_promotes_starved_ticket_over_priority():
    """A low-priority ticket past its deadline leads the next wave even
    under sustained higher-priority load — max_wait_ms really bounds every
    request's wait, not just the front-runner's."""
    now = [0.0]
    q = RequestQueue(max_wave_voxels=1024, max_wait_ms=5.0,
                     clock=lambda: now[0])
    big = q.submit(_stub(2000, "big"), priority=0)
    for i in range(4):
        q.submit(_stub(512, f"hp{i}"), priority=1)
    w1 = q.form_wave(flush=True)  # before the deadline, priority wins
    assert big not in w1 and len(w1) == 2
    now[0] = 0.010                # big's deadline expired
    w2 = q.form_wave()
    assert w2 == [big]            # promoted to the front, served alone
    assert len(q.form_wave(flush=True)) == 2  # remaining high-prio pair


def test_voxel_budget_makes_wave_due_immediately():
    q = RequestQueue(max_wave_voxels=256, max_wait_ms=10_000.0)
    q.submit(_stub(200))
    assert not q.wave_due()
    q.submit(_stub(56))
    assert q.wave_due()  # budget reached long before the deadline


def test_no_deadline_means_flush_only():
    q = RequestQueue()  # no cap, no deadline: only drain flushes
    q.submit(_stub(10 ** 6))
    assert not q.wave_due()
    assert q.form_wave() == []
    assert len(q.form_wave(flush=True)) == 1


def test_rejected_requests_never_enter_the_queue():
    q = RequestQueue(validator=lambda r: "nope" if r.n_voxels < 0 else None)
    bad = q.submit(_stub(-1))
    assert bad.state == RequestState.FAILED and bad.error == "nope"
    assert q.n_pending == 0 and q.n_rejected == 1
    ok = q.submit(_stub(5))
    assert ok.state == RequestState.PENDING and q.n_pending == 1


def test_queue_arg_validation():
    with pytest.raises(ValueError, match="max_wave_voxels"):
        RequestQueue(max_wave_voxels=0)
    with pytest.raises(ValueError, match="max_wait_ms"):
        RequestQueue(max_wait_ms=-1.0)


# --------------------------------------------------------------------------
# executor: device-side staging + the one-sync-per-wave contract
# --------------------------------------------------------------------------

def test_executor_stages_padded_pool_on_device():
    params, _, _ = _calibrated_net()
    ex = WaveExecutor(backend="float", params=params, buckets=(64, 128))
    pool, tiles, total = ex.stage([_features(100, 1), _features(30, 2)])
    assert total == 130
    assert tiles == plan_tiles(130, (64, 128))
    padded = tiles[-1][0] + tiles[-1][2]
    assert isinstance(pool, jnp.ndarray) and pool.shape == (padded, ex.in_dim)
    assert np.all(np.asarray(pool)[130:] == 0)  # pad rows are zeros

    handle = ex.dispatch([_features(100, 1), _features(30, 2)])
    assert isinstance(handle, InflightWave)
    assert handle.n_tiles == len(tiles) and handle.total == 130
    pred = handle.wait()
    assert pred.shape == (130, 2)
    # outputs come back already denormalized (ms): the rescale is fused
    # into the jitted forward so retirement never re-touches the device
    want = jitted_float_maps(
        params, jnp.concatenate([_features(100, 1), _features(30, 2)]),
        buckets=(64, 128))
    np.testing.assert_array_equal(pred, want)


def test_pipelined_executor_syncs_once_per_wave(monkeypatch):
    """The pipelined path must never host-sync per tile: exactly one
    ``jax.block_until_ready`` per wave, however many tiles the wave has.
    The sync baseline, by contrast, syncs every tile."""
    params, _, _ = _calibrated_net()
    reqs = [ReconRequest(features=_features(300, seed=i), request_id=str(i))
            for i in range(3)]
    n_tiles_per_wave = len(plan_tiles(300, (64, 128, 256)))
    assert n_tiles_per_wave == 2  # 256-tile + padded 64-tile

    def counting_engine(mode):
        eng = ReconEngine(backend="float", params=params, mode=mode,
                          buckets=(64, 128, 256), max_wave_voxels=300)
        eng.reconstruct(reqs)  # warmup: trace outside the counted region
        return eng

    calls = {"n": 0}
    orig = jax.block_until_ready

    def counted(x):
        calls["n"] += 1
        return orig(x)

    for mode, expect in (("pipelined", 3), ("sync", 6)):
        engine = counting_engine(mode)
        for r in reqs:
            engine.enqueue(r)
        calls["n"] = 0
        monkeypatch.setattr(jax, "block_until_ready", counted)
        results = engine.drain()
        monkeypatch.setattr(jax, "block_until_ready", orig)
        assert engine.last_wave["n_waves"] == 3
        assert len(results) == 3
        assert calls["n"] == expect, mode  # waves, not tiles, when pipelined


# --------------------------------------------------------------------------
# engine: pipelined == sync bit-exactness, both backends
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["float", "int8"])
def test_pipelined_matches_sync_bitexact(backend):
    params, _, ints = _calibrated_net()
    net_kw = ({"params": params} if backend == "float"
              else {"int_layers": ints})
    mask = np.zeros((10, 13), bool)
    mask.flat[3:80] = True
    reqs = [ReconRequest(features=_features(n, seed=n), request_id=str(n),
                         mask=(mask if n == 77 else None))
            for n in (137, 64, 333, 77, 501, 0)]

    sync = ReconEngine(backend=backend, mode="sync", **net_kw)
    pipe = ReconEngine(backend=backend, mode="pipelined",
                       max_wave_voxels=256, **net_kw)
    want = sync.reconstruct(reqs)
    got = pipe.reconstruct(reqs)
    assert pipe.last_wave["n_waves"] > 1  # the trace really was split
    for w, g in zip(want, got):
        assert w.request_id == g.request_id
        assert np.array_equal(w.t1_ms, g.t1_ms)
        assert np.array_equal(w.t2_ms, g.t2_ms)
    # wave splitting must not grow the jit cache past the bucket set
    assert pipe.compile_cache_size() <= len(pipe.buckets)


def test_priority_requests_complete_first():
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params, mode="pipelined",
                         max_wave_voxels=128)
    engine.reconstruct([ReconRequest(features=_features(128))])  # warmup
    low = engine.enqueue(ReconRequest(features=_features(128, 1),
                                      request_id="low"), priority=0)
    high = engine.enqueue(ReconRequest(features=_features(128, 2),
                                       request_id="high"), priority=5)
    engine.drain()
    assert low.state == high.state == RequestState.DONE
    assert high.done_t <= low.done_t  # scheduled into the earlier wave


# --------------------------------------------------------------------------
# latency: measured from enqueue, not wave start
# --------------------------------------------------------------------------

def test_latency_includes_queue_wait():
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    engine.reconstruct([ReconRequest(features=_features(64))])  # warmup
    early = engine.enqueue(ReconRequest(features=_features(64, 1)))
    time.sleep(0.05)
    late = engine.enqueue(ReconRequest(features=_features(64, 2)))
    engine.drain()
    # same wave, so the earlier-enqueued request carries the queue wait
    assert early.result.latency_s >= 0.05
    assert early.result.latency_s > late.result.latency_s
    assert early.result.latency_s - late.result.latency_s >= 0.04
    assert early.latency_s == early.result.latency_s


# --------------------------------------------------------------------------
# failures are lifecycle states on the streaming path
# --------------------------------------------------------------------------

def test_streaming_failure_does_not_poison_the_wave():
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    bad_dim = ReconRequest(features=jnp.zeros((4, 7)), request_id="bad-dim")
    bad_mask = ReconRequest(features=_features(4), request_id="bad-mask",
                            mask=np.ones((3, 3), bool))
    ok = ReconRequest(features=_features(50, 3), request_id="ok")

    t_bad = engine.enqueue(bad_dim)        # admission rejects, no raise
    t_mask = engine.enqueue(bad_mask)
    t_ok = engine.enqueue(ok)
    assert t_bad.state == RequestState.FAILED and "feature dim" in t_bad.error
    assert t_mask.state == RequestState.FAILED and "mask selects" in t_mask.error
    assert engine.queue.n_pending == 1     # only the valid request queued

    results = engine.drain()
    assert t_ok.state == RequestState.DONE and len(results) == 1
    assert engine.last_wave["n_requests"] == 1
    want = jitted_float_maps(params, ok.features)
    np.testing.assert_array_equal(t_ok.result.t1_ms, want[:, 0])

    # the batch wrapper keeps all-or-nothing semantics: it raises up front,
    # before admitting anything
    with pytest.raises(ValueError, match="feature dim"):
        engine.reconstruct([ok, bad_dim])
    assert engine.queue.n_pending == 0


def test_int_mask_is_validated_on_its_bool_cast():
    """An int mask summing to n_voxels but selecting fewer cells must be
    rejected at admission — validation counts exactly what assembly
    scatters through (the bool cast)."""
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    tricky = np.zeros((2, 2), np.int64)
    tricky[0, 0] = 2  # sums to 2, bool-selects 1 cell
    req = ReconRequest(features=_features(2), mask=tricky)
    with pytest.raises(ValueError, match="mask selects 1 voxels"):
        engine.reconstruct([req])
    assert engine.enqueue(req).state == RequestState.FAILED


def test_batch_path_raises_on_assembly_failure(monkeypatch):
    """reconstruct() must never hand back a silent None: if assembly fails
    mid-wave, the wave completes for everyone else, then it raises with
    the underlying error (the streaming path keeps the failed ticket)."""
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    orig = ReconEngine._assemble

    def flaky(self, req, pred, latency):
        if req.request_id == "boom":
            raise RuntimeError("synthetic assembly failure")
        return orig(self, req, pred, latency)

    monkeypatch.setattr(ReconEngine, "_assemble", flaky)
    good = ReconRequest(features=_features(40, 1), request_id="good")
    boom = ReconRequest(features=_features(30, 2), request_id="boom")
    with pytest.raises(ValueError, match="synthetic assembly failure"):
        engine.reconstruct([boom, good])
    # streaming path: same failure stays a lifecycle state, wave-mates fine
    t_boom, t_good = engine.enqueue(boom), engine.enqueue(good)
    results = engine.drain()
    assert t_boom.state == RequestState.FAILED
    assert "synthetic assembly failure" in t_boom.error
    assert t_good.state == RequestState.DONE and len(results) == 1
    assert engine.last_wave["n_failed"] == 1


def test_non_array_features_and_crashing_validator_never_raise():
    """Admission absorbs even type-level garbage: a features list (no
    .shape) and a validator that itself crashes both yield failed tickets,
    not exceptions out of enqueue()."""
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    t = engine.enqueue(ReconRequest(features=[[0.1] * 32], request_id="ls"))
    assert t.state == RequestState.FAILED and "must be an array" in t.error
    q = RequestQueue(validator=lambda r: r.no_such_attr)
    t2 = q.submit(_stub(4))
    assert t2.state == RequestState.FAILED
    assert "validator error" in t2.error and q.n_pending == 0
    # validator-less queue fed a request without usable n_voxels: same deal
    t3 = RequestQueue().submit(types.SimpleNamespace(request_id="x"))
    assert t3.state == RequestState.FAILED and "n_voxels" in t3.error


def test_malformed_rank_rejected_at_admission():
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    bad = ReconRequest(features=jnp.zeros((4, 3, 2 * N_FRAMES)),
                       request_id="rank3")
    t = engine.enqueue(bad)
    assert t.state == RequestState.FAILED and "rank-2" in t.error
    with pytest.raises(ValueError, match="rank-2"):
        engine.reconstruct([bad])


def test_execution_failure_fails_the_wave_not_the_drain(monkeypatch):
    """A device-side error during wave *execution* (after dispatch) must
    also end as failed tickets — never an exception out of drain() leaving
    popped tickets stranded in 'scheduled'."""
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params, mode="pipelined")
    monkeypatch.setattr(InflightWave, "wait",
                        lambda self: (_ for _ in ()).throw(
                            RuntimeError("synthetic device failure")))
    t = engine.enqueue(ReconRequest(features=_features(10, 1)))
    results = engine.drain()
    assert results == [] and len(engine._inflight) == 0
    assert t.state == RequestState.FAILED
    assert "synthetic device failure" in t.error
    assert engine.last_wave["n_failed"] == 1


def test_dispatch_failure_fails_the_wave_not_the_drain(monkeypatch):
    """If the executor cannot stage a wave, its tickets end 'failed' with
    the error attached — drain() never raises and never strands tickets
    in 'scheduled'."""
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    monkeypatch.setattr(engine.executor, "dispatch",
                        lambda feats, **kw: (_ for _ in ()).throw(
                            RuntimeError("synthetic stage failure")))
    t1 = engine.enqueue(ReconRequest(features=_features(10, 1)))
    t2 = engine.enqueue(ReconRequest(features=_features(20, 2)))
    results = engine.drain()
    assert results == []
    assert t1.state == t2.state == RequestState.FAILED
    assert "synthetic stage failure" in t1.error
    assert engine.last_wave["n_failed"] == 2


def test_streaming_poll_then_drain_serves_everything_once():
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params, mode="pipelined",
                         max_wave_voxels=256, max_wait_ms=0.0)
    engine.reconstruct([ReconRequest(features=_features(256))])  # warmup
    tickets = []
    for i, n in enumerate((100, 250, 64, 300, 0)):
        tickets.append(engine.enqueue(
            ReconRequest(features=_features(n, seed=10 + i),
                         request_id=f"s{i}")))
        engine.poll()  # deadline 0 ms: dispatch whatever is pending
    results = engine.drain()
    assert all(t.state == RequestState.DONE for t in tickets)
    assert sum(t.result.n_voxels for t in tickets) == 714
    # drain returns its own waves' results (poll-retired ones live on the
    # tickets the caller holds — never retained by the engine), but the
    # session stats must account for every served request
    ticket_results = {id(t.result) for t in tickets}
    assert results and all(id(r) in ticket_results for r in results)
    assert engine.last_wave["n_requests"] == len(tickets)
    assert engine.last_wave["total_voxels"] == 714
    solo = ReconEngine(backend="float", params=params)
    for t in tickets:
        want, = solo.reconstruct([t.request])
        assert np.array_equal(t.result.t1_ms, want.t1_ms)
        assert np.array_equal(t.result.t2_ms, want.t2_ms)
