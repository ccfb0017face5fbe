"""Serving-engine tests: bucket tiling, pad-to-bucket shape stability,
int8 artifact save/load bit-exactness, masked re-assembly + centralized
denormalization, and the multi-host-style data-parallel serving smoke
(simulated multi-device mesh + ``host_sharded_key`` request streams)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _serve_helpers import (calibrated_net as _calibrated_net,
                            features as _features, jitted_float_maps)

from repro.core import qat
from repro.data.pipeline import denormalize_targets
from repro.serve.recon import (DEFAULT_BUCKETS, ReconEngine, ReconRequest,
                               latency_percentiles, plan_tiles)

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------------------
# bucket tiling
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 64, 128, 129, 333, 1024, 1025, 5000])
def test_plan_tiles_covers_exactly(n):
    tiles = plan_tiles(n, DEFAULT_BUCKETS)
    off = 0
    for t_off, count, bucket in tiles:
        assert t_off == off
        assert 0 < count <= bucket
        assert bucket in DEFAULT_BUCKETS
        off += count
    assert off == n
    # the tail uses the smallest bucket that fits (minimal padding)
    _, count, bucket = tiles[-1]
    if count < bucket:
        smaller = [b for b in DEFAULT_BUCKETS if b < bucket]
        assert all(b < count for b in smaller)


def test_plan_tiles_empty_and_full():
    assert plan_tiles(0, DEFAULT_BUCKETS) == []
    assert plan_tiles(2048, DEFAULT_BUCKETS) == [(0, 1024, 1024),
                                                 (1024, 1024, 1024)]


# --------------------------------------------------------------------------
# int8 artifact: export -> save -> load -> serve, bit-exact
# --------------------------------------------------------------------------

def test_artifact_roundtrip_bitexact(tmp_path):
    _, _, ints = _calibrated_net()
    path = qat.save_int8_artifact(tmp_path / "net", ints)
    assert path.suffix == ".npz" and path.exists()
    loaded = qat.load_int8_artifact(path)
    assert len(loaded) == len(ints)
    for a, b in zip(ints, loaded):
        assert a.w_q.dtype == b.w_q.dtype == jnp.int8
        assert b.b_q.dtype == jnp.int32
        assert jnp.array_equal(a.w_q, b.w_q)
        assert jnp.array_equal(a.b_q, b.b_q)
        assert jnp.array_equal(a.s_in, b.s_in)
        assert jnp.array_equal(a.s_w, b.s_w)
        assert (a.s_out is None) == (b.s_out is None)
        if a.s_out is not None:
            assert jnp.array_equal(a.s_out, b.s_out)

    from repro.kernels.qat_dense.ops import int_forward_pallas
    x = _features(200, seed=3)
    want = qat.int_forward(ints, x)
    got = int_forward_pallas(loaded, x)
    assert jnp.array_equal(want, got), "loaded artifact must serve bit-exact"


# --------------------------------------------------------------------------
# engine: padding invariance, oracle equality, masked re-assembly
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_voxels", [1, 77, 128, 500])
def test_float_engine_matches_direct_forward(n_voxels):
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    x = _features(n_voxels, seed=n_voxels)
    res, = engine.reconstruct([ReconRequest(features=x)])
    want = jitted_float_maps(params, x)
    np.testing.assert_array_equal(res.t1_ms, want[:, 0])
    np.testing.assert_array_equal(res.t2_ms, want[:, 1])
    assert res.n_voxels == n_voxels and res.latency_s > 0


def test_int8_engine_matches_oracle_bitexact():
    _, _, ints = _calibrated_net()
    engine = ReconEngine(backend="int8", int_layers=ints)
    x = _features(333, seed=9)
    res, = engine.reconstruct([ReconRequest(features=x)])
    want = np.asarray(denormalize_targets(qat.int_forward(ints, x)))
    assert np.array_equal(res.t1_ms, want[:, 0])
    assert np.array_equal(res.t2_ms, want[:, 1])


@pytest.mark.parametrize("impl", ["fused", "lax", "layered"])
def test_int8_impls_serve_identical_maps(impl):
    """Every int8 implementation (fused whole-network kernel, pure-lax
    fallback, layered chain) serves the oracle's bits through the engine —
    switching impl can never change a reconstructed map."""
    _, _, ints = _calibrated_net()
    engine = ReconEngine(backend="int8", int_layers=ints, int8_impl=impl)
    assert engine.int8_impl == impl
    x = _features(333, seed=9)
    res, = engine.reconstruct([ReconRequest(features=x)])
    want = np.asarray(denormalize_targets(qat.int_forward(ints, x)))
    assert np.array_equal(res.t1_ms, want[:, 0])
    assert np.array_equal(res.t2_ms, want[:, 1])


def test_int8_impl_resolution_and_validation():
    _, _, ints = _calibrated_net()
    with pytest.raises(ValueError, match="int8 impl"):
        ReconEngine(backend="int8", int_layers=ints, int8_impl="tensorrt")
    # None resolves per rig: Pallas-compiled fused on TPU, lax elsewhere
    engine = ReconEngine(backend="int8", int_layers=ints)
    expect = "fused" if jax.default_backend() == "tpu" else "lax"
    assert engine.int8_impl == expect
    # a float engine has no int8 impl
    params, _, _ = _calibrated_net()
    assert ReconEngine(backend="float", params=params).int8_impl is None


def test_executor_records_request_size_distribution():
    """Every dispatched request's voxel count lands in request_sizes — the
    input to measured bucket autotuning."""
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    engine.reconstruct([ReconRequest(features=_features(n, seed=n))
                        for n in (7, 333, 64)])
    engine.reconstruct([ReconRequest(features=_features(130, seed=130))])
    assert engine.request_sizes == [7, 333, 64, 130]


def test_masked_reassembly_and_background():
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    mask = np.zeros((8, 9), bool)
    mask[2:6, 3:7] = True
    x = _features(int(mask.sum()), seed=5)
    res, = engine.reconstruct([ReconRequest(features=x, mask=mask)])
    assert res.t1_ms.shape == mask.shape
    assert np.all(res.t1_ms[~mask] == 0) and np.all(res.t2_ms[~mask] == 0)
    want = jitted_float_maps(params, x)
    np.testing.assert_array_equal(res.t1_ms[mask], want[:, 0])


def test_request_validation():
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    with pytest.raises(ValueError, match="feature dim"):
        engine.reconstruct([ReconRequest(features=jnp.zeros((4, 7)))])
    bad_mask = np.ones((3, 3), bool)
    with pytest.raises(ValueError, match="mask selects"):
        engine.reconstruct([ReconRequest(features=_features(4),
                                         mask=bad_mask)])
    with pytest.raises(ValueError, match="backend"):
        ReconEngine(backend="fp64", params=params)
    assert engine.reconstruct([]) == []
    assert all(np.isnan(v) for v in latency_percentiles([]).values())


def test_zero_voxel_requests_still_get_results():
    """An all-background slice (0 voxels) must yield a real ReconResult,
    alone in a wave or mixed with non-empty requests."""
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params)
    empty_mask = np.zeros((4, 4), bool)
    empty = ReconRequest(features=_features(0), mask=empty_mask,
                         request_id="empty")
    res, = engine.reconstruct([empty])
    assert res.n_voxels == 0 and res.t1_ms.shape == (4, 4)
    assert np.all(res.t1_ms == 0) and np.all(res.t2_ms == 0)
    mixed = engine.reconstruct([empty, ReconRequest(features=_features(30))])
    assert mixed[0].n_voxels == 0 and mixed[1].n_voxels == 30
    assert engine.last_wave["total_voxels"] == 30


def test_bucketing_never_recompiles_after_warmup():
    """Pad-to-bucket means ragged request mixes reuse the same traced
    shapes: the jit cache stays bounded by the bucket set."""
    params, _, _ = _calibrated_net()
    engine = ReconEngine(backend="float", params=params,
                         buckets=(128, 256, 512))
    wave1 = [ReconRequest(features=_features(n, seed=n))
             for n in (50, 300, 601)]
    engine.reconstruct(wave1)
    traced = engine.compile_cache_size()
    assert traced <= 3
    # different raggedness, same bucket set -> zero new traces
    wave2 = [ReconRequest(features=_features(n, seed=n))
             for n in (1, 77, 130, 512, 700)]
    engine.reconstruct(wave2)
    assert engine.compile_cache_size() == traced
    assert engine.bucket_shapes_run <= {128, 256, 512}


def test_pooled_wave_equals_individual_requests():
    """Pooling many requests into one wave must not change any prediction."""
    _, _, ints = _calibrated_net()
    engine = ReconEngine(backend="int8", int_layers=ints)
    reqs = [ReconRequest(features=_features(n, seed=n), request_id=str(n))
            for n in (40, 333, 128)]
    pooled = engine.reconstruct(reqs)
    pooled_wave = dict(engine.last_wave)
    for req, res in zip(reqs, pooled):
        solo, = engine.reconstruct([req])
        assert res.request_id == req.request_id
        assert np.array_equal(res.t1_ms, solo.t1_ms)
        assert np.array_equal(res.t2_ms, solo.t2_ms)
    pct = latency_percentiles(pooled)
    assert pct["p50_ms"] <= pct["p90_ms"] <= pct["p99_ms"]
    assert pooled_wave["total_voxels"] == 40 + 333 + 128


# --------------------------------------------------------------------------
# denormalization is centralized
# --------------------------------------------------------------------------

def test_denormalize_targets_owns_the_ranges():
    y = jnp.array([[0.5, 0.5], [1.0, 0.1]])
    ms = np.asarray(denormalize_targets(y))
    np.testing.assert_allclose(ms, [[2000.0, 300.0], [4000.0, 60.0]])
    custom = np.asarray(denormalize_targets(y, t1_range=(0.0, 1000.0),
                                            t2_range=(0.0, 100.0)))
    np.testing.assert_allclose(custom, [[500.0, 50.0], [1000.0, 10.0]])

    from repro.core.metrics import table1_metrics, table1_metrics_normalized
    pred, true = jnp.abs(_features(32, 1)[:, :2]), jnp.abs(_features(32, 2)[:, :2])
    a = table1_metrics_normalized(pred, true)
    b = table1_metrics(np.asarray(denormalize_targets(pred)),
                       np.asarray(denormalize_targets(true)))
    assert a == b


# --------------------------------------------------------------------------
# multi-host-style data-parallel serving smoke (ROADMAP open item)
# --------------------------------------------------------------------------

_DP_SUBPROC = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import mrf_net
    from repro.data.epg import default_sequence
    from repro.data.pipeline import MRFSampleStream, host_sharded_key, sample_batch
    from repro.dist.sharding import AxisRules, make_compat_mesh, use_rules
    from repro.serve.recon import ReconEngine, ReconRequest

    n_frames = 8
    sizes = mrf_net.layer_sizes(n_frames)
    params = mrf_net.init_params(jax.random.PRNGKey(0), sizes)
    stream = MRFSampleStream(seq=default_sequence(n_frames), batch_size=256)

    # two simulated hosts draw i.i.d. request streams without coordination
    reqs = []
    for host in range(2):
        key = host_sharded_key(seed=7, process_index=host)
        x, _ = sample_batch(stream, jax.random.fold_in(key, 0))
        reqs.append(ReconRequest(features=x, request_id=f"host{host}"))
    assert not np.allclose(np.asarray(reqs[0].features),
                           np.asarray(reqs[1].features))

    # mesh-less reference vs batch-sharded serving on an 8-device mesh
    ref = ReconEngine(backend="float", params=params).reconstruct(reqs)
    mesh = make_compat_mesh((8,), ("data",))
    rules = AxisRules(rules={"batch": "data"}, mesh=mesh)
    with use_rules(rules):
        sharded_engine = ReconEngine(backend="float", params=params)
        got = sharded_engine.reconstruct(reqs)
    out = {"n_devices": jax.device_count(),
           "match": all(
               np.allclose(r.t1_ms, g.t1_ms, rtol=1e-5, atol=1e-3)
               and np.allclose(r.t2_ms, g.t2_ms, rtol=1e-5, atol=1e-3)
               for r, g in zip(ref, got)),
           "voxels": sharded_engine.last_wave["total_voxels"]}
    print(json.dumps(out))
""")


def test_data_parallel_serving_smoke():
    proc = subprocess.run(
        [sys.executable, "-c", _DP_SUBPROC], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    assert out["match"], "sharded serving diverged from mesh-less serving"
    assert out["voxels"] == 512
